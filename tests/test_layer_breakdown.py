"""Every device millisecond to a layer and a pass (ISSUE 26,
docs/observability.md §5.1): the program's own reader on a recorded TPU
v5e capture and on a CPU capture of a real train step.

- (a) ``benchmarks/tests/data/toy.serve.xplane.pb.gz`` (one v5e chip, PR
  24's chip run; read only): events come with scopes, every op lands in a
  layer, the rows sum to the window, the idle row comes with the bounds on
  the capture's clock offset and the host's own dispatch and fetch time
  (no split by host activity: ``tests/test_attention_scopes.py`` (e)), and
  the numbers agree with the benchmark's own reducers.
- (b) a CPU capture of two ``make_train_step`` steps of a toy
  ``RingTransformer`` under remat: every pass appears, the ``train/*``
  scopes are the update, the chunked cross-entropy is ``loss and head``,
  backward and recompute are told apart.
"""

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from ring_attention_tpu.models import RingTransformer
from ring_attention_tpu.utils import make_train_step
from ring_attention_tpu.utils.profiling import (
    STAGES,
    _self_times,
    annotate,
    layer_breakdown,
    layer_of,
    read_capture,
    read_xplane_events,
    trace,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
V5E = os.path.join(REPO, "benchmarks", "tests", "data",
                   "toy.serve.xplane.pb.gz")
DECODE = ["bench/token", "bench/fetch"]
WINDOWS = [
    pytest.param(None, None, id="all-device-ops"),
    pytest.param("bench/prefill", None, id="prefill-span"),
    pytest.param(DECODE, "bench/token", id="decode-spans"),
    pytest.param("jit_decode_fn", "jit_decode_fn", id="decode-program"),
    pytest.param((43_000_000, 47_000_000), 4, id="nanoseconds"),
]


@pytest.fixture(scope="module")
def v5e():
    capture = read_capture(V5E)
    assert not capture.note, capture.note
    return capture


# ----------------------------------------------------------------------
# (a) the recorded v5e capture
# ----------------------------------------------------------------------


def test_v5e_events_carry_scopes(v5e):
    events, note = read_xplane_events(V5E)
    assert note == "" and events == v5e.ops
    assert len(events) > 1000
    assert {e.chip for e in events} == {0}
    assert {e.plane for e in events} == {"/device:TPU:0"}
    total = sum(e.self_ns for e in events)
    scoped = sum(e.self_ns for e in events if e.scope)
    assert scoped >= 0.95 * total
    assert all(0 <= e.self_ns <= e.dur_ns for e in events)
    # the program of every op is known: both jitted functions ran
    assert {name for _, name, _, _ in v5e.programs} == {
        "jit_prefill_fn", "jit_decode_fn"}
    # a weight's prefetch has no path of its own and takes its user's
    prefetch = [e for e in events if e.name.startswith("copy-done")]
    assert prefetch and all("/" in e.scope for e in prefetch)


@pytest.mark.parametrize("needle, layer", [
    ("ff_layers_", "feed-forward"),
    ("flash/fwd", "xla flash"),
    ("_project_qkv", "attention projections"),
    ("to_logits", "loss and head"),
    ("final_norm", "loss and head"),
    ("embed/", "embed"),
])
def test_v5e_scope_lands_in_layer(v5e, needle, layer):
    hits = [e for e in v5e.ops if needle in e.scope]
    assert hits, f"no op of the capture has {needle!r} in its scope"
    assert {e.layer for e in hits} == {layer}
    assert {e.pass_ for e in hits} == {"forward"}  # a server has no other


def test_v5e_kernels_go_by_their_own_name(v5e):
    """``flash kernels`` is the Pallas calls and nothing else, as the
    benchmark's ``^flash_`` metrics have it: what XLA runs beside a kernel
    (a mask's broadcast that took the kernel's path) is its layer's."""
    kernels = [e for e in v5e.ops if e.layer == "flash kernels"]
    assert {e.name.split(".")[0] for e in kernels} == {"flash_decode"}
    assert len(kernels) == 12 * 2  # tokens x layers
    beside = [e for e in v5e.ops
              if "flash_decode" in e.scope and e not in kernels]
    assert beside and {e.layer for e in beside} == {"attention projections"}


@pytest.mark.parametrize("window, per", WINDOWS)
def test_v5e_rows_sum_to_window(v5e, window, per):
    got = layer_breakdown(v5e, window, per=per)
    assert "note" not in got, got
    rows = got["rows"]
    assert sum(r["ms"] for r in rows) == pytest.approx(
        got["window_ms"], rel=1e-6)
    assert sum(r["share"] for r in rows) == pytest.approx(1.0, rel=1e-6)
    idle = rows[-1]
    assert idle["layer"] == "idle"
    # the same time by stage, each with its largest instructions
    assert sum(r["ms"] for r in got["stages"]) + idle["ms"] == pytest.approx(
        got["window_ms"], rel=1e-6)
    assert all(r["top"] and len(r["top"]) <= 5 for r in got["stages"])
    # a TPU capture's two clocks sit further apart than a launch takes
    # (PR 37): no split of the idle row, the bounds instead, and the
    # host's own time on the host's clock
    assert got["idle_host"] is None and got["idle_activity"] is None
    assert set(got["host_activity"]) == {"dispatch", "fetch"}
    lower, upper = got["offset_bounds_ms"]
    if window != (43_000_000, 47_000_000):  # between two launches: none
        assert lower < upper and upper - lower > 0.1
    # what matched no scope is counted and named, never dropped
    other = sum(r["ms"] for r in rows if r["layer"] == "other")
    assert other == pytest.approx(
        sum(ms for _, ms in got["other_ops"]), rel=1e-6)


def test_v5e_decode_window_reads_tokens_and_host(v5e):
    got = layer_breakdown(v5e, DECODE, per="bench/token")
    assert got["units"] == 12 and got["chip"] == 0
    layers = {r["layer"] for r in got["rows"]}
    assert {"flash kernels", "feed-forward", "attention projections",
            "loss and head", "embed", "idle"} <= layers
    assert "xla flash" not in layers  # the prefill's, outside this window
    # the toy's device is idle nearly all the time, waiting for the host.
    # Which of the host's events that is cannot be read off this capture:
    # twelve launches and fetches bound its clocks' offset only to 1.1 ms,
    # so the host's rows are the host's own time (np.asarray, open for the
    # whole step it waits for, is in neither)
    assert got["idle_activity"] is None
    lower, upper = got["offset_bounds_ms"]
    assert lower == pytest.approx(-0.1984, abs=1e-4)
    assert upper == pytest.approx(0.9167, abs=1e-4)
    act = got["host_activity"]
    assert act["dispatch"] == pytest.approx(0.3909, abs=1e-4)
    assert act["fetch"] == pytest.approx(0.1270, abs=1e-4)
    assert act["dispatch"] + act["fetch"] < [
        r for r in got["rows"] if r["layer"] == "idle"][0]["ms"]


def test_v5e_agrees_with_the_benchmarks_reducers(v5e):
    """The program's reader and the benchmark's read one file: device
    idle share, host gap per token, kernel time per token agree."""
    sys.path.insert(0, REPO)
    try:
        from benchmarks import reduce
    finally:
        sys.path.remove(REPO)
    bench = reduce.load_trace(V5E)
    run = {"units": {"token": 12}}
    got = layer_breakdown(v5e, DECODE, per="bench/token")
    rows = {r["layer"]: r for r in got["rows"]}
    assert 100 * rows["idle"]["share"] == pytest.approx(
        reduce.idle_share(bench, run, spans=DECODE), rel=1e-4)
    assert rows["idle"]["ms"] == pytest.approx(
        reduce.host_gap_ms(bench, run, spans=DECODE, per="token"), rel=1e-4)
    assert rows["flash kernels"]["ms"] == pytest.approx(
        reduce.op_time_ms(bench, run, "^flash_decode", per="token",
                          spans=DECODE), rel=2e-3)  # nanosecond rounding
    rest = sum(r["ms"] for r in got["rows"]
               if r["layer"] not in ("flash kernels", "idle"))
    assert rest == pytest.approx(
        reduce.op_time_ms(bench, run, ".", "^flash_", per="token",
                          spans=DECODE), rel=2e-3)


@pytest.mark.parametrize("spans, want", [
    ([(0, 10), (2, 4), (4, 6)], [6, 2, 2]),          # a while and its body
    ([(0, 10), (10, 20)], [10, 10]),                 # back to back
    ([(0, 10), (5, 15)], [5, 10]),                   # overlapping: the union
    ([(0, 10), (1, 9), (2, 3)], [2, 7, 1]),          # nested twice
    ([], []),
])
def test_self_times_sum_to_the_union(spans, want):
    assert _self_times(spans) == want


def test_no_capture_is_a_note_not_an_error(tmp_path):
    events, note = read_xplane_events(str(tmp_path))
    assert events == [] and "no .xplane.pb" in note
    assert "note" in layer_breakdown(str(tmp_path))
    got = layer_breakdown(V5E, "no/such/span")
    assert "no/such/span" in got["note"]


def test_stages_table_names_every_layer_once_per_needle():
    needles = [row[0] for row in STAGES]
    assert len(needles) == len(set(needles))
    assert {row[3] for row in STAGES} == {
        "ring", "flash kernels", "xla flash", "optimizer", "loss and head",
        "feed-forward", "attention projections", "embed", "experts",
        "router", "latent attention", "state space", "residual"}
    assert {row[4] for row in STAGES} <= {None, "backward", "update"}
    # a kernel is a kernel wherever it is called from; the rest by scope
    assert layer_of("flash_partials_tile.3",
                    "jit(step)/attn_layers_0/ring/hop2/pallas_call") == (
        "flash kernels", "forward")
    assert layer_of("fusion.7", "jit(step)/attn_layers_0/ring/hop2/mul")[
        0] == "ring"
    assert layer_of("fusion.9", "") == ("other", "forward")
    # the routed layer's parts go by their own scopes, not the module's
    path = "jit(f)/RingTransformer.prefill/ff_layers_3/moe/{}/x"
    assert layer_of("ragged-dot-none.2", path.format("experts"))[0] == "experts"
    assert layer_of("fusion.4", path.format("router"))[0] == "router"
    assert layer_of("fusion.5", "jit(f)/attn_layers_1.prefill/attn/gate/mul")[
        0] == "attention projections"
    # so do a latent layer's: its projections by scope, its kernel by name,
    # the group choice inside the router's scope
    step = "jit(f)/RingTransformer.decode_step/attn_layers_2.decode_step/{}"
    for scope in ("attn/latent_q/dot", "attn/latent_kv/dynamic_update_slice",
                  "attn/expand/dot", "attn/absorb/dot_general"):
        assert layer_of("fusion.6", step.format(scope))[0] == "latent attention"
    assert layer_of("flash_decode_latent.2", step.format("pallas_call")) == (
        "flash kernels", "forward")
    assert layer_of("fusion.8", path.format("router/moe/groups"))[0] == "router"
    # and a Mamba-2 mixer's: its six scopes by path (its norm sits inside
    # ssm/in_proj, so nothing of it falls to attn_layers_), its kernel by name
    for call, scope in (("prefill", "ssm/in_proj/prenorm/mul"),
                        ("prefill", "ssm/conv/add"),
                        ("prefill", "ssm/scan/while/body/dot_general"),
                        ("decode_step", "ssm/step/exp"),
                        ("decode_step", "ssm/gate_norm/gate_norm/mul"),
                        ("decode_step", "ssm/out_proj/dot_general")):
        assert layer_of("fusion.9", f"jit(f)/attn_layers_0.{call}/{scope}")[
            0] == "state space"
    assert layer_of("ssm_decode_step.3", step.format("ssm/step/pallas_call")
                    ) == ("state space", "forward")
    assert layer_of("ssm_decode_step.3", "")[0] == "state space"
    assert layer_of("fusion.8", step.format("to_out/dot"))[
        0] == "attention projections"


def test_trace_report_prints_the_layer_table_without_a_metrics_dir():
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "trace_report.py"),
         "--xprof", V5E, "--window", "bench/token,bench/fetch",
         "--per", "bench/token"],
        capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == 0, proc.stderr
    assert "layer and pass: bench/token,bench/fetch x12" in proc.stdout
    assert "flash kernels" in proc.stdout and "idle" in proc.stdout
    assert "no split by host activity" in proc.stdout
    assert "host activity on the host's clock: dispatch" in proc.stdout
    assert "stage and pass: bench/token,bench/fetch" in proc.stdout
    assert "per-stage device time" in proc.stdout
    # with no window named: one table per program
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "trace_report.py"),
         "--xprof", V5E], capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == 0, proc.stderr
    assert "layer and pass: jit_prefill_fn x1" in proc.stdout
    assert "layer and pass: jit_decode_fn x12" in proc.stdout


# ----------------------------------------------------------------------
# (b) a CPU capture of a real train step
# ----------------------------------------------------------------------


@pytest.fixture(scope="module")
def train_capture(tmp_path_factory):
    """Two steps of ``make_train_step`` (two micro-batches, clipping, the
    non-finite guard, AdamW) over a toy ``RingTransformer`` with remat,
    chunked cross-entropy and chunked feed-forward.  Compiled with the
    persistent cache off: its key leaves metadata out, so an executable
    stored before a scope was renamed would come back with the old names."""
    from jax.experimental.compilation_cache import compilation_cache

    model = RingTransformer(
        num_tokens=64, dim=32, depth=2, heads=2, dim_head=16, kv_heads=1,
        causal=True, rotary=True, bucket_size=16, use_ring=False,
        remat=True, remat_policy="save_attn", loss_chunk_size=16,
        ff_chunk_size=16)
    tokens = jnp.asarray(
        np.random.default_rng(0).integers(0, 64, (2, 33)), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), tokens[:, :-1])
    optimizer = optax.adamw(1e-3)
    opt_state = optimizer.init(params)
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        step = make_train_step(
            lambda p, t: model.apply(p, t, return_loss=True), optimizer,
            accum_steps=2, clip_grad_norm=1.0, jit_donate=True)
        params, opt_state, loss = step(params, opt_state, tokens)
        jax.block_until_ready(loss)  # compiled and warm before the trace
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
        compilation_cache.reset_cache()
    logdir = str(tmp_path_factory.mktemp("train_xprof"))
    with trace(logdir):
        for _ in range(2):
            with annotate("train/step"):
                params, opt_state, loss = step(params, opt_state, tokens)
                jax.block_until_ready(loss)
    capture = read_capture(logdir)
    assert not capture.note, capture.note
    return capture


def test_train_step_every_pass_appears(train_capture):
    got = layer_breakdown(train_capture, "train/step", per="train/step")
    assert got["units"] == 2
    rows = got["rows"]
    assert sum(r["ms"] for r in rows) == pytest.approx(
        got["window_ms"], rel=1e-6)
    assert {r["pass"] for r in rows if r["layer"] not in ("idle", "other")
            } == {"forward", "recompute", "backward", "update"}
    cells = {(r["layer"], r["pass"]) for r in rows if r["ms"] > 0}
    for layer in ("feed-forward", "attention projections"):
        assert {(layer, "forward"), (layer, "recompute"),
                (layer, "backward")} <= cells
    # the chunked cross-entropy makes its gradient in its forward scan:
    # nothing of it is recomputed
    assert {("loss and head", "forward"),
            ("loss and head", "backward")} <= cells
    assert ("loss and head", "recompute") not in cells
    assert ("optimizer", "update") in cells
    assert ("embed", "forward") in cells and ("embed", "backward") in cells
    # save_attn keeps the attention output: flash runs forward and backward
    assert ("xla flash", "forward") in cells
    assert ("xla flash", "backward") in cells
    assert ("xla flash", "recompute") not in cells
    # by count, not by time: a CPU's timings under a loaded test run say
    # nothing (the residual adds and loop-carried copies have no scope)
    other = sum(r["ops"] for r in rows if r["layer"] == "other")
    assert other < 0.1 * sum(r["ops"] for r in rows), got["other_ops"]


@pytest.mark.parametrize("needle, layer, passes", [
    ("train/optimizer", "optimizer", {"update"}),
    ("train/clip", "optimizer", {"update"}),
    ("train/accumulate", "optimizer", {"update"}),
    ("_chunked_ce", "loss and head", {"forward", "backward"}),
    ("loss/nll", "loss and head", {"forward"}),
    ("loss/grad", "loss and head", {"forward"}),
    ("final_norm", "loss and head", {"forward", "backward"}),
    ("ff_layers_", "feed-forward", {"forward", "recompute", "backward"}),
])
def test_train_step_scope_lands_in_layer_and_pass(
        train_capture, needle, layer, passes):
    hits = [e for e in train_capture.ops if needle in e.scope]
    assert hits, f"no op of the step has {needle!r} in its scope"
    assert {e.layer for e in hits} == {layer}
    assert {e.pass_ for e in hits} == passes


def test_train_step_backward_and_recompute_are_told_apart(train_capture):
    """``nn.remat`` runs a layer's forward again inside the backward:
    JAX writes ``rematted_computation`` into those paths and
    ``transpose(jvp(...))`` into the whole backward."""
    by_pass = {}
    for e in train_capture.ops:
        if e.layer == "feed-forward":
            by_pass.setdefault(e.pass_, []).append(e.scope)
    assert all("rematted_computation" in s for s in by_pass["recompute"])
    assert all("transpose(" in s and "rematted_computation" not in s
               for s in by_pass["backward"])
    assert all("transpose(" not in s for s in by_pass["forward"])
