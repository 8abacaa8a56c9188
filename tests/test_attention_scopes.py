"""The attention module names its own inside (ISSUE 37,
docs/observability.md §5.1): ``attn/qkv_proj``, ``attn/heads``,
``attn/rotary``, ``attn/cache_write``, ``attn/out_proj``,
``attn/kernel_io``, the routed layer's ``moe/combine_sum`` and the stack
walker's ``block/residual``.

- (a) the compiled programs of the four family toys carry every scope the
  family has, in the forward, the loss's backward, ``prefill`` and
  ``decode_step``;
- (b) the program's table (``utils/profiling.py::STAGES``) gives each a
  stage and a layer, behind the rows that must keep theirs;
- (c) the six metric files that read them are well formed against the
  table and the manifest;
- (d) a CPU capture of a real train step has an operation in each new stage,
  forward and backward, and nothing of the model is left in ``other``;
- (e) ``layer_breakdown`` splits the idle row by host activity only where
  the capture's two clocks are known to agree.

A scope is metadata: the twelve lowered-text digests of ``tests/test_mla.py``
and ``tests/test_ssm.py`` prove that no program moved.
"""

import functools
import json
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from ring_attention_tpu.models import ModelConfig, RingTransformer, moe
from ring_attention_tpu.utils import profiling
from ring_attention_tpu.utils.profiling import (
    ALIGNED_NS,
    HOST_ACTIVITIES,
    STAGES,
    Capture,
    HostEvent,
    OpEvent,
    layer_breakdown,
    layer_of,
    read_capture,
    stage_of,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
from benchmarks import reduce  # noqa: E402
from tests.test_layer_breakdown import V5E, train_capture  # noqa: E402,F401
from tests.test_mla import AFMOE_TOY, STARCODER2_TOY  # noqa: E402
from tests.test_mla import TINY as DOTS_TOY  # noqa: E402
from tests.test_ssm import TINY as GRANITE_TOY  # noqa: E402

TOYS = {"starcoder2": STARCODER2_TOY, "afmoe": AFMOE_TOY,
        "dots_vlm": DOTS_TOY, "granitemoehybrid": GRANITE_TOY}
CALLS = ("forward", "loss", "prefill", "decode_step")
SCOPES = ("attn/qkv_proj", "attn/heads", "attn/rotary", "attn/cache_write",
          "attn/out_proj", "attn/kernel_io", "moe/combine_sum",
          "block/residual")


# ----------------------------------------------------------------------
# (a) the compiled programs carry the scopes
# ----------------------------------------------------------------------


def has(family: str, call: str, scope: str) -> bool:
    """Whether ``family``'s program for ``call`` runs anything in ``scope``."""
    return {
        # granite's attention layer has no positional encoding
        "attn/rotary": family != "granitemoehybrid",
        # a latent layer's write is attn/latent_kv's
        "attn/cache_write": (call in ("prefill", "decode_step")
                             and family != "dots_vlm"),
        # the absorbed decode step has no heads-major layout to make
        "attn/heads": (family, call) != ("dots_vlm", "decode_step"),
        # a decode step calls its kernel itself, not through _attend
        "attn/kernel_io": call != "decode_step",
        # the pass loop is a while_loop: forward programs only
        "moe/combine_sum": family != "starcoder2" and call != "loss",
    }.get(scope, True)


@functools.lru_cache(maxsize=None)
def op_names(family: str, call: str) -> tuple[str, ...]:
    """The ``op_name`` of every instruction of the compiled CPU program.
    Compiled with the persistent cache off (its key leaves metadata out: an
    executable stored before a scope was named would come back without it)
    and, but for the loss's backward, with two expert passes a routed layer
    (``moe.PASS_ROWS`` small), so that the pass loop's add is there."""
    from jax.experimental.compilation_cache import compilation_cache

    model = RingTransformer.from_config(
        ModelConfig.from_dict(TOYS[family]), mesh=None, use_ring=False,
        bucket_size=4)
    tokens = jnp.zeros((2, 20), jnp.int32)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0), tokens)
    cache = jax.eval_shape(
        lambda: model.apply({}, 2, 32, method=RingTransformer.init_cache))
    fn, args = {
        "forward": (lambda p, t: model.apply(p, t), (params, tokens)),
        "loss": (jax.grad(lambda p, t: model.apply(p, t, return_loss=True)),
                 (params, tokens)),
        "prefill": (lambda p, t, c: model.apply(
            p, t, c, method=RingTransformer.prefill),
            (params, tokens[:, :14], cache)),
        "decode_step": (lambda p, t, c, i: model.apply(
            p, t, c, i, method=RingTransformer.decode_step),
            (params, tokens[:, 0], cache, jnp.int32(14))),
    }[call]
    rows = moe.PASS_ROWS
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        moe.PASS_ROWS = rows if call == "loss" else 4
        text = jax.jit(fn).lower(*args).compile().as_text()
    finally:
        moe.PASS_ROWS = rows
        jax.config.update("jax_enable_compilation_cache", True)
        compilation_cache.reset_cache()
    return tuple(re.findall(r'op_name="([^"]*)"', text))


@pytest.mark.parametrize("scope", SCOPES)
@pytest.mark.parametrize("call", CALLS)
@pytest.mark.parametrize("family", sorted(TOYS))
def test_compiled_program_carries_the_scope(family, call, scope):
    names = [n for n in op_names(family, call) if scope in n]
    if not has(family, call, scope):
        assert not names, names[:3]
        return
    assert names, f"no instruction of {family}.{call} is inside {scope}"
    # a module's own scope sits inside the module's path
    where = "_blocks" if scope == "block/residual" else (
        "ff_layers_" if scope.startswith("moe/") else "attn_layers_")
    assert all(where in n for n in names), [
        n for n in names if where not in n][:3]
    if call == "loss" and scope.startswith("attn/"):
        # the backward keeps the name: JAX wraps the path, it does not drop it
        assert any("transpose(" in n for n in names)


# ----------------------------------------------------------------------
# (b) the program's table
# ----------------------------------------------------------------------

NEW_ROWS = [
    ("attn/qkv_proj", "qkv product", "attention projections"),
    ("attn/heads", "head layout", "attention projections"),
    ("attn/rotary", "rotary", "attention projections"),
    ("attn/cache_write", "cache write", "attention projections"),
    ("attn/out_proj", "output product", "attention projections"),
    ("attn/kernel_io", "kernel operand layout", "attention projections"),
    ("moe/combine_sum", "expert combine sum", "experts"),
    ("block/residual", "residual add", "residual"),
    ("_blocks/", "stack walker", "residual"),
]


@pytest.mark.parametrize("needle, label, layer", NEW_ROWS)
def test_new_stage_row(needle, label, layer):
    rows = [r for r in STAGES if r[0] == needle]
    assert [(r[1], r[3], r[4]) for r in rows] == [(label, layer, None)]
    home = {"attention projections": "attn_layers_3.prefill",
            "experts": "ff_layers_3/RoutedFeedForward._routed",
            "residual": "RingTransformer._blocks"}[layer]
    inside = needle if needle != "_blocks/" else "add_any"
    path = f"jit(f)/RingTransformer.prefill/{home}/{inside}/mul"
    assert stage_of("fusion.7", path) == (label, "compute")
    assert layer_of("fusion.7", path) == (layer, "forward")
    assert layer_of("fusion.7", f"transpose(jvp({path}))") == (
        layer, "backward")
    if needle.startswith("attn/"):
        # a latent layer's rotated positional keys stay latent attention's,
        # as latent.projection_* reads them; a kernel goes by its own name
        latent = path.replace(needle, f"attn/latent_kv/{needle}")
        assert layer_of("fusion.7", latent)[0] == "latent attention"
        assert layer_of("flash_fwd_tile.2", path) == (
            "flash kernels", "forward")
        # and no accepted needle or exclusion hides in the name
        assert not any(word in needle for word in (
            "flash", "embed", "moe/", "ssm", "loss/", "train/", "ring/",
            "attn/latent", "attn/expand", "attn/absorb", "attn/gate",
            "attn/qk_norm"))


def test_new_rows_stand_where_no_layer_row_moves():
    needles = [row[0] for row in STAGES]
    at = needles.index
    attn = [n for n, _, layer in NEW_ROWS if n.startswith("attn/")]
    # behind the latent and state-space rows, ahead of the module's catch-all
    assert max(at(n) for n in needles if n.startswith(("attn/latent", "ssm")
               )) < min(map(at, attn))
    assert max(map(at, attn)) < at("attn_layers_")
    # whatever the call runs inside another scope keeps that scope's stage
    assert at("attn/rotary") < at("attn/kernel_io")
    # the sum's name holds the combine's needle: it stands ahead of it
    assert at("moe/combine_sum") < at("moe/combine")
    assert at("attn_layers_") < at("block/residual") < at("_blocks/") < at(
        "embed")
    inside = "jit(f)/ff_layers_1/_routed/while/body/moe/combine_sum/add"
    assert stage_of("add_add_fusion.3", inside)[0] == "expert combine sum"
    assert stage_of("fusion.3", inside.replace("_sum", ""))[0] == (
        "expert combine")
    # a rotation inside the sequence-parallel call is rotary, its ring hops
    # the ring's
    ring = "jit(step)/attn_layers_0/attn/kernel_io/shard_map/{}"
    assert stage_of("fusion.1", ring.format("attn/rotary/mul"))[0] == "rotary"
    assert layer_of("fusion.1", ring.format("ring/hop1/mul"))[0] == "ring"
    assert stage_of("copy.1", ring.format("reshape"))[0] == (
        "kernel operand layout")


# ----------------------------------------------------------------------
# (c) the metric files
# ----------------------------------------------------------------------

METRICS = os.path.join(REPO, "benchmarks", "metrics")
NEW_METRICS = [
    "model.prefill_attention_products_ms", "model.prefill_head_layout_ms",
    "model.prefill_rotary_ms", "step.attention_products_ms",
    "step.attention_head_layout_ms", "step.attention_rotary_ms",
]


@pytest.mark.parametrize("name", NEW_METRICS)
def test_new_metric_file(name):
    with open(os.path.join(METRICS, name + ".json")) as f:
        spec = json.load(f)
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    entry = [m for m in manifest["per_layer"] if m["name"] == name]
    assert len(entry) == 1 and entry[0] in manifest["per_layer"][-6:]
    entry = entry[0]
    assert (spec["name"], spec["unit"], spec["source"]) == (
        name, "ms", "device_trace") == (
        entry["name"], entry["unit"], entry["source"])
    assert entry["better"] == "lower"
    assert (spec["layer"], spec["moves"]) == (entry["layer"], entry["moves"])
    assert spec["reducer"] == "scope_time_ms" in reduce.REDUCERS
    args = spec["arguments"]
    needles = [row[0] for row in STAGES]
    assert args["scopes"] and set(args["scopes"]) <= set(needles)
    # every row of the program's table ahead of its layer's first is
    # excluded (by its needle or by a prefix of it, as `moe/` stands for
    # its six; a kernel by its name), exactly as the lump's file lists
    # them: its layer's own rows are siblings, no path holds two of them
    layers = [row[3] for row in STAGES]
    assert {layers[needles.index(s)] for s in args["scopes"]} == {
        "attention projections"}
    ahead = needles[:layers.index("attention projections")]
    kernels = re.compile(args["exclude_regex"])
    missed = [n for n, layer in zip(ahead, layers)
              if not any(x in n for x in args["exclude_scopes"])
              and not kernels.search(n) and layer != "flash kernels"]
    assert not missed, missed
    lump = ("step.attention_projection_ms" if name.startswith("step.")
            else "model.prefill_projection_ms")
    with open(os.path.join(METRICS, lump + ".json")) as f:
        lump = json.load(f)
    for key in ("exclude_scopes", "exclude_regex", "spans"):
        assert args[key] == lump["arguments"][key]
    assert args.get("per") == lump["arguments"].get("per")
    # its cells are cells of the manifest that report the metric it moves
    moved = [m for m in manifest["end_to_end"] if m["name"] == spec["moves"]]
    assert len(moved) == 1
    cells = {w["name"] for w in manifest["workloads"]}
    assert set(entry["workloads"]) <= set(moved[0]["workloads"]) <= cells
    lumps_cells = [m for m in manifest["per_layer"]
                   if m["name"] == lump["name"]][0]["workloads"]
    rotary = name.endswith("rotary_ms")
    assert entry["workloads"] == [
        c for c in lumps_cells if not (rotary and c.startswith("granite"))]


# ----------------------------------------------------------------------
# (d) a CPU capture of a real train step
# ----------------------------------------------------------------------


@pytest.mark.parametrize("pass_", ["forward", "backward"])
@pytest.mark.parametrize("stage", [
    "rotary", "head layout", "qkv product", "output product"])
def test_train_step_has_the_stage_in_the_pass(train_capture, stage, pass_):  # noqa: F811
    hits = [o for o in train_capture.ops
            if o.stage == stage and o.pass_ == pass_]
    assert hits, f"no operation of the step is {stage} / {pass_}"
    assert {o.layer for o in hits} == {"attention projections"}


@pytest.mark.parametrize("pass_", ["forward", "recompute", "backward"])
def test_the_permutation_product_is_rotary_in_every_pass(train_capture, pass_):  # noqa: F811
    """ISSUE 38: the half rotation is a product by a signed permutation
    (``ops/rotary.py``).  The product, and whatever is fused with it, keeps
    ``attn/rotary`` in the forward, in remat's recompute and in the
    backward, so that ``*_rotary_ms`` reads what took rotate_half's place."""
    ops = [o for o in train_capture.ops
           if o.pass_ == pass_ and "attn/rotary/" in o.scope]
    assert ops, f"no operation of the step's {pass_} is inside attn/rotary"
    assert {(o.layer, o.stage) for o in ops} == {
        ("attention projections", "rotary")}
    assert any(o.scope.endswith("attn/rotary/dot_general") for o in ops), {
        o.scope.rsplit("/", 1)[-1] for o in ops}


def test_train_step_leaves_nothing_of_the_model_in_other(train_capture):  # noqa: F811
    """The stack walker's adds, the residual stream's gradient sums and
    remat's copies are the layer ``residual``.  What the CPU step still has
    in ``other`` is utils/train.py's accumulation loop (its carried copies
    and zeroed accumulators, in no scope) and the loss entry's token slice."""
    other = [o for o in train_capture.ops if o.layer == "other"]
    named = ("_blocks", "attn_layers_", "ff_layers_", "post_attn_norms_")
    assert not [o.scope for o in other if any(n in o.scope for n in named)]
    residual = {(o.stage, o.pass_) for o in train_capture.ops
                if o.layer == "residual"}
    assert ("residual add", "forward") in residual
    assert ("stack walker", "backward") in residual
    got = layer_breakdown(train_capture, "train/step", per="train/step")
    stages = {(r["layer"], r["stage"], r["pass"]): r for r in got["stages"]}
    assert sum(r["ms"] for r in got["stages"]) + got["rows"][-1][
        "ms"] == pytest.approx(got["window_ms"], rel=1e-6)
    row = stages["attention projections", "rotary", "forward"]
    assert row["ms"] == pytest.approx(sum(ms for _, ms in row["top"]))
    # a CPU capture's operations are host events: one clock, so the split
    assert got["offset_bounds_ms"] == [0.0, 0.0]
    assert got["idle_activity"] is not None


# ----------------------------------------------------------------------
# (e) the idle row and the capture's two clocks
# ----------------------------------------------------------------------

DECODE = ["bench/token", "bench/fetch"]


def shifted(capture: Capture, ns: int) -> Capture:
    """The capture with its device plane's clock moved by ``ns``."""
    return capture._replace(
        ops=[o._replace(start_ns=o.start_ns + ns) for o in capture.ops],
        programs=[(c, n, s + ns, d) for c, n, s, d in capture.programs])


@pytest.fixture(scope="module")
def v5e():
    capture = read_capture(V5E)
    assert not capture.note, capture.note
    return capture


@pytest.mark.parametrize("shift_ms", [-0.5, 0.0, 0.5])
def test_v5e_capture_gets_no_idle_split_wherever_its_clock_sits(v5e, shift_ms):
    ns = int(shift_ms * 1e6)
    base = layer_breakdown(v5e, DECODE, per="bench/token")
    got = layer_breakdown(shifted(v5e, ns), DECODE, per="bench/token")
    lower, upper = got["offset_bounds_ms"]
    # a launch below, a fetch above: over a millisecond apart on this chip
    assert upper - lower > 10 * ALIGNED_NS * 1e-6
    assert got["idle_activity"] is None and got["idle_host"] is None
    # the bounds follow the clock, the host's own rows do not see it
    assert lower == pytest.approx(base["offset_bounds_ms"][0] - shift_ms,
                                  abs=1e-6)
    assert upper == pytest.approx(base["offset_bounds_ms"][1] - shift_ms,
                                  abs=1e-6)
    assert got["host_activity"] == base["host_activity"]
    assert got["host_activity"]["dispatch"] > got["host_activity"][
        "fetch"] > 0
    # what the benchmark reads from the same file on the host's clock
    bench = reduce.load_trace(V5E)
    run = {"units": {"token": 12}}
    for activity, exclude in (("dispatch", None), ("fetch", "dispatch")):
        assert got["host_activity"][activity] == pytest.approx(
            reduce.host_activity_ms(
                bench, run, activity=list(dict(HOST_ACTIVITIES)[activity]),
                exclude=exclude and list(dict(HOST_ACTIVITIES)[exclude]),
                spans=DECODE, per="token"), rel=1e-6)


@pytest.mark.parametrize("shift_ms", [-0.5, 0.5])
def test_trace_report_prints_the_same_host_rows_for_a_shifted_capture(
        v5e, tmp_path, shift_ms, monkeypatch, capsys):
    sys.path.insert(0, os.path.join(REPO, "tools"))
    try:
        import trace_report
    finally:
        sys.path.remove(os.path.join(REPO, "tools"))
    printed = []
    for capture in (v5e, shifted(v5e, int(shift_ms * 1e6))):
        out: list[str] = []
        trace_report.layer_report(capture, out, [DECODE], "bench/token", None)
        printed.append(out)
    rows = [[line for line in out if "host activity" in line
             or "idle by" in line] for out in printed]
    # no split either way (the idle line carries the bounds, which follow
    # the clock), and the host's own rows to the digit
    for idle, host in rows:
        assert idle.endswith("no split by host activity")
    assert rows[0][1] == rows[1][1]
    assert rows[0][1].startswith("  host activity on the host's clock: "
                                 "dispatch 0.39")
    assert any(line.startswith("  stage and pass: ") for line in printed[0])


def synthetic(offset_ns: int, launch_ns: int = 20_000,
              fetch_ns: int = 30_000) -> Capture:
    """Three steps of a program on a device whose clock reads
    ``offset_ns`` less than the host's: the host calls, the device starts
    ``launch_ns`` later and runs 1 ms, the blocking fetch ends ``fetch_ns``
    after that."""
    ops, host, programs = [], [], []
    for i in range(3):
        t = 10_000_000 * i
        start = t + launch_ns - offset_ns
        programs.append((0, "jit_step", start, 1_000_000))
        ops.append(OpEvent("/device:TPU:0", "XLA Ops", "fusion.1",
                           "jit(step)/ff_layers_0/dot", "feed-forward",
                           "compute", start, 1_000_000, 0, 1_000_000,
                           "feed-forward", "forward"))
        host += [HostEvent("PjitFunction(jit(step))", t, 15_000),
                 HostEvent("np.asarray(jax.Array)", t + 16_000,
                           launch_ns + 1_000_000 + fetch_ns - 16_000),
                 HostEvent("bench/step", t, 1_100_000)]
    return Capture(ops, host, programs)


@pytest.mark.parametrize("offset_ns", [0, 400_000, -700_000])
def test_offset_bounds_hold_the_true_offset(offset_ns):
    got = layer_breakdown(synthetic(offset_ns), "bench/step",
                          per="bench/step")
    lower, upper = got["offset_bounds_ms"]
    assert lower == pytest.approx((offset_ns - 20_000) * 1e-6)
    assert upper == pytest.approx((offset_ns + 30_000) * 1e-6)
    # 0.05 ms between them: close enough for a split
    assert got["idle_activity"] is not None
    assert sum(got["idle_activity"].values()) == pytest.approx(
        got["rows"][-1]["ms"])
    wide = layer_breakdown(synthetic(offset_ns, launch_ns=300_000),
                           "bench/step", per="bench/step")
    assert wide["idle_activity"] is None
    assert wide["host_activity"]["dispatch"] == pytest.approx(0.015)


def test_a_capture_that_began_inside_a_call_gives_no_bounds():
    capture = synthetic(0)
    capture = capture._replace(host=capture.host[3:])  # the first call lost
    got = layer_breakdown(capture, "bench/step", per="bench/step")
    assert got["offset_bounds_ms"] == [None, None]
    assert got["idle_activity"] is None


def test_np_asarray_is_no_host_activity():
    """It is open for the whole device step it waits for: counted as a
    fetch it would turn a step's device time into fetch time."""
    assert not [n for _, needles in HOST_ACTIVITIES for n in needles
                if "asarray" in n]
    assert profiling._activity_of(["np.asarray(jax.Array)"]) == "other"


def test_trace_report_cli_says_what_a_scope_cannot_see():
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "trace_report.py"),
         "--xprof", V5E, "--window", "bench/prefill"],
        capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr
    assert "stage and pass: bench/prefill" in proc.stdout
    assert "one fusion and has one path" in proc.stdout
    assert "host clock = device clock + [" in proc.stdout
