"""Telemetry spine: in-graph metrics, JSONL logging, trace annotations,
MFU accounting, and the no-extra-collectives HLO pin.

The contract under test (ISSUE 4 / docs/observability.md): telemetry is
ADDITIVE — the instrumented train step computes its metrics from values
the step already produces, so the compiled program issues the same
collective sequence as the uninstrumented one, and every logged number is
either exact (loss, grad_norm, counters), measured (step latency), or
analytic-and-documented-as-such (MFU, hop/byte accounting).
"""

import os
import subprocess
import sys
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from ring_attention_tpu.utils import (
    MetricsLogger,
    Telemetry,
    achieved_mfu,
    attention_logit_summaries,
    device_peak_tflops,
    flash_attention_flops,
    init_step_stats,
    init_train_metrics,
    make_train_step,
    read_metrics,
    ring_comms_accounting,
    transformer_step_flops,
)
from ring_attention_tpu.utils import resilience
from ring_attention_tpu.utils.profiling import StepTimer
from ring_attention_tpu.utils.telemetry import SCHEMA_VERSION, telemetry

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TRACE_REPORT = os.path.join(REPO, "tools", "trace_report.py")


@pytest.fixture(autouse=True)
def _clean_telemetry():
    telemetry.drain_events()
    yield
    telemetry.drain_events()


def _quad_step(**kwargs):
    """Tiny quadratic problem: loss/grads are hand-checkable."""
    opt = optax.sgd(0.1)

    def loss_fn(p, x):
        return ((p["w"] * x) ** 2).mean()

    params = {"w": jnp.asarray([1.0, 2.0])}
    step = make_train_step(loss_fn, opt, collect_metrics=True, **kwargs)
    return step, params, opt.init(params), jnp.asarray([1.0, 1.0])


# ----------------------------------------------------------------------
# In-graph stats: parity under jit, donated and non-donated
# ----------------------------------------------------------------------


def test_train_metrics_parity_under_jit():
    step, params, opt_state, x = _quad_step(skip_nonfinite=True,
                                            clip_grad_norm=10.0)
    m0 = init_train_metrics()
    eager = step(params, opt_state, m0, x)
    jitted = jax.jit(step)(params, opt_state, m0, x)
    for a, b in zip(jax.tree.leaves(eager), jax.tree.leaves(jitted)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6)
    _, _, m, loss = jitted
    # loss = mean((w*x)^2) = (1 + 4)/2; grad = 2*w*x^2/2 = w -> norm sqrt(5)
    assert float(loss) == pytest.approx(2.5)
    assert float(m.grad_norm) == pytest.approx(np.sqrt(5.0), rel=1e-6)
    assert bool(m.step_ok) and int(m.skipped) == 0 and int(m.nonfinite) == 0


def test_train_metrics_parity_donated():
    step, params, opt_state, x = _quad_step(skip_nonfinite=True,
                                            jit_donate=True)
    ref_step, p2, s2, _ = _quad_step(skip_nonfinite=True)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")  # CPU can't honor donation
        got = step(params, opt_state, init_train_metrics(), x)
    want = jax.jit(ref_step)(p2, s2, init_train_metrics(), x)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6)


def test_train_metrics_guarded_skip_counts():
    """Poisoned step under the guard: params bit-identical, skipped and
    nonfinite both count, loss still reports the offending value."""
    opt = optax.sgd(0.1)
    loss_fn = resilience.faulty_loss(
        lambda p, x: ((p["w"] * x) ** 2).mean()
    )
    step = jax.jit(make_train_step(
        loss_fn, opt, collect_metrics=True, skip_nonfinite=True
    ))
    params = {"w": jnp.asarray([1.0, 2.0])}
    opt_state = opt.init(params)
    x = jnp.ones((2,))
    m = init_train_metrics()
    params, opt_state, m, _ = step(params, opt_state, m, x)
    with resilience.inject("nan_loss"):
        p_after, opt_state, m, loss = step(params, opt_state, m, x)
    assert not bool(m.step_ok)
    assert int(m.skipped) == 1 and int(m.nonfinite) == 1
    assert np.isnan(float(loss))
    np.testing.assert_array_equal(
        np.asarray(p_after["w"]), np.asarray(params["w"])
    )
    # recovery: counters hold, step_ok returns
    p2, _, m, _ = step(p_after, opt_state, m, x)
    assert bool(m.step_ok) and int(m.skipped) == 1 and int(m.nonfinite) == 1
    assert not np.array_equal(np.asarray(p2["w"]), np.asarray(p_after["w"]))


def test_train_metrics_unguarded_counts_nonfinite():
    """Without the guard the update is applied anyway — but the nonfinite
    counter still fires: the 'run is corrupting itself' alarm."""
    opt = optax.sgd(0.1)
    loss_fn = resilience.faulty_loss(
        lambda p, x: ((p["w"] * x) ** 2).mean()
    )
    step = jax.jit(make_train_step(loss_fn, opt, collect_metrics=True))
    params = {"w": jnp.asarray([1.0, 2.0])}
    m = init_train_metrics()
    with resilience.inject("nan_loss"):
        params, _, m, _ = step(params, opt.init(params), m, jnp.ones((2,)))
    assert bool(m.step_ok)  # applied (no guard)
    assert int(m.skipped) == 0 and int(m.nonfinite) == 1


def test_init_train_metrics_resume_counters():
    m = init_train_metrics(skipped=7, nonfinite=9)
    assert int(m.skipped) == 7 and int(m.nonfinite) == 9


# ----------------------------------------------------------------------
# Telemetry registry: in-graph observation
# ----------------------------------------------------------------------


def test_telemetry_observe_inside_jit():
    tel = Telemetry()

    @jax.jit
    def fwd(x):
        with tel.collecting() as col:
            y = (x * 2).sum()
            tel.observe("y_sum", y)
            tel.observe("lazy", lambda: y + 1)  # thunk form
        return y, col.values()

    y, vals = fwd(jnp.ones((4,)))
    assert float(vals["y_sum"]) == 8.0 and float(vals["lazy"]) == 9.0


def test_telemetry_observe_noop_when_inactive():
    tel = Telemetry()
    calls = []
    tel.observe("x", lambda: calls.append(1))  # thunk must NOT run
    assert not calls and not tel.active()


# ----------------------------------------------------------------------
# MetricsLogger: schema round-trip, atomic append under a killed writer
# ----------------------------------------------------------------------


def test_metrics_logger_roundtrip(tmp_path):
    with MetricsLogger(str(tmp_path)) as logger:
        logger.log(0, loss=1.5, grad_norm=jnp.float32(2.0), step_ok=True)
        logger.log(5, loss=1.25, tokens_per_sec=100)
    rows = read_metrics(str(tmp_path))
    assert [r["step"] for r in rows] == [0, 5]
    assert all(r["schema"] == SCHEMA_VERSION for r in rows)
    assert rows[0]["loss"] == 1.5 and rows[0]["grad_norm"] == 2.0
    assert rows[0]["step_ok"] is True
    assert rows[1]["tokens_per_sec"] == 100


def test_metrics_logger_survives_killed_writer(tmp_path):
    """A writer killed mid-line leaves one torn final line; a new writer's
    appends land on a fresh line boundary is NOT guaranteed — what IS
    guaranteed is the reader skips garbage and keeps every whole row."""
    path = os.path.join(str(tmp_path), "metrics.jsonl")
    with MetricsLogger(str(tmp_path)) as logger:
        logger.log(0, loss=3.0)
    # simulate the kill: a torn, newline-terminated-nowhere partial row
    with open(path, "a") as f:
        f.write('{"schema": 1, "step": 1, "loss": 2.')
    rows = read_metrics(str(tmp_path))
    assert [r["step"] for r in rows] == [0]
    # a fresh writer appends after the torn line; its row must survive.
    # (the torn fragment corrupts at most ITSELF plus nothing — the new
    # row is written via one O_APPEND write that starts with a newline
    # only if we add one; instead verify the reader still sees both whole
    # rows once a newline separates them)
    with open(path, "a") as f:
        f.write("\n")
    with MetricsLogger(str(tmp_path)) as logger:
        logger.log(2, loss=1.0)
    rows = read_metrics(str(tmp_path))
    assert [r["step"] for r in rows] == [0, 2]


def test_metrics_logger_csv_export(tmp_path):
    csv_path = os.path.join(str(tmp_path), "metrics.csv")
    with MetricsLogger(str(tmp_path), csv_path=csv_path) as logger:
        logger.log(0, loss=2.0)
        logger.log(1, loss=1.0)
    lines = open(csv_path).read().strip().splitlines()
    assert len(lines) == 3 and "loss" in lines[0]


def test_degraded_kernel_lands_in_metrics_and_events(tmp_path):
    """The resilience satellite: a forced Pallas failure (the injection
    harness) must surface as a telemetry event AND a degraded=1 metric
    row — not only as a one-shot warning."""
    resilience.reset()
    telemetry.drain_events()
    try:
        with resilience.inject(resilience.PALLAS_FAULT):
            with warnings.catch_warnings():
                warnings.simplefilter("ignore")
                assert not resilience.pallas_available(refresh=True)
        events = telemetry.events()
        assert any(
            e["event"] == "degraded"
            and e["component"] == resilience.PALLAS_COMPONENT
            for e in events
        )
        with MetricsLogger(str(tmp_path)) as logger:
            logger.log(3, loss=1.0)
        rows = read_metrics(str(tmp_path))
        assert rows[0]["event"] == "degraded"  # the event row
        assert rows[1]["degraded"] == 1  # and the next metric row's flag
        assert rows[1]["step"] == 3
    finally:
        resilience.reset()
        telemetry.drain_events()


# ----------------------------------------------------------------------
# Trace annotations: stable names present in compiled HLO and in a
# jax.profiler trace captured on CPU
# ----------------------------------------------------------------------


def test_flash_scope_names_in_profiler_trace(tmp_path):
    """End-to-end: the names land in an actual xplane capture on CPU (the
    same artifact XProf reads on TPU)."""
    from ring_attention_tpu.ops.flash import flash_attention

    q = jnp.ones((1, 2, 64, 8), jnp.float32)
    f = jax.jit(lambda q: flash_attention(q, q, q, causal=True,
                                          bucket_size=32))
    jax.block_until_ready(f(q))  # compile outside the trace
    with jax.profiler.trace(str(tmp_path)):
        jax.block_until_ready(f(q))
    blobs = []
    for root, _, files in os.walk(str(tmp_path)):
        for name in files:
            if name.endswith(".xplane.pb"):
                blobs.append(open(os.path.join(root, name), "rb").read())
    assert blobs, "profiler produced no xplane capture"
    assert any(b"flash/fwd" in blob for blob in blobs)
    # and the observatory's stdlib parser resolves the same capture into
    # a stage timeline (the per-hop/ring assertions live in
    # tests/test_observatory.py; this pins the single-device join)
    from ring_attention_tpu.utils.profiling import (
        read_xplane_events,
        stage_timeline,
    )

    events, note = read_xplane_events(str(tmp_path))
    assert events, f"stdlib xplane parser found no events: {note}"
    rows = stage_timeline(events)["stages"]
    flash = [r for r in rows if r["stage"] == "flash forward"]  # XLA path
    assert flash and flash[0]["busy_ms"] > 0


def test_ring_scope_names_in_compiled_hlo(rng, devices):
    """Compiled-HLO metadata carries the ring's stable scope names (this
    metadata is exactly what XProf displays as the op name)."""
    from ring_attention_tpu.models.attention import RingAttention
    from ring_attention_tpu.parallel.mesh import create_mesh

    mesh = create_mesh(ring_size=4)
    att = RingAttention(dim=32, heads=4, dim_head=8, bucket_size=8,
                        causal=True, use_ring=True, auto_shard=True,
                        mesh=mesh)
    x = jnp.asarray(rng.standard_normal((2, 64, 32)), jnp.float32)
    params = att.init(jax.random.PRNGKey(0), x)
    txt = jax.jit(
        lambda p, x: att.apply(p, x)
    ).lower(params, x).compile().as_text()
    for name in ("ring/hop", "ring/rotate"):
        assert name in txt, f"scope {name!r} missing from compiled HLO"


def test_backward_scope_names_in_compiled_hlo():
    from ring_attention_tpu.ops.flash import flash_attention

    q = jnp.ones((1, 2, 64, 8), jnp.float32)
    txt = jax.jit(jax.grad(
        lambda q: flash_attention(q, q, q, causal=True,
                                  bucket_size=32).sum()
    )).lower(q).compile().as_text()
    assert "flash/bwd" in txt


# ----------------------------------------------------------------------
# MFU formulas pinned against hand counts
# ----------------------------------------------------------------------


def test_flash_flops_pinned_hand_count():
    """One (seq, heads, dim) point counted by hand: seq 1024, 8 heads,
    d=64, causal.  qk^T is 1024*1024*64 MACs = 2*1024^2*64 FLOPs per
    head; pv the same; causal halves; 8 heads:
    2 matmuls * 2 * 1024^2 * 64 * 8 * 0.5 = 1_073_741_824."""
    got = flash_attention_flops(1024, heads=8, dim_head=64, causal=True)
    assert got == 2 * 2 * 1024 * 1024 * 8 * 64 * 0.5 == 1_073_741_824.0
    # backward = 7 matmuls (score recompute + dv, dp, dq, dk): 3.5x fwd
    bwd = flash_attention_flops(1024, heads=8, dim_head=64, causal=True,
                                backward=True)
    assert bwd == got * 3.5
    # non-causal doubles; cross-lengths multiply
    assert flash_attention_flops(1024, heads=8, dim_head=64) == 2 * got
    assert flash_attention_flops(
        512, 2048, heads=8, dim_head=64
    ) == 2 * 2 * 512 * 2048 * 8 * 64


def test_transformer_step_flops_and_mfu():
    dense_only = transformer_step_flops(
        1000, 4096, depth=0, heads=8, dim_head=64, seq_len=4096
    )
    assert dense_only == 6.0 * 1000 * 4096
    full = transformer_step_flops(
        1000, 4096, depth=2, heads=8, dim_head=64, seq_len=4096
    )
    assert full == dense_only + 2 * flash_attention_flops(
        4096, heads=8, dim_head=64, causal=True, backward=True
    )
    # a step achieving exactly peak is MFU 1.0
    assert achieved_mfu(197e12 * 0.5, 0.5, 197.0) == pytest.approx(1.0)
    assert achieved_mfu(1.0, 0.0, 197.0) == 0.0
    # a device the table does not list is an error, never a default
    with pytest.raises(ValueError, match="no bf16 peak listed"):
        device_peak_tflops()  # the CPU test backend

    class _V5e:
        device_kind = "TPU v5 lite"

    assert device_peak_tflops(_V5e()) == 197.0


def test_ring_comms_accounting_hybrid_factoring():
    """The PR 3 claim as numbers: at equal world 8, the 2x4 hybrid
    factoring cuts latency-chain hops from 7 to 3 and circulates the
    kv-head subset of the ring chunk per hop."""
    pure = ring_comms_accounting(
        ring_size=8, seq_len=8192, kv_heads=8, dim_head=64, depth=2
    )
    hybrid = ring_comms_accounting(
        ring_size=4, ulysses_size=2, seq_len=8192, kv_heads=8,
        dim_head=64, heads=8, depth=2
    )
    assert pure["ring_hops"] == 7 and pure["pure_ring_hops"] == 7
    assert hybrid["ring_hops"] == 3 and hybrid["pure_ring_hops"] == 7
    # hop payload: 2 (k+v) * kv_heads_local * chunk * d * 2 bytes
    assert pure["hop_bytes"] == 2 * 8 * (8192 // 8) * 64 * 2
    assert hybrid["hop_bytes"] == 2 * 4 * (8192 // 4) * 64 * 2
    assert 0.0 < hybrid["hop_overlap_fraction"] <= 1.0
    # limited passes shrink the chain; indivisible seq is a loud error
    limited = ring_comms_accounting(
        ring_size=8, seq_len=8192, kv_heads=8, dim_head=64, passes=2
    )
    assert limited["ring_hops"] == 1
    with pytest.raises(ValueError, match="divide"):
        ring_comms_accounting(
            ring_size=3, seq_len=8192, kv_heads=8, dim_head=64
        )


def test_ring_comms_accounting_compression_and_counter():
    """PR 6 terms as numbers.  int8 hop compression: bytes/hop shrink
    dtype_bytes * d / (d + 4)-fold — ~3.8x from f32 at d=64 (the "~4x"
    acceptance pin), hop COUNTS untouched, backward bytes untouched (the
    compressed forward payload never enters the backward ring).  Counter-
    rotation: one extra forward collective (the out/lse catch-up), the
    backward's resident-KV schedule repays it, and the busier forward
    link direction carries about half the baseline's rotation traffic."""
    base = ring_comms_accounting(
        ring_size=8, seq_len=8192, kv_heads=8, dim_head=64, dtype_bytes=4
    )
    comp = ring_comms_accounting(
        ring_size=8, seq_len=8192, kv_heads=8, dim_head=64, dtype_bytes=4,
        hop_compression="int8",
    )
    # per-hop payload: values 1 byte + 4 bitcast f32 scale bytes per row
    assert comp["hop_bytes"] == 2 * 8 * (8192 // 8) * (64 + 4)
    ratio = base["hop_bytes"] / comp["hop_bytes"]
    assert ratio == pytest.approx(4 * 64 / (64 + 4))  # ~3.76x from f32
    assert 3.5 < ratio < 4.0
    assert comp["ring_hops"] == base["ring_hops"]
    assert comp["fwd_collectives"] == base["fwd_collectives"]
    # backward recirculates exact (k, v) + f32 (dk, dv): unchanged
    assert (comp["ring_bytes_per_step_bwd"]
            == base["ring_bytes_per_step_bwd"])

    ctr = ring_comms_accounting(
        ring_size=8, seq_len=8192, kv_heads=8, dim_head=64, dtype_bytes=4,
        counter_rotate=True,
    )
    assert ctr["counter_rotate"] is True
    # fwd: 7 rotations + the out/lse catch-up; baseline: 7
    assert ctr["fwd_collectives"] == 8 and base["fwd_collectives"] == 7
    # bwd: the q-side pack's 8 collectives vs the baseline's 2*8 - 1
    assert ctr["bwd_collectives"] == 8 and base["bwd_collectives"] == 15
    assert (ctr["fwd_collectives"] + ctr["bwd_collectives"]
            < base["fwd_collectives"] + base["bwd_collectives"])
    # full-duplex split: the busier direction carries well under the
    # baseline's single-direction total
    assert ctr["fwd_link_direction_bytes"] < base["fwd_link_direction_bytes"]
    assert ctr["q_pack_bytes"] == 4 * 1 * 8 * (8192 // 8) * (2 * 64 + 2)
    with pytest.raises(ValueError, match="hop_compression"):
        ring_comms_accounting(
            ring_size=8, seq_len=8192, kv_heads=8, dim_head=64,
            hop_compression="fp4",
        )


def test_ring_comms_accounting_compute_dtype():
    """PR 13 terms as numbers.  compute_dtype="int8": the matmul FEED
    shrinks to 1 byte/element (q + k + v per hop), the f32 (acc, m, l)
    accumulator bytes are INVARIANT (the precision auditor's contract as
    a pinned number), the wire terms are untouched (quantized matmuls
    change what the kernels read, never what the ring moves), and the
    overlap model's compute leg runs at the 2x int8 MXU rate — less
    compute time available to hide the same transfer."""
    kw = dict(ring_size=8, seq_len=8192, kv_heads=8, dim_head=64,
              dtype_bytes=2)
    bf16 = ring_comms_accounting(**kw)
    q8 = ring_comms_accounting(compute_dtype="int8", **kw)
    n_chunk = 8192 // 8
    # feed: q (8 heads) + k + v (8 kv heads) rows of the held chunk
    assert q8["matmul_operand_bytes"] == 3 * 8 * n_chunk * 64
    assert bf16["matmul_operand_bytes"] == 2 * 3 * 8 * n_chunk * 64
    # the f32 (acc, m, l) state: (d + 2) f32 per (head, token), invariant
    expected_acc = 4 * 8 * n_chunk * (64 + 2)
    assert q8["accumulator_bytes"] == expected_acc
    assert bf16["accumulator_bytes"] == expected_acc
    # wire terms untouched
    for key in ("hop_bytes", "fwd_collectives", "bwd_collectives",
                "ring_bytes_per_step", "ring_bytes_per_step_bwd"):
        assert q8[key] == bf16[key], key
    # int8 compute finishes in half the time -> overlap can only drop
    assert q8["hop_overlap_fraction"] <= bf16["hop_overlap_fraction"]
    assert q8["compute_dtype"] == "int8" and bf16["compute_dtype"] is None
    with pytest.raises(ValueError, match="compute_dtype"):
        ring_comms_accounting(compute_dtype="fp8", **kw)


def test_ring_comms_accounting_fused():
    """PR 18 terms as numbers.  ``impl="fused"``: the whole hop schedule
    rides ONE kernel launch, so the launch count drops from ``passes`` to
    1, the per-hop dispatch-overhead term vanishes, and the forward
    issues ZERO XLA collectives (hops are in-kernel remote DMAs — the
    ``fused_ring`` contract row pins the count from the lowered module).
    Analytic HOPS and bytes are EQUAL to the scan path — the fused ring
    moves the same KV the same number of times; what it deletes is the
    launch boundary."""
    kw = dict(ring_size=8, seq_len=8192, kv_heads=8, dim_head=64,
              dtype_bytes=2)
    scan = ring_comms_accounting(**kw)
    fused = ring_comms_accounting(impl="fused", **kw)
    assert scan["impl"] == "scan" and fused["impl"] == "fused"
    # the launch model: one launch, no per-hop dispatch overhead
    assert scan["kernel_launches"] == 8
    assert fused["kernel_launches"] == 1
    assert scan["dispatch_overhead_s"] > 0.0
    assert fused["dispatch_overhead_s"] == 0.0
    # hops are in-kernel remote DMAs, not XLA collectives
    assert scan["fwd_collectives"] == 7
    assert fused["fwd_collectives"] == 0
    # the backward retains the scan-path schedule
    assert fused["bwd_collectives"] == scan["bwd_collectives"]
    # analytic hop/byte accounting is IDENTICAL — same KV, same moves
    for key in ("ring_hops", "hop_bytes", "ring_bytes_per_step",
                "ring_bytes_per_step_bwd"):
        assert fused[key] == scan[key], key
    # removing the exposed dispatch term can only improve overlap
    assert fused["hop_overlap_fraction"] >= scan["hop_overlap_fraction"]
    # limited passes: the scan path pays one launch per pass, fused one
    limited = ring_comms_accounting(passes=3, **kw)
    assert limited["kernel_launches"] == 3
    assert ring_comms_accounting(
        passes=3, impl="fused", **kw
    )["kernel_launches"] == 1
    with pytest.raises(ValueError, match="impl"):
        ring_comms_accounting(impl="triton", **kw)
    # counter-rotation has no fused form (parallel/ring.py raises on the
    # same combination): the analytic model refuses it too
    with pytest.raises(ValueError, match="counter_rotate"):
        ring_comms_accounting(impl="fused", counter_rotate=True, **kw)


def test_ring_comms_accounting_fused_north_star():
    """The acceptance number: at the 262k north-star shape the fused
    ring's measured-vs-analytic overlap target is ~1.0 — with the
    dispatch term gone, per-hop compute fully hides the transfer."""
    fused = ring_comms_accounting(
        ring_size=8, seq_len=262144, kv_heads=8, dim_head=64,
        dtype_bytes=2, impl="fused",
    )
    assert fused["hop_overlap_fraction"] == pytest.approx(1.0)
    assert fused["kernel_launches"] == 1
    assert fused["fwd_collectives"] == 0


def test_train_memory_estimate_compute_dtype():
    """train_memory_estimate's int8 keys: operand bytes quarter from f32
    (halve from bf16), accumulator bytes invariant, peak untouched (the
    FFN/CE transients dominate every modeled shape)."""
    from ring_attention_tpu.utils.telemetry import train_memory_estimate

    kw = dict(seq_len=4096, dim=256, depth=2, heads=4, vocab=256,
              n_params=1_000_000, dtype_bytes=2)
    bf16 = train_memory_estimate(**kw)
    q8 = train_memory_estimate(compute_dtype="int8", **kw)
    assert bf16["attn_operand_bytes"] == 3 * 4096 * 256 * 2
    assert q8["attn_operand_bytes"] == 3 * 4096 * 256
    expected_acc = 4096 * (256 + 2 * 4) * 4
    assert q8["attn_accumulator_bytes"] == expected_acc
    assert bf16["attn_accumulator_bytes"] == expected_acc
    assert q8["peak_hbm_bytes"] == bf16["peak_hbm_bytes"]


def test_attention_logit_summaries_match_dense_oracle(rng):
    q = jnp.asarray(rng.standard_normal((1, 2, 32, 8)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((1, 2, 32, 8)), jnp.float32)
    got = attention_logit_summaries(q, k, causal=True, bucket_size=8)
    s = np.einsum("bhid,bhjd->bhij", np.asarray(q), np.asarray(k)) * 8**-0.5
    s = np.where(np.tril(np.ones((32, 32), bool)), s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    ent = -np.where(p > 0, p * np.log(np.maximum(p, 1e-30)), 0.0).sum(-1)
    assert float(got["max_logit"]) == pytest.approx(
        s.max(), rel=1e-5
    )
    assert float(got["softmax_entropy"]) == pytest.approx(
        ent.mean(), rel=1e-5
    )
    assert float(got["softmax_entropy_min"]) == pytest.approx(
        ent.min(), abs=1e-5
    )


# ----------------------------------------------------------------------
# StepTimer hardening
# ----------------------------------------------------------------------


def test_steptimer_percentiles(monkeypatch):
    t = {"now": 0.0}
    monkeypatch.setattr(
        "ring_attention_tpu.utils.tracing.time.perf_counter",
        lambda: t["now"],
    )
    timer = StepTimer(tokens_per_step=10)
    deltas = [0.1, 0.1, 0.1, 0.1, 0.5]  # one straggler step
    timer.step()
    for d in deltas:
        t["now"] += d
        timer.step()
    assert timer.step_ms_p50 == pytest.approx(100.0)
    assert timer.step_ms_p95 > 300.0  # the tail sees the straggler
    assert timer.steps_per_sec == pytest.approx(len(deltas) / sum(deltas))
    assert timer.tokens_per_sec == pytest.approx(
        10 * len(deltas) / sum(deltas)
    )


def test_steptimer_monotonic_guard(monkeypatch):
    t = {"now": 100.0}
    monkeypatch.setattr(
        "ring_attention_tpu.utils.tracing.time.perf_counter",
        lambda: t["now"],
    )
    timer = StepTimer(tokens_per_step=10)
    timer.step()
    t["now"] = 99.0  # clock went backwards
    timer.step()
    assert timer.clock_anomalies == 1
    assert timer.steps_per_sec == 0.0  # window reset, not a negative rate
    t["now"] = 100.0
    timer.step()
    assert timer.steps_per_sec > 0


def test_steptimer_warns_once_without_tokens():
    timer = StepTimer()  # tokens_per_step unset
    with pytest.warns(UserWarning, match="tokens_per_step is unset"):
        timer.step(jnp.float32(1.0))
    with warnings.catch_warnings():
        warnings.simplefilter("error")  # second call must NOT warn again
        timer.step(jnp.float32(1.0))
    assert timer.tokens_per_sec == 0.0


# ----------------------------------------------------------------------
# The acceptance HLO pin: instrumentation adds no collectives
# ----------------------------------------------------------------------


@pytest.mark.parametrize("guarded", [True, False],
                         ids=["guarded", "unguarded"])
def test_metrics_add_no_collectives(rng, devices, guarded):
    """The instrumented train step must issue the SAME collective sequence
    as the uninstrumented one — telemetry derives every metric from values
    the step already computes.  (The unguarded baseline is compared with
    clipping on, which already computes the global grad norm the metrics
    reuse.)  The collective signature comes from the shared contract
    checker (``analysis/contracts.py::hlo_collective_sequence``) so this
    pin and the per-strategy contracts can never disagree on what counts
    as a collective."""
    from ring_attention_tpu import RingTransformer, create_mesh
    from ring_attention_tpu.analysis.contracts import hlo_collective_sequence

    mesh = create_mesh(ring_size=4)
    model = RingTransformer(
        num_tokens=64, dim=32, depth=1, heads=4, dim_head=8, causal=True,
        striped=True, bucket_size=8, mesh=mesh, use_ring=True,
    )
    toks = jnp.asarray(
        rng.integers(0, 64, (2, 64)), jnp.int32
    )
    params = model.init(jax.random.PRNGKey(0), toks, return_loss=True)
    opt = optax.adam(1e-3)
    opt_state = opt.init(params)

    def loss_fn(p, t):
        return model.apply(p, t, return_loss=True)

    kw = dict(skip_nonfinite=guarded, clip_grad_norm=1.0)
    base = make_train_step(loss_fn, opt, **kw)
    inst = make_train_step(loss_fn, opt, collect_metrics=True, **kw)
    base_args = (
        (params, opt_state, init_step_stats(), toks)
        if guarded else (params, opt_state, toks)
    )
    inst_args = (params, opt_state, init_train_metrics(), toks)

    txt_base = jax.jit(base).lower(*base_args).compile().as_text()
    txt_inst = jax.jit(inst).lower(*inst_args).compile().as_text()
    seq_base = hlo_collective_sequence(txt_base)
    seq_inst = hlo_collective_sequence(txt_inst)
    assert seq_base, "expected ring collectives in the train step"
    if guarded:
        # signatures match (StepStats vs TrainMetrics carry): the compiled
        # programs must issue the identical collective SEQUENCE
        assert seq_inst == seq_base
    else:
        # the extra metric outputs shift XLA's scheduling of independent
        # collectives; the pin here is that the SET is unchanged — no
        # collective was added by instrumentation
        from collections import Counter

        assert Counter(seq_inst) == Counter(seq_base)


# ----------------------------------------------------------------------
# trace_report.py golden output
# ----------------------------------------------------------------------

_GOLDEN_ROWS = """\
{"schema": 1, "step": 0, "time": 1.0, "loss": 4.0, "grad_norm": 2.0, "tokens_per_sec": 100.0, "mfu": 0.25, "ring_hops": 3, "skipped": 0}
{"schema": 1, "event": "degraded", "component": "pallas_flash", "reason": "boom", "time": 2.0}
{"schema": 1, "step": 5, "time": 3.0, "loss": 2.0, "grad_norm": 1.0, "tokens_per_sec": 200.0, "mfu": 0.35, "ring_hops": 3, "skipped": 1, "degraded": 1}
{"schema": 1, "step": 10, "loss": 1.\
"""

_GOLDEN_OUT = """\
rows: 2 metric + 1 event | steps 0..5 | schema 1
  event: degraded pallas_flash
  DEGRADED run: 1 kernel-fallback event(s) — see ring_attention_tpu.utils.resilience.degradation

comms accounting (analytic, per device)
  ring_hops                3

  metric                       last         mean          p50          p95
  loss                            2            3            3          3.9
  grad_norm                       1          1.5          1.5         1.95
  tokens_per_sec                200          150          150          195
  mfu                          0.35          0.3          0.3        0.345
  degraded                        1            1            1            1
  skipped                         1          0.5          0.5         0.95
"""


def test_trace_report_golden_output(tmp_path):
    """Pinned end-to-end output: schema summary, event surfacing, the
    degraded banner, accounting echo, percentile table — and the torn
    final line (a killed writer) silently skipped."""
    path = os.path.join(str(tmp_path), "metrics.jsonl")
    with open(path, "w") as f:
        f.write(_GOLDEN_ROWS)
    proc = subprocess.run(
        [sys.executable, TRACE_REPORT, str(tmp_path)],
        capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == 0, proc.stderr
    got = proc.stdout.splitlines()
    # first line echoes the (tmp) path; compare everything after it
    assert got[0].startswith("trace report: ")
    assert "\n".join(got[1:]) + "\n" == _GOLDEN_OUT


def test_trace_report_missing_xprof_is_note_not_error(tmp_path):
    path = os.path.join(str(tmp_path), "metrics.jsonl")
    with open(path, "w") as f:
        f.write('{"schema": 1, "step": 0, "loss": 1.0}\n')
    proc = subprocess.run(
        [sys.executable, TRACE_REPORT, str(tmp_path),
         "--xprof", os.path.join(str(tmp_path), "nope")],
        capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == 0, proc.stderr
    assert "loss" in proc.stdout


# ----------------------------------------------------------------------
# examples/train.py --metrics-dir end to end (the acceptance command)
# ----------------------------------------------------------------------


@pytest.mark.slow
def test_train_example_writes_schema_valid_metrics(tmp_path):
    mdir = os.path.join(str(tmp_path), "m")
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "examples", "train.py"),
         "--fake-devices", "4", "--steps", "6", "--seq-len", "128",
         "--metrics-dir", mdir, "--log-every", "2", "--skip-nonfinite"],
        capture_output=True, text=True, timeout=600,
        env={**os.environ, "JAX_PLATFORMS": "cpu"}, cwd=REPO,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    rows = [r for r in read_metrics(mdir) if "event" not in r]
    assert rows, "no metric rows written"
    for field in ("loss", "grad_norm", "tokens_per_sec",
                  "ring_hops", "skipped", "nonfinite", "step_ms_p95"):
        assert field in rows[-1], f"missing {field}: {sorted(rows[-1])}"
    # the CPU has no listed peak, so no utilization is written for it
    assert "mfu" not in rows[-1]
    assert rows[-1]["schema"] == SCHEMA_VERSION
    assert rows[-1]["ring_hops"] == 3  # 4-device ring: 3 hops
    # and the report tool renders it
    proc = subprocess.run(
        [sys.executable, TRACE_REPORT, mdir],
        capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == 0, proc.stderr
    assert "tokens_per_sec" in proc.stdout
