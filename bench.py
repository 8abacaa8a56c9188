"""Benchmark: causal flash attention + train-step throughput on one TPU chip.

Prints ONE JSON line:
  {"metric": ..., "value": N, "unit": "TFLOPs/chip", "vs_baseline": N,
   "fwdbwd_tflops": ..., "tokens_per_sec": ..., ...}

North-star config (BASELINE.json): seq_len=262144, causal, 8 heads — both
attention TFLOPs/chip AND tokens/sec (train step: fwd+bwd+adam).  The
reference publishes no performance numbers (BASELINE.md), so
``vs_baseline`` reports the fraction of the chip's bf16 peak (MFU) —
a hardware-grounded, round-over-round comparable scalar.

No chip, no number: with no TPU, or with every device phase failed, the
run exits non-zero and prints no result.

Process discipline: the chip belongs to one process at a time, so this
parent never imports jax and runs its workers strictly one after another
(``subprocess.run`` blocks until each has exited), each with a hard
timeout; all of them share one persistent compile cache
(``utils.enable_compile_cache``).

Measurement hygiene: seeded random inputs (degenerate softmax rows on
constant inputs can distort timing), compile time recorded separately from
step time.  Each measurement is a single jitted ``lax.scan`` whose
iterations are chained by a data dependency, synced by fetching a scalar,
with the separately-measured fetch round-trip subtracted
(``utils/benchtime.py``); ROADMAP S0 decides whether a plain
``block_until_ready`` loop replaces it.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time
import traceback

TARGET_SEQ = 262144
HEADS = 8
DIM_HEAD = 64


def _load_repo_module(name: str, *relpath: str):
    """Load a package module by FILE PATH, bypassing the package
    ``__init__`` chain: this parent process must touch no jax code (a
    parent that has touched jax holds the chip its workers need).  Only
    valid for the modules that are stdlib-only at module level by design
    (analysis/perfgate.py)."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        name,
        os.path.join(os.path.dirname(os.path.abspath(__file__)), *relpath),
    )
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod  # dataclass field resolution
    spec.loader.exec_module(mod)
    return mod


_GATE_SCHEMA_CACHE: list[int] = []


def _gate_schema() -> int:
    """The perf-gate history schema version (``analysis/perfgate.py``),
    stamped on every phase payload so ``tools/perf_gate.py``'s ingest can
    version-check rounds.  Loaded by file path ONCE per process; returns
    0 (unknown) if the module cannot load — a stamping failure must
    never cost a bench round."""
    if _GATE_SCHEMA_CACHE:
        return _GATE_SCHEMA_CACHE[0]
    try:
        mod = _load_repo_module(
            "_bench_perfgate", "ring_attention_tpu", "analysis",
            "perfgate.py",
        )
        version = int(mod.GATE_SCHEMA_VERSION)
    except Exception:  # noqa: BLE001
        version = 0
    _GATE_SCHEMA_CACHE.append(version)
    return version

# attention FLOPs: 2 matmuls fwd; bwd recomputes scores + 4 grad matmuls
# (dv, dp, dq, dk) => 2.5x fwd; causal halves the work
FWD_MATMULS = 2
FWDBWD_MATMULS = 7


def _attn_fn(impl: str, seq_len: int, head_chunks: int | None = None):
    from functools import partial

    if impl == "pallas":
        from ring_attention_tpu.ops.pallas_flash import pallas_flash_attention

        return partial(
            pallas_flash_attention, causal=True, head_chunks=head_chunks
        )
    from ring_attention_tpu.ops.flash import flash_attention

    bucket = min(1024, seq_len)
    qc = 2048 if seq_len > 2048 else None  # two-level blocking for memory
    return partial(
        flash_attention, causal=True, bucket_size=bucket, q_chunk_size=qc
    )


def _device_peak():
    """(device, bf16 peak TFLOPs) — raises for a device_kind the peak
    table (utils/telemetry.py) does not list."""
    import jax

    from ring_attention_tpu.utils.telemetry import device_peak_tflops

    dev = jax.devices()[0]
    return dev, device_peak_tflops(dev)


def _device_worker() -> None:
    """One JSON line naming what jax finds here (see ``_require_tpu``)."""
    import jax

    dev = jax.devices()[0]
    print(json.dumps({"platform": dev.platform, "device_kind": dev.device_kind,
                      "device_count": len(jax.devices())}))


def _fetch_rtt(samples: int = 3):
    from ring_attention_tpu.utils.benchtime import fetch_rtt

    return fetch_rtt(samples)


def _timed(chained_fn, args, iters):
    from ring_attention_tpu.utils.benchtime import timed_chained

    return timed_chained(chained_fn, args, iters)


def _cost_fields(chained, args, secs_per_iter, iters):
    """Best-effort XLA cost + memory analysis of the timed executable:
    the compiler-counted FLOPs/bytes next to the analytic formula, the
    achieved HBM bandwidth (``bytes accessed`` over the measured wall
    time), and the compiled peak-memory accounting (``temp_bytes`` is the
    scratch high-water mark the chunking/remat knobs shrink — the 1M
    claim as a number, not prose).  The lowering hits the jit cache, so
    this re-lower is cheap; any failure returns ``{}`` — diagnostics
    never fail a measurement."""
    try:
        from ring_attention_tpu.utils.telemetry import (
            compiled_cost,
            compiled_memory,
        )

        exe = chained.lower(*args).compile()
        cost = compiled_cost(exe)
        mem = compiled_memory(exe)
    except Exception:  # noqa: BLE001
        return {}
    out = {}
    if cost.get("xla_flops"):
        out["xla_flops"] = cost["xla_flops"]
    if cost.get("bytes_accessed") and secs_per_iter > 0:
        out["bytes_accessed"] = cost["bytes_accessed"]
        # the executable runs `iters` chained iterations per call
        out["hbm_gbps"] = round(
            cost["bytes_accessed"] / (secs_per_iter * iters) / 1e9, 1
        )
    for key in ("temp_bytes", "argument_bytes", "output_bytes",
                "host_temp_bytes", "host_argument_bytes"):
        if key in mem:
            out[key] = mem[key]
    return out


def _degradation_fields():
    """Kernel-fallback record for this worker's JSON (utils/telemetry.py):
    a run that silently lost its Pallas kernels must say so in the bench
    output, not only in a scrolled-away warning."""
    try:
        from ring_attention_tpu.utils.telemetry import degradation_fields

        return degradation_fields()
    except Exception:  # noqa: BLE001
        return {}


def _fingerprint_worker() -> None:
    """Collective fingerprint of the hot entry points, from the contract
    checker (``analysis/contracts.py``) on simulated CPU devices.

    Per-strategy forward collective counts (ppermute / all_to_all /
    all_gather) land in the bench JSON so the perf trajectory catches a
    comms regression — an extra hop, an accidental O(seq) gather — even
    when tokens/sec moves for unrelated reasons.  Needs no TPU: the
    compiled collective sequence is backend-independent at this level.
    Env must be set before the first jax import, which is why this worker
    runs in its own subprocess.
    """
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=8"
    )
    os.environ["JAX_PLATFORMS"] = "cpu"

    from ring_attention_tpu.analysis.contracts import collective_fingerprint

    print(json.dumps(collective_fingerprint()))


def _coverage_worker() -> None:
    """Tile-coverage fingerprint (``analysis/coverage.py``): per-row
    compact-grid tile counts from the coverage prover, next to the
    collective fingerprint in the bench JSON — a mask/hint change that
    starts visiting dead tiles (or dropping live ones) shows up as a
    fingerprint diff in the perf trajectory.
    Pure numpy + trace-time helpers: no devices, no compiles."""
    os.environ["JAX_PLATFORMS"] = "cpu"

    from ring_attention_tpu.analysis.coverage import coverage_fingerprint

    print(json.dumps(coverage_fingerprint()))


def _protocol_worker() -> None:
    """Fused-ring DMA-protocol fingerprint (bench phase 0f): schedverify's
    derived primitive counts, PROTOCOL row count, per-ring model event
    counts, and total violations (0 on a healthy tree), from
    ``analysis/schedverify.py::protocol_fingerprint`` — the verified hop
    schedule as a pinned number, so any edit to the kernel's DMA/
    semaphore protocol (or to its declared table) shows up in the perf
    trajectory.  The extraction cross-check
    traces the kernel on the simulated 8-device ring; env must precede
    the first jax import, hence the subprocess."""
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=8"
    )
    os.environ["JAX_PLATFORMS"] = "cpu"

    from ring_attention_tpu.analysis.schedverify import protocol_fingerprint

    print(json.dumps(protocol_fingerprint()))


def _multihost_worker() -> None:
    """Multihost dryrun fingerprint (bench phase 0e): the hierarchical
    ``(dcn_data, data, ring[, ulysses])`` mesh's forward collective
    counts + the machine-checked dcn-isolation verdict, from
    ``analysis/contracts.py::dcn_collective_fingerprint`` on simulated
    CPU devices.

    This is the pod-scale placement contract as a pinned number: zero
    ring/ulysses collectives over the dcn axis, proven from optimized
    HLO — so a change that starts hopping rings over DCN shows up in the
    perf trajectory (``analysis/perfgate.py`` gates the family exactly).
    Env must precede the first jax import, hence the subprocess."""
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=8"
    )
    os.environ["JAX_PLATFORMS"] = "cpu"

    from ring_attention_tpu.analysis.contracts import (
        dcn_collective_fingerprint,
    )

    print(json.dumps(dcn_collective_fingerprint()))


def _window262k_worker(extra: dict) -> None:
    """Sliding-window 262k certified-grid accounting (CPU-countable).

    Lowers ``Causal() & SlidingWindow(w)`` and plain ``Causal()`` at the
    north-star forward shape through the mask algebra (the same
    ``band_plan`` grids a Pallas launch would run), certifies both
    (``masks.certify`` — elementwise proof at the capped spec, closed-
    form-vs-enumeration tile accounting at the full 262k shape), and
    reports the certified work-tile reduction the window buys over
    causal.  Pure numpy, like the coverage fingerprint; a timed windowed
    forward belongs to a future chip phase.
    """
    os.environ["JAX_PLATFORMS"] = "cpu"

    from ring_attention_tpu import masks as M

    seq = int(extra.get("seq", TARGET_SEQ))
    window = int(extra.get("window", 4096))
    block = int(extra.get("block", 1024))
    spec = M.GridSpec(strategy="single", n_local=seq, block_q=block,
                      block_k=block)
    masks = {
        "causal": M.Causal(),
        "window": M.Causal() & M.SlidingWindow(window),
    }
    payload: dict = {"seq": seq, "window": window, "block": block}
    tiles = {}
    for name, mask in masks.items():
        cert = M.certify(mask, spec)
        low = M.lower(mask, spec)
        work = sum(h.plan.work_tiles for h in low.hops if h.plan is not None)
        total = sum(len(h.plan.tile_q) for h in low.hops
                    if h.plan is not None)
        tiles[name] = work
        payload[f"{name}_work_tiles"] = work
        payload[f"{name}_tiles"] = total
        payload[f"{name}_certified"] = cert.ok
        payload[f"{name}_proof_n"] = cert.proof_n
    payload["tile_reduction_x"] = round(
        tiles["causal"] / max(tiles["window"], 1), 2
    )
    print(json.dumps(payload))


def _train1m_mem_worker(extra: dict) -> None:
    """CPU-provable half of the ``train1m`` phase: the memory claim.

    Compiles the SAME train-step program twice at a proof shape — once
    with the memory-axis knobs on (blockwise FFN + chunked CE +
    ``nothing_saveable`` remat), once dense — and reports the compiler's
    own peak-scratch accounting (``memory_analysis`` temp bytes) for
    both: the acceptance relation is *chunked strictly below dense at
    equal shape*.  Forced onto the CPU like the fingerprint worker (the
    backend-independent program structure is what the knobs change;
    tokens/sec comes from the timed chip phase).  Also
    emits the analytic peak-HBM estimate of the full 2^20-token target
    config (``telemetry.train_memory_estimate``) next to a v5e chip's
    16 GB so the "1M fits" claim is checkable arithmetic.
    """
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    import jax.numpy as jnp

    from ring_attention_tpu.utils import enable_compile_cache
    from ring_attention_tpu.utils.telemetry import (
        compiled_memory,
        train_memory_estimate,
    )

    enable_compile_cache()
    target_seq = int(extra.get("target_seq", 1 << 20))
    proof_seq = int(extra.get("proof_seq", 8192))
    ff_chunk = int(extra.get("ff_chunk", 512))
    loss_chunk = int(extra.get("loss_chunk", 512))
    vocab = int(extra.get("vocab", 256))

    from ring_attention_tpu.models import RingTransformer

    def proof_model(chunk: bool):
        # the train worker's dims, but bucket 512 instead of 2048: the
        # relation under proof is the FFN term, and at bucket 2048 the
        # attention recompute's tile scratch (h x bucket^2 f32) swamps it
        # with scheduling noise at CPU-compilable sequence lengths
        return RingTransformer(
            num_tokens=vocab, dim=512, depth=2, causal=True, heads=HEADS,
            dim_head=DIM_HEAD, bucket_size=min(512, proof_seq), rotary=True,
            remat=True, remat_policy="nothing_saveable",
            ff_chunk_size=ff_chunk if chunk else None,
            loss_chunk_size=loss_chunk if chunk else None,
            dtype=jnp.bfloat16,
        )

    chunked, dense = proof_model(True), proof_model(False)
    params = chunked.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 129), jnp.int32),
        return_loss=True,
    )
    tokens = jax.random.randint(
        jax.random.PRNGKey(1), (1, proof_seq + 1), 0, vocab, jnp.int32
    )

    def temp_bytes(model):
        fn = jax.jit(jax.value_and_grad(
            lambda p, t: model.apply(p, t, return_loss=True)
        ))
        return compiled_memory(fn.lower(params, tokens).compile())

    mem_c = temp_bytes(chunked)
    mem_d = temp_bytes(dense)
    n_params = sum(x.size for x in jax.tree.leaves(params))
    # the estimate describes the TARGET (phase 7) configuration — its
    # chunk sizes are emitted alongside so the arithmetic is checkable
    # against exactly the config the row claims to describe
    target_ff = int(extra.get("target_ff_chunk", 2048))
    target_loss = int(extra.get("target_loss_chunk", 2048))
    est_kw = dict(
        seq_len=target_seq, dim=512, depth=2, heads=HEADS, vocab=vocab,
        n_params=n_params, dtype_bytes=2, remat_policy="save_attn",
    )
    est_chunked = train_memory_estimate(
        ff_chunk_size=target_ff, loss_chunk_size=target_loss, **est_kw
    )
    est_dense = train_memory_estimate(**est_kw)
    tc, td = mem_c.get("temp_bytes"), mem_d.get("temp_bytes")
    print(json.dumps({
        "target_seq": target_seq,
        "target_ff_chunk": target_ff,
        "target_loss_chunk": target_loss,
        "peak_hbm_estimate_gb": est_chunked["peak_hbm_gb"],
        "peak_hbm_dense_estimate_gb": est_dense["peak_hbm_gb"],
        "proof_seq": proof_seq,
        "proof_ff_chunk": ff_chunk,
        "proof_loss_chunk": loss_chunk,
        "temp_bytes_chunked": tc,
        "temp_bytes_dense": td,
        "chunked_below_dense": (
            tc is not None and td is not None and tc < td
        ),
        "temp_ratio": (
            round(td / tc, 2) if tc and td else None
        ),
    }))


def _worker(impl: str, seq_len: int, mode: str, extra: dict) -> None:
    """Runs one timed measurement and prints its own JSON line.

    ``extra`` carries per-attempt config: heads / kv_heads / dim_head for
    shape variants (GQA, wide head), remat_policy for the train step.
    """
    import jax
    import jax.numpy as jnp

    from ring_attention_tpu.utils import enable_compile_cache

    enable_compile_cache()

    if mode == "train":
        _train_worker(impl, seq_len, extra.get("remat_policy"),
                      vocab=extra.get("vocab", 256),
                      loss_chunk_size=extra.get("loss_chunk_size"),
                      ff_chunk_size=extra.get("ff_chunk_size"))
        return
    if mode == "hops":
        _hops_worker(seq_len, int(extra.get("ring", 4)))
        return
    if mode == "hybrid":
        # "world" = TOTAL sequence-parallel degree (outer ring = world /
        # ulysses); "ring" is accepted as a legacy alias for it
        _hybrid_worker(seq_len,
                       int(extra.get("world", extra.get("ring", 4))),
                       int(extra.get("ulysses", 2)))
        return
    if mode == "counter":
        _counter_worker(seq_len, int(extra.get("ring", 4)),
                        extra.get("hop_compression"))
        return
    if mode == "q8":
        _q8_worker(seq_len, int(extra.get("ring", 4)))
        return
    if mode == "fused":
        _fused_worker(seq_len, int(extra.get("ring", 4)))
        return
    if mode == "decode":
        _decode_worker(impl, seq_len, extra)
        return
    if mode == "packed":
        _packed_worker(impl, seq_len, extra)
        return

    heads = int(extra.get("heads", HEADS))
    kv_heads = int(extra.get("kv_heads", heads))
    dim_head = int(extra.get("dim_head", DIM_HEAD))
    head_chunks = extra.get("head_chunks")
    if head_chunks and impl != "pallas":
        # fail fast: a sweep step must not silently measure the default
        # config in a scarce hardware window
        raise ValueError(f"head_chunks only applies to impl='pallas', "
                         f"got impl={impl!r}")

    dev, peak = _device_peak()
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (1, heads, seq_len, dim_head), jnp.bfloat16)
    k = jax.random.normal(ks[1], (1, kv_heads, seq_len, dim_head), jnp.bfloat16)
    v = jax.random.normal(ks[2], (1, kv_heads, seq_len, dim_head), jnp.bfloat16)

    attn = _attn_fn(
        impl, seq_len, int(head_chunks) if head_chunks else None
    )
    iters = 3 if seq_len >= TARGET_SEQ else 10

    if mode == "fwdbwd":
        grad_fn = jax.grad(
            lambda q, k, v: attn(q, k, v).astype(jnp.float32).sum(),
            argnums=(0, 1, 2),
        )

        @jax.jit
        def chained(q, k, v):
            def body(carry, _):
                dq, dk, dv = grad_fn(carry, k, v)
                # chain through all three grads so none is dead code
                nxt = (carry + 1e-6 * dq.astype(carry.dtype)
                       + (dk.mean() + dv.mean()).astype(carry.dtype) * 1e-9)
                return nxt, dq[0, 0, 0, 0]
            out, ys = jax.lax.scan(body, q, None, length=iters)
            return ys.sum()

        matmuls = FWDBWD_MATMULS
    else:

        @jax.jit
        def chained(q, k, v):
            def body(carry, _):
                o = attn(carry, k, v)
                # perturb rather than replace: feeding o back as q would
                # collapse score variance into the degenerate-softmax
                # regime the seeded inputs exist to avoid
                return carry + 1e-3 * o.astype(carry.dtype), o[0, 0, 0, 0]
            out, ys = jax.lax.scan(body, q, None, length=iters)
            return ys.astype(jnp.float32).sum()

        matmuls = FWD_MATMULS

    compile_s, secs = _timed(chained, (q, k, v), iters)

    flops = matmuls * 2 * seq_len * seq_len * heads * dim_head * 0.5  # causal
    tflops = flops / secs / 1e12
    print(
        json.dumps(
            {
                # 4 decimals: small-shape CPU-backend runs (the test
                # suite's contract checks) land in the 1e-3 TFLOPs range
                # and must not round to a zero measurement
                "value": round(tflops, 4),
                "vs_baseline": round(tflops / peak, 4),
                # same number under its proper name (docs/observability.md)
                "mfu": round(tflops / peak, 4),
                **_cost_fields(chained, (q, k, v), secs, iters),
                **_degradation_fields(),
                "seq_len": seq_len,
                "impl": impl,
                "heads": heads,
                "kv_heads": kv_heads,
                "dim_head": dim_head,
                # head_chunks only applies to the pallas launcher; don't
                # record it on impls where _attn_fn drops it
                **({"head_chunks": int(head_chunks)}
                   if head_chunks and impl == "pallas" else {}),
                "device": getattr(dev, "device_kind", str(dev)),
                "ms_per_step": round(secs * 1e3, 2),
                "compile_s": round(compile_s, 1),
            }
        )
    )


def _hop_sequence(q, k, v, ring: int, n_local: int, scale: float):
    """Device R-1's per-hop span calls of a contiguous causal ring: seed
    partials, in-kernel carry resume, fused normalized final write
    (parallel/ring.py ``_ring_fwd_pallas``).  Shared by the pure-ring and
    hybrid hop workers so their kernel schedules cannot diverge."""
    from ring_attention_tpu.ops.pallas_flash import (
        pallas_flash_fused,
        pallas_flash_partials,
    )

    def hop_kv(i):  # device R-1's hop i holds origin (R-1-i)'s block
        j = ring - 1 - i
        sl = slice(j * n_local, (j + 1) * n_local)
        return k[:, :, sl], v[:, :, sl]

    if ring == 1:  # degenerate factoring: one fused local sweep
        out, _ = pallas_flash_fused(
            q, k, v, scale=scale, causal_offset=0, block_q=1024, block_k=1024,
        )
        return out
    kh, vh = hop_kv(0)
    carry = pallas_flash_partials(
        q, kh, vh, scale=scale, causal_offset=0, block_q=1024, block_k=1024,
    )
    for i in range(1, ring - 1):
        kh, vh = hop_kv(i)
        carry = pallas_flash_partials(  # fully-visible span, resumed
            q, kh, vh, scale=scale, block_q=1024, block_k=1024, carry=carry,
        )
    kh, vh = hop_kv(ring - 1)
    out, _ = pallas_flash_fused(
        q, kh, vh, scale=scale, block_q=1024, block_k=1024, carry=carry,
    )
    return out


def _hybrid_worker(seq_len: int, world: int, ulysses: int) -> None:
    """Single-chip simulation of the hybrid Ulysses x Ring hop sequence.

    At equal sequence-parallel world, the hybrid factoring trades the
    ``world``-hop ring for a ``world/ulysses``-hop ring over ``h/ulysses``
    heads (the Ulysses all-to-all legs ride the fast intra-node tier and
    have no per-hop latency chain).  This worker runs the per-device span
    calls that remain after the all-to-all — the exact kernel sequence of
    ``parallel/hybrid.py``'s ring leg: seed, in-kernel resume, fused final
    write — and reports the hop count next to tokens/sec so the
    ``hybrid262k`` entry is directly comparable with the ``ring_hops``
    one."""
    import jax
    import jax.numpy as jnp

    assert world % ulysses == 0, f"ulysses {ulysses} must divide world {world}"
    ring = world // ulysses
    heads = HEADS // ulysses
    assert heads >= 1, f"ulysses {ulysses} needs at least {ulysses} heads"
    dev, peak = _device_peak()
    n_local = seq_len // ring
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (1, heads, n_local, DIM_HEAD), jnp.bfloat16)
    k = jax.random.normal(ks[1], (1, heads, seq_len, DIM_HEAD), jnp.bfloat16)
    v = jax.random.normal(ks[2], (1, heads, seq_len, DIM_HEAD), jnp.bfloat16)
    scale = DIM_HEAD**-0.5

    def hop_sequence(q):
        return _hop_sequence(q, k, v, ring, n_local, scale)

    iters = 3

    @jax.jit
    def chained(q):
        def body(carry, _):
            o = hop_sequence(carry)
            return carry + 1e-3 * o.astype(carry.dtype), o[0, 0, 0, 0]

        out, ys = jax.lax.scan(body, q, None, length=iters)
        return ys.astype(jnp.float32).sum()

    compile_s, secs = _timed(chained, (q,), iters)
    flops = (
        FWD_MATMULS * 2 * heads * DIM_HEAD * n_local * n_local * (ring - 0.5)
    )
    tflops = flops / secs / 1e12
    print(
        json.dumps(
            {
                "value": round(tflops, 4),
                "vs_baseline": round(tflops / peak, 4),
                "mfu": round(tflops / peak, 4),
                "seq_len": seq_len,
                "world": world,
                "ulysses": ulysses,
                "ring": ring,
                # inter-device transfers in the latency chain, vs world-1
                # for the pure ring at the same world size
                "hops": ring - 1,
                "pure_ring_hops": world - 1,
                # whole-slice rate: the world processes seq_len queries per
                # step while each device runs this hop sequence
                "tokens_per_sec": round(seq_len / secs),
                "impl": "pallas-hybrid",
                "device": getattr(dev, "device_kind", str(dev)),
                "ms_per_step": round(secs * 1e3, 2),
                "compile_s": round(compile_s, 1),
            }
        )
    )


def _hops_worker(seq_len: int, ring: int) -> None:
    """Single-chip simulation of a causal ring's per-device hop sequence.

    Runs the exact span calls device ``ring-1`` of a contiguous causal ring
    makes (parallel/ring.py ``_ring_fwd_pallas``): hop 0 = compact diagonal
    sweep seeding the carry, hops 1..R-2 = full sweeps resuming the carry
    in-kernel, last hop = fused normalized write.  Validates that the
    measured static-offset kernel rates survive on the path a real
    multi-chip ring executes (VERDICT r2 missing #1 'done' criterion).
    """
    import jax
    import jax.numpy as jnp

    dev, peak = _device_peak()
    n_local = seq_len // ring
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (1, HEADS, n_local, DIM_HEAD), jnp.bfloat16)
    k = jax.random.normal(ks[1], (1, HEADS, seq_len, DIM_HEAD), jnp.bfloat16)
    v = jax.random.normal(ks[2], (1, HEADS, seq_len, DIM_HEAD), jnp.bfloat16)
    scale = DIM_HEAD**-0.5

    def hop_sequence(q):
        return _hop_sequence(q, k, v, ring, n_local, scale)

    iters = 3

    @jax.jit
    def chained(q):
        def body(carry, _):
            o = hop_sequence(carry)
            return carry + 1e-3 * o.astype(carry.dtype), o[0, 0, 0, 0]

        out, ys = jax.lax.scan(body, q, None, length=iters)
        return ys.astype(jnp.float32).sum()

    compile_s, secs = _timed(chained, (q,), iters)
    # hop 0 is half-masked; hops 1..R-1 are full n_local x n_local spans
    flops = (
        FWD_MATMULS * 2 * HEADS * DIM_HEAD * n_local * n_local * (ring - 0.5)
    )
    tflops = flops / secs / 1e12
    print(
        json.dumps(
            {
                "value": round(tflops, 4),
                "vs_baseline": round(tflops / peak, 4),
                "mfu": round(tflops / peak, 4),
                "seq_len": seq_len,
                "ring": ring,
                "impl": "pallas-hops",
                "device": getattr(dev, "device_kind", str(dev)),
                "ms_per_step": round(secs * 1e3, 2),
                "compile_s": round(compile_s, 1),
            }
        )
    )


def _fused_worker(seq_len: int, ring: int) -> None:
    """Single-chip timing of the fused-ring kernel's whole hop chain.

    Where ``_hops_worker`` times the scan path's per-hop SEQUENCE of span
    launches (one ``pallas_call`` per hop, carry re-materialized through
    HBM at every boundary), this worker times the SAME work as ONE
    launch: ``ops/pallas_ring.py::fused_ring_local`` sweeps every hop's
    KV span inside a single kernel, the f32 ``(acc, m, l)`` state
    resident in VMEM scratch across hops.  The hop schedule is the real
    one — ``parallel/ring.py::_fused_tables`` for the causal last rank,
    the exact tables the multi-chip fused ring prefetches — so
    ``fused262k / ring_hops_tflops`` is the measured launch-boundary
    cost the fused path deletes.  The analytic comms terms ride from
    ``telemetry.ring_comms_accounting(impl="fused")``: ``kernel_launches
    == 1``, ``dispatch_overhead_s == 0``, ``fwd_collectives == 0`` (hops
    are in-kernel remote DMAs, pinned by phase 0's ``fused_ring``
    fingerprint row), overlap ~1.0 at the north-star shape.
    """
    import jax
    import jax.numpy as jnp

    from ring_attention_tpu.ops import pallas_ring
    from ring_attention_tpu.parallel import ring as ring_mod
    from ring_attention_tpu.utils.telemetry import ring_comms_accounting

    dev, peak = _device_peak()
    n_local = seq_len // ring
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (1, HEADS, n_local, DIM_HEAD), jnp.bfloat16)
    k = jax.random.normal(ks[1], (1, HEADS, seq_len, DIM_HEAD), jnp.bfloat16)
    v = jax.random.normal(ks[2], (1, HEADS, seq_len, DIM_HEAD), jnp.bfloat16)
    scale = DIM_HEAD**-0.5

    # causal last rank: hop 0 = banded diagonal, hops 1..R-1 full spans —
    # the same (ring - 0.5) work as _hops_worker's span sequence
    origins, his, los, works = ring_mod._fused_tables(
        ring - 1, ring, n_local, True, False, None, ring
    )

    def hop_sequence(q):
        out, _ = pallas_ring.fused_ring_local(
            q, k, v, origins=origins, his=his, los=los, works=works,
            n_local=n_local, scale=scale, block_q=1024, block_k=1024,
        )
        return out

    iters = 3

    @jax.jit
    def chained(q):
        def body(carry, _):
            o = hop_sequence(carry)
            return carry + 1e-3 * o.astype(carry.dtype), o[0, 0, 0, 0]

        out, ys = jax.lax.scan(body, q, None, length=iters)
        return ys.astype(jnp.float32).sum()

    compile_s, secs = _timed(chained, (q,), iters)
    flops = (
        FWD_MATMULS * 2 * HEADS * DIM_HEAD * n_local * n_local * (ring - 0.5)
    )
    tflops = flops / secs / 1e12
    comms = ring_comms_accounting(
        ring_size=ring, seq_len=seq_len, kv_heads=HEADS, heads=HEADS,
        dim_head=DIM_HEAD, dtype_bytes=2, impl="fused", peak_tflops=peak,
    )
    print(
        json.dumps(
            {
                "value": round(tflops, 4),
                "vs_baseline": round(tflops / peak, 4),
                "mfu": round(tflops / peak, 4),
                "seq_len": seq_len,
                "ring": ring,
                "kernel_launches": comms["kernel_launches"],
                "dispatch_overhead_s": comms["dispatch_overhead_s"],
                "hop_bytes": comms["hop_bytes"],
                "fwd_collectives": comms["fwd_collectives"],
                "bwd_collectives": comms["bwd_collectives"],
                "hop_overlap_fraction": comms["hop_overlap_fraction"],
                "tokens_per_sec": round(seq_len / secs),
                "impl": "pallas-fused",
                "device": getattr(dev, "device_kind", str(dev)),
                "ms_per_step": round(secs * 1e3, 2),
                "compile_s": round(compile_s, 1),
            }
        )
    )


def _counter_worker(seq_len: int, ring: int, hop_compression: str | None) -> None:
    """Single-chip simulation of the TokenRing counter-rotated hop chain.

    The counter schedule's per-device COMPUTE is the same span sequence as
    the baseline ring (pairing ``i`` attends the block ``i`` ranks behind
    — ``parallel/ring.py::_counter_fwd``); what changes on hardware is the
    communication (full-duplex split, int8 payloads).  This worker times
    the compute chain the compressed variant actually executes — per-hop
    int8 dequantization feeding the resumed span kernels — and reports
    the ANALYTIC comms terms (bytes/hop for the compressed KV handle and
    the f32 Q-pack, fwd/bwd collective counts) from
    ``telemetry.ring_comms_accounting``, so the ``counter262k`` entry sits
    next to ``ring_hops`` with directly comparable fields.  The collective
    fingerprint (phase 0) pins the corresponding hop COUNTS from compiled
    HLO.
    """
    import jax
    import jax.numpy as jnp

    from ring_attention_tpu.ops.pallas_flash import (
        pallas_flash_fused,
        pallas_flash_partials,
    )
    from ring_attention_tpu.parallel.collectives import (
        dequantize_ring_payload,
        quantize_ring_payload,
    )
    from ring_attention_tpu.utils.telemetry import ring_comms_accounting

    dev, peak = _device_peak()
    n_local = seq_len // ring
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (1, HEADS, n_local, DIM_HEAD), jnp.bfloat16)
    k = jax.random.normal(ks[1], (1, HEADS, seq_len, DIM_HEAD), jnp.bfloat16)
    v = jax.random.normal(ks[2], (1, HEADS, seq_len, DIM_HEAD), jnp.bfloat16)
    scale = DIM_HEAD**-0.5

    def hop_sequence(q):
        if hop_compression != "int8":
            return _hop_sequence(q, k, v, ring, n_local, scale)
        handle = quantize_ring_payload(k, v)  # once at ring entry

        def hop_kv(i):
            j = ring - 1 - i
            kh, vh = dequantize_ring_payload(
                handle[:, :, :, j * n_local:(j + 1) * n_local], q.dtype
            )
            return kh, vh

        kh, vh = hop_kv(0)
        carry = pallas_flash_partials(
            q, kh, vh, scale=scale, causal_offset=0,
            block_q=1024, block_k=1024,
        )
        for i in range(1, ring - 1):
            kh, vh = hop_kv(i)
            carry = pallas_flash_partials(
                q, kh, vh, scale=scale, block_q=1024, block_k=1024,
                carry=carry,
            )
        kh, vh = hop_kv(ring - 1)
        out, _ = pallas_flash_fused(
            q, kh, vh, scale=scale, block_q=1024, block_k=1024, carry=carry,
        )
        return out

    iters = 3

    @jax.jit
    def chained(q):
        def body(carry, _):
            o = hop_sequence(carry)
            return carry + 1e-3 * o.astype(carry.dtype), o[0, 0, 0, 0]

        out, ys = jax.lax.scan(body, q, None, length=iters)
        return ys.astype(jnp.float32).sum()

    compile_s, secs = _timed(chained, (q,), iters)
    flops = (
        FWD_MATMULS * 2 * HEADS * DIM_HEAD * n_local * n_local * (ring - 0.5)
    )
    tflops = flops / secs / 1e12
    comms = ring_comms_accounting(
        ring_size=ring, seq_len=seq_len, kv_heads=HEADS, heads=HEADS,
        dim_head=DIM_HEAD, dtype_bytes=2, counter_rotate=True,
        hop_compression=hop_compression, peak_tflops=peak,
    )
    print(
        json.dumps(
            {
                "value": round(tflops, 4),
                "vs_baseline": round(tflops / peak, 4),
                "mfu": round(tflops / peak, 4),
                "seq_len": seq_len,
                "ring": ring,
                "hop_compression": hop_compression,
                "hop_bytes": comms["hop_bytes"],
                "q_pack_bytes": comms["q_pack_bytes"],
                "fwd_collectives": comms["fwd_collectives"],
                "bwd_collectives": comms["bwd_collectives"],
                "hop_overlap_fraction": comms["hop_overlap_fraction"],
                "tokens_per_sec": round(seq_len / secs),
                "impl": "pallas-counter",
                "device": getattr(dev, "device_kind", str(dev)),
                "ms_per_step": round(secs * 1e3, 2),
                "compile_s": round(compile_s, 1),
            }
        )
    )


def _q8_worker(seq_len: int, ring: int) -> None:
    """Single-chip simulation of the int8 COMPUTE hop chain (PR 13).

    Where ``_counter_worker`` times the compressed ring's per-hop
    dequant feeding bf16 kernels, this worker times what the dequant-free
    composition actually executes: the KV payload quantized ONCE at ring
    entry with kernel-ready scales (``quant.pack_kv(v_block=...)``), each
    hop's span kernel consuming the int8 values + scales DIRECTLY
    (``compute_dtype="int8"`` / ``kv_quantized=``) with q re-quantized
    per hop and the f32 ``(acc, m, l)`` carry resumed in-kernel.  On
    v5e/v5p the int8 MXU rate is ~2x bf16 peak, so ``vs_baseline`` /
    ``mfu`` are reported against the BF16 peak (a number > the bf16 MFU
    ceiling is the int8 win, not an accounting error).  Operand/
    accumulator byte accounting and the wire terms ride along from
    ``telemetry.ring_comms_accounting(compute_dtype="int8")``; phase 0's
    collective fingerprint pins the ``counter_q8`` hop counts from
    compiled HLO.
    """
    import jax
    import jax.numpy as jnp

    from ring_attention_tpu.ops import quant
    from ring_attention_tpu.ops.pallas_flash import (
        pallas_flash_fused,
        pallas_flash_partials,
    )
    from ring_attention_tpu.utils.telemetry import ring_comms_accounting

    dev, peak = _device_peak()
    n_local = seq_len // ring
    blk = 1024
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (1, HEADS, n_local, DIM_HEAD), jnp.bfloat16)
    k = jax.random.normal(ks[1], (1, HEADS, seq_len, DIM_HEAD), jnp.bfloat16)
    v = jax.random.normal(ks[2], (1, HEADS, seq_len, DIM_HEAD), jnp.bfloat16)
    scale = DIM_HEAD**-0.5

    def hop_sequence(q):
        payload = quant.pack_kv(k, v, v_block=blk)  # once at ring entry

        def hop_feed(i):
            j = ring - 1 - i
            return quant.payload_kernel_feed(
                payload[:, :, :, j * n_local:(j + 1) * n_local], blk
            )

        carry = pallas_flash_partials(
            q, None, None, scale=scale, causal_offset=0,
            block_q=blk, block_k=blk,
            compute_dtype="int8", kv_quantized=hop_feed(0),
        )
        for i in range(1, ring - 1):
            carry = pallas_flash_partials(
                q, None, None, scale=scale, block_q=blk, block_k=blk,
                carry=carry, compute_dtype="int8", kv_quantized=hop_feed(i),
            )
        out, _ = pallas_flash_fused(
            q, None, None, scale=scale, block_q=blk, block_k=blk,
            carry=carry, compute_dtype="int8",
            kv_quantized=hop_feed(ring - 1),
        )
        return out

    iters = 3

    @jax.jit
    def chained(q):
        def body(carry, _):
            o = hop_sequence(carry)
            return carry + 1e-3 * o.astype(carry.dtype), o[0, 0, 0, 0]

        out, ys = jax.lax.scan(body, q, None, length=iters)
        return ys.astype(jnp.float32).sum()

    compile_s, secs = _timed(chained, (q,), iters)
    flops = (
        FWD_MATMULS * 2 * HEADS * DIM_HEAD * n_local * n_local * (ring - 0.5)
    )
    tflops = flops / secs / 1e12
    comms = ring_comms_accounting(
        ring_size=ring, seq_len=seq_len, kv_heads=HEADS, heads=HEADS,
        dim_head=DIM_HEAD, dtype_bytes=2, counter_rotate=True,
        hop_compression="int8", compute_dtype="int8", peak_tflops=peak,
    )
    print(
        json.dumps(
            {
                "value": round(tflops, 4),
                "vs_baseline": round(tflops / peak, 4),
                "mfu": round(tflops / peak, 4),
                "seq_len": seq_len,
                "ring": ring,
                "compute_dtype": "int8",
                "hop_compression": "int8",
                "hop_bytes": comms["hop_bytes"],
                "matmul_operand_bytes": comms["matmul_operand_bytes"],
                "accumulator_bytes": comms["accumulator_bytes"],
                "fwd_collectives": comms["fwd_collectives"],
                "bwd_collectives": comms["bwd_collectives"],
                "hop_overlap_fraction": comms["hop_overlap_fraction"],
                "tokens_per_sec": round(seq_len / secs),
                "impl": "pallas-q8",
                "device": getattr(dev, "device_kind", str(dev)),
                "ms_per_step": round(secs * 1e3, 2),
                "compile_s": round(compile_s, 1),
            }
        )
    )


def _decode_worker(impl: str, seq_len: int, extra: dict) -> None:
    """Single-token decode latency against a ``seq_len``-token KV cache.

    BASELINE config 5 (million-token context) is HBM-bandwidth-bound:
    the cost of a decode step IS the KV read.  ``impl="pallas"`` =
    ``pallas_flash_decode`` (cache read once per kv head);
    ``impl="dense"`` = the dense ``default_attention`` tile (the r2
    hardware-log path, 1.05 ms/token at 1M).  Reports ms/token and the
    effective KV-read bandwidth."""
    import jax
    import jax.numpy as jnp

    heads = int(extra.get("heads", HEADS))
    kv_heads = int(extra.get("kv_heads", 2))
    dev, _ = _device_peak()
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (1, heads, 1, DIM_HEAD), jnp.bfloat16)
    k = jax.random.normal(ks[1], (1, kv_heads, seq_len, DIM_HEAD), jnp.bfloat16)
    v = jax.random.normal(ks[2], (1, kv_heads, seq_len, DIM_HEAD), jnp.bfloat16)
    # live decode always carries a cache-validity mask (models/attention.py
    # _decode_mask); include its read in the measurement
    mask = jnp.ones((1, seq_len), jnp.bool_)

    block_k = extra.get("block_k")
    if block_k and impl not in ("pallas", "pallas_q8"):
        raise ValueError(f"decode block_k only applies to the pallas "
                         f"impls, got impl={impl!r}")
    if impl == "pallas":
        from ring_attention_tpu.ops.pallas_flash import pallas_flash_decode

        def attend(q, k, v, mask):
            out, _ = pallas_flash_decode(
                q, k, v, mask, block_k=int(block_k) if block_k else None
            )
            return out
    elif impl == "pallas_q8":
        # int8 cache: quantized OUTSIDE the timed loop (a live cache is
        # written quantized at decode_step time, read many times)
        from ring_attention_tpu.ops.pallas_flash import (
            pallas_flash_decode_q8,
            quantize_kv_cache,
        )

        def attend(q, kv, mask):
            out, _ = pallas_flash_decode_q8(
                q, kv, mask, block_k=int(block_k) if block_k else None
            )
            return out
    else:
        from ring_attention_tpu.ops.attention import default_attention

        def attend(q, k, v, mask):
            return default_attention(q, k, v, mask)

    iters = 50
    if impl == "pallas_q8":
        cache = (jax.jit(quantize_kv_cache)(k, v),)
        # int8 rows + f32 per-token scales actually read per step
        kv_bytes = 2 * kv_heads * seq_len * (DIM_HEAD + 4)
    else:
        cache = (k, v)
        kv_bytes = 2 * kv_heads * seq_len * DIM_HEAD * 2  # k+v, bf16

    # cache/mask as arguments, never closures: a jit-captured 537 MB cache
    # becomes an embedded constant in the compiled program
    @jax.jit
    def chained(q, cache, mask):
        def body(carry, _):
            o = attend(carry, *cache, mask)
            return carry + 1e-3 * o.astype(carry.dtype), o[0, 0, 0, 0]

        out, ys = jax.lax.scan(body, q, None, length=iters)
        return ys.astype(jnp.float32).sum()

    compile_s, secs = _timed(chained, (q, cache, mask), iters)

    # per-call latency distribution: the chained scan above gives the
    # amortized mean; this eager loop (one dispatch + block per token,
    # the shape of a live decode server) feeds the mergeable fixed-bucket
    # histogram that the perfgate latency family and generate.py share
    from ring_attention_tpu.utils import tracing

    single = jax.jit(lambda q, cache, mask: attend(q, *cache, mask))
    single(q, cache, mask).block_until_ready()  # compile outside the loop
    hist = tracing.LatencyHistogram()
    for _ in range(30):
        t0 = tracing.perf_counter()
        single(q, cache, mask).block_until_ready()
        hist.record(tracing.perf_counter() - t0)
    print(
        json.dumps(
            {
                "decode_ms_per_token": round(secs * 1e3, 3),
                "decode_ms_p50": round(hist.percentile_ms(50), 3),
                "decode_ms_p95": round(hist.percentile_ms(95), 3),
                "decode_ms_p99": round(hist.percentile_ms(99), 3),
                "decode_kv_gbps": round(kv_bytes / secs / 1e9, 1),
                "decode_seq_len": seq_len,
                "decode_impl": impl,
                "decode_kv_heads": kv_heads,
                **({"decode_block_k": int(block_k)} if block_k else {}),
                "decode_compile_s": round(compile_s, 1),
                "device": getattr(dev, "device_kind", str(dev)),
            }
        )
    )


def _bench_transformer(impl: str, vocab: int, remat_policy: str | None,
                       loss_chunk_size: int | None = None,
                       ff_chunk_size: int | None = None):
    """The ONE benchmark RingTransformer config + its init, shared by the
    train and packed workers so their tokens/sec stay comparable (same
    dims, remat, dtype; params are seq-independent so init runs on a
    short sequence to keep it cheap)."""
    import jax
    import jax.numpy as jnp

    from ring_attention_tpu.models import RingTransformer

    model = RingTransformer(
        num_tokens=vocab,
        dim=512,
        depth=2,
        causal=True,
        heads=HEADS,
        dim_head=DIM_HEAD,
        bucket_size=2048,
        rotary=True,
        use_pallas=(impl == "pallas"),
        remat=True,
        remat_policy=remat_policy,
        loss_chunk_size=loss_chunk_size,
        ff_chunk_size=ff_chunk_size,
        dtype=jnp.bfloat16,
    )
    init_tokens = jnp.zeros((1, 129), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), init_tokens, return_loss=True,
                        segment_ids=jnp.zeros((1, 129), jnp.int32))
    return model, params


def _packed_worker(impl: str, seq_len: int, extra: dict) -> None:
    """Packed vs padded train-step throughput at one position budget.

    Real corpora are unequal documents.  The *padded* batch mimics the
    classic recipe: ``docs`` fixed slots per row, each holding a document
    filling 75% of the slot plus 25% pad (pad slots carry their own
    segment id, so they attend nothing real — but they still occupy
    positions).  The *packed* batch fills every position with a document
    token under segment-id masking.  Same (1, seq_len) compiled shapes,
    same step cost structure; the honest metric is USEFUL tokens/sec —
    what the padded recipe wastes, packing recovers (the tentpole win),
    on top of the kernels skipping/masking cross-document attention.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from ring_attention_tpu.utils import make_train_step
    from ring_attention_tpu.utils.benchtime import timed_chained

    docs = int(extra.get("docs", 8))
    pad_frac = float(extra.get("pad_frac", 0.25))
    vocab = int(extra.get("vocab", 256))
    dev, _ = _device_peak()
    if seq_len % docs:
        raise ValueError(
            f"packed worker: docs={docs} must divide seq_len={seq_len}"
        )
    slot = seq_len // docs

    model, params = _bench_transformer(impl, vocab, "save_attn")
    opt = optax.adam(1e-3)

    tokens = jax.random.randint(
        jax.random.PRNGKey(1), (1, seq_len + 1), 0, vocab, jnp.int32
    )
    # segment rows span seq_len + 1 tokens (the model shifts labels off the
    # last token); the final doc simply extends one slot position
    def with_tail(row):
        return jnp.asarray(np.append(row, row[-1])[None, :])

    # packed: docs equal slots, every position useful
    seg_packed = with_tail(np.repeat(np.arange(docs, dtype=np.int32), slot))
    # padded: each slot = useful prefix + pad tail in its own segment
    useful = int(slot * (1.0 - pad_frac))
    row = np.repeat(np.arange(docs, dtype=np.int32) * 2, slot)
    for i in range(docs):
        row[i * slot + useful:(i + 1) * slot] = 2 * i + 1  # pad segment
    seg_padded = with_tail(row)

    step = make_train_step(
        lambda p, t, s: model.apply(p, t, return_loss=True, segment_ids=s),
        opt,
    )
    iters = 3 if seq_len >= 65536 else 5

    def chained(params, opt_state, tokens, segs):
        def body(carry, _):
            params, opt_state = carry
            params, opt_state, loss = step(params, opt_state, tokens, segs)
            return (params, opt_state), loss
        _, losses = jax.lax.scan(body, (params, opt_state), None, length=iters)
        return losses[-1]

    chained = jax.jit(chained)
    out = {"packed_seq_len": seq_len, "packed_docs": docs,
           "packed_pad_frac": pad_frac, "packed_impl": impl,
           "device": getattr(dev, "device_kind", str(dev))}
    for label, segs, n_useful in (
        ("packed", seg_packed, seq_len),
        ("padded", seg_padded, docs * useful),
    ):
        opt_state = opt.init(params)
        compile_s, secs = timed_chained(
            chained, (params, opt_state, tokens, segs), iters
        )
        out[f"{label}_tokens_per_sec"] = round(n_useful / secs)
        out[f"{label}_ms_per_step"] = round(secs * 1e3, 2)
        out[f"{label}_compile_s"] = round(compile_s, 1)
    out["packed_speedup"] = round(
        out["packed_tokens_per_sec"] / max(out["padded_tokens_per_sec"], 1), 3
    )
    print(json.dumps(out))


def _train_worker(impl: str, seq_len: int, remat_policy: str | None,
                  vocab: int = 256,
                  loss_chunk_size: int | None = None,
                  ff_chunk_size: int | None = None) -> None:
    """Full train step (fwd+bwd+adam) tokens/sec on one chip.

    ``remat_policy="save_attn"`` saves each layer's flash output + lse so
    the backward skips re-running the O(n^2) attention forward (VERDICT r2
    weak #1: the elective recompute cost the r2 headline ~2 s/step).
    ``vocab``/``loss_chunk_size`` measure the realistic-vocabulary
    configuration: at vocab 50257 the full-logits CE cannot fit a chip at
    262k tokens, so the chunked loss is what makes the shape trainable.
    ``ff_chunk_size`` adds the blockwise feedforward — with it, the
    train1m phase's 2^20-token step fits one chip (docs/memory.md)."""
    import jax
    import jax.numpy as jnp
    import optax

    dev, peak = _device_peak()
    model, params = _bench_transformer(impl, vocab, remat_policy,
                                       loss_chunk_size, ff_chunk_size)
    opt = optax.adam(1e-3)
    opt_state = opt.init(params)

    tokens = jax.random.randint(
        jax.random.PRNGKey(1), (1, seq_len + 1), 0, vocab, jnp.int32
    )

    from ring_attention_tpu.utils import make_train_step

    # the framework's own composed step (utils/train.py) — the bench
    # measures the API users actually call
    step = make_train_step(
        lambda p, t: model.apply(p, t, return_loss=True), opt
    )

    iters = 3 if seq_len >= 65536 else 5

    @jax.jit
    def chained(params, opt_state, tokens):
        def body(carry, _):
            params, opt_state = carry
            params, opt_state, loss = step(params, opt_state, tokens)
            return (params, opt_state), loss
        _, losses = jax.lax.scan(body, (params, opt_state), None, length=iters)
        return losses[-1]

    from ring_attention_tpu.utils.benchtime import timed_chained

    compile_s, secs, loss = timed_chained(
        chained, (params, opt_state, tokens), iters, return_value=True
    )

    # achieved MFU of the whole step (fwd+bwd+adam): XLA's counted FLOPs
    # when the backend reports them, the analytic transformer formula
    # otherwise — next to tokens/sec so a regression says WHICH of
    # "the model got slower" vs "the chip got slower" happened
    from ring_attention_tpu.utils.telemetry import (
        achieved_mfu, transformer_step_flops,
    )

    cost = _cost_fields(chained, (params, opt_state, tokens), secs, iters)
    n_params = sum(x.size for x in jax.tree.leaves(params))
    step_flops = transformer_step_flops(
        n_params, seq_len, depth=2, heads=HEADS, dim_head=DIM_HEAD,
        seq_len=seq_len, causal=True,
    )
    if cost.get("xla_flops"):
        step_flops = cost["xla_flops"] / iters
    print(
        json.dumps(
            {
                "tokens_per_sec": round(seq_len / secs),
                "train_seq_len": seq_len,
                "train_impl": impl,
                "train_remat_policy": remat_policy or "full",
                "train_vocab": vocab,
                **({"train_loss_chunk_size": loss_chunk_size}
                   if loss_chunk_size else {}),
                **({"train_ff_chunk_size": ff_chunk_size}
                   if ff_chunk_size else {}),
                "train_ms_per_step": round(secs * 1e3, 2),
                "train_compile_s": round(compile_s, 1),
                "train_loss": round(float(loss), 4),
                "train_mfu": round(achieved_mfu(step_flops, secs, peak), 4),
                "train_flops_per_step": step_flops,
                **cost,
                **_degradation_fields(),
                "device": getattr(dev, "device_kind", str(dev)),
            }
        )
    )


def _run_attempt(impl: str, seq: int, mode: str, budget: float,
                 extra: dict | None = None):
    """Subprocess-isolated measurement; returns parsed dict or error string."""
    tag = f"{mode}:{impl}@{seq}" + (
        f"[{','.join(f'{k}={v}' for k, v in extra.items())}]" if extra else ""
    )
    try:
        proc = subprocess.run(
            [
                sys.executable, os.path.abspath(__file__),
                "--worker", impl, str(seq), mode, json.dumps(extra or {}),
            ],
            capture_output=True,
            text=True,
            timeout=budget,
            cwd=os.path.dirname(os.path.abspath(__file__)),
        )
        if proc.returncode == 0:
            payload = json.loads(proc.stdout.strip().splitlines()[-1])
            if isinstance(payload, dict):
                # stamp the perf-gate history schema on every phase
                # payload (analysis/perfgate.py ingests these rounds)
                payload.setdefault("gate_schema", _gate_schema())
            return payload, None
        return None, f"{tag}: rc={proc.returncode} {proc.stderr[-200:]}"
    except subprocess.TimeoutExpired:
        return None, f"{tag}: timeout"
    except Exception:
        return None, f"{tag}: {traceback.format_exc(limit=1)}"


def _require_tpu() -> dict:
    """What jax finds, asked of one short-lived worker (the parent stays
    off jax).  Exits non-zero, naming what was found, unless it is a TPU:
    a number from another backend is never written under these names."""
    found, err = _run_attempt("any", 0, "device", 300)
    if found is None or found.get("platform") != "tpu":
        print(f"bench: no TPU: {found if found is not None else err}",
              file=sys.stderr)
        sys.exit(1)
    return found


def main() -> None:
    result = {
        "metric": (
            f"causal flash attention fwd TFLOPs/chip + train tokens/sec "
            f"(h={HEADS}, d={DIM_HEAD}, bf16)"
        ),
        "value": 0.0,
        "unit": "TFLOPs/chip",
        "vs_baseline": 0.0,
        "gate_schema": _gate_schema(),
    }
    result["device"] = _require_tpu()

    # phase 0 — collective fingerprint (CPU-only): per-strategy collective
    # counts from the contract checker, the comms half of the perf
    # trajectory
    fp, fp_err = _run_attempt(
        "cpu", 0, "fingerprint", float(os.environ.get("BENCH_FP_BUDGET_S", 420))
    )
    if fp is not None:
        result["collective_fingerprint"] = fp
    else:
        result["collective_fingerprint"] = {"error": (fp_err or "failed")[-200:]}

    # phase 0b — tile-coverage fingerprint (numpy-only): per-row
    # compact-grid tile counts, gated exactly in
    # analysis/perfgate.py next to the collective counts
    cov, cov_err = _run_attempt(
        "cpu", 0, "coverage", float(os.environ.get("BENCH_COV_BUDGET_S", 180))
    )
    if cov is not None:
        result["coverage_fingerprint"] = cov
    else:
        result["coverage_fingerprint"] = {"error": (cov_err or "failed")[-200:]}

    # phase 0d — sliding-window 262k certified-grid accounting (numpy-
    # only): the work-tile reduction the certified window grid buys over
    # causal at the north-star shape — the scenario-diversity half of the
    # mask algebra as a number in BENCH output
    win, win_err = _run_attempt(
        "cpu", 0, "window262k",
        float(os.environ.get("BENCH_WIN_BUDGET_S", 180)),
    )
    if win is not None:
        result["window262k"] = win
    else:
        result["window262k"] = {"error": (win_err or "failed")[-200:]}

    # phase 0e — multihost dryrun (CPU-only): the DCN-aware collective
    # fingerprint over the hierarchical mesh — zero ring/ulysses
    # collectives over dcn_data, machine-checked, pinned as an exact
    # perf-gate family
    mh, mh_err = _run_attempt(
        "cpu", 0, "multihost",
        float(os.environ.get("BENCH_MH_BUDGET_S", 420)),
    )
    if mh is not None:
        result["multihost_dryrun"] = mh
    else:
        result["multihost_dryrun"] = {"error": (mh_err or "failed")[-200:]}

    # phase 0f — fused-ring DMA-protocol fingerprint (CPU-only):
    # schedverify's verified hop schedule as pinned numbers —
    # derived DMA/semaphore counts, model event counts for rings 2..8,
    # zero violations — gated exactly in analysis/perfgate.py
    pr, pr_err = _run_attempt(
        "cpu", 0, "protocol",
        float(os.environ.get("BENCH_PROTO_BUDGET_S", 420)),
    )
    if pr is not None:
        result["protocol_fingerprint"] = pr
    else:
        result["protocol_fingerprint"] = {"error": (pr_err or "failed")[-200:]}

    # phase 0c — train1m memory proof (CPU-only, like the fingerprint):
    # chunked-vs-dense compiled peak temp bytes at equal shape + the
    # analytic 2^20-token peak-HBM estimate, so the memory-axis claim is a
    # number in BENCH output
    mm, mm_err = _run_attempt(
        "cpu", 0, "train1m_mem",
        float(os.environ.get("BENCH_MEM_BUDGET_S", 900)),
    )
    if mm is not None:
        result["train1m_memory"] = mm
    else:
        result["train1m_memory"] = {"error": (mm_err or "failed")[-200:]}

    deadline = time.monotonic() + float(os.environ.get("BENCH_BUDGET_S", 3600))
    log = []

    def budget_left(need: float) -> bool:
        return deadline - time.monotonic() >= need / 3

    # phase 1 — forward TFLOPs: one quick config first (guarantees a real
    # measurement), then the north-star config directly; intermediate sizes
    # only as fallbacks if the target fails.
    attempts = [
        ("xla", 8192, 420, False),
        ("pallas", TARGET_SEQ, 1500, False),
        ("pallas", 65536, 900, True),   # fallback-only
        ("pallas", 16384, 600, True),   # fallback-only
    ]
    best = None  # (impl, seq) of the best successful fwd run
    got_target = False
    got_fallback = False
    for impl, seq, budget, fallback_only in attempts:
        # fallbacks are ordered largest-first: stop after the first success
        # so a smaller one never overwrites it
        if fallback_only and (got_target or got_fallback):
            continue
        if not budget_left(budget):
            log.append(f"fwd:{impl}@{seq}: skipped (budget exhausted)")
            continue
        payload, err = _run_attempt(
            impl, seq, "fwd", min(budget, deadline - time.monotonic())
        )
        if payload is None:
            log.append(err)
            continue
        result.update(payload)
        best = (impl, seq)
        got_target = got_target or seq == TARGET_SEQ
        got_fallback = got_fallback or fallback_only
        log.append(f"fwd:{impl}@{seq}: ok")

    # phase 2 — fwd+bwd TFLOPs at the best forward config (bwd timing is
    # half the north-star training story; BASELINE.md)
    if best is not None and budget_left(900):
        impl, seq = best
        payload, err = _run_attempt(
            impl, seq, "fwdbwd", min(900, deadline - time.monotonic())
        )
        if payload is not None:
            result["fwdbwd_tflops"] = payload["value"]
            result["fwdbwd_ms_per_step"] = payload["ms_per_step"]
            result["fwdbwd_compile_s"] = payload["compile_s"]
            log.append(f"fwdbwd:{impl}@{seq}: ok")
        else:
            log.append(err)

    # phase 3 — train-step tokens/sec (fwd+bwd+adam), largest seq that
    # fits; both remat variants (save_attn skips the backward's attention
    # recompute and should lead — report both, headline the best)
    if best is not None:
        impl = best[0]
        train_seqs = []
        for s in (best[1], best[1] // 4, 8192):
            if s >= 1024 and s not in train_seqs:
                train_seqs.append(s)
        variants = {}  # policy label -> full worker payload (incl. its seq)
        for policy in ("save_attn", None):
            label = policy or "full"
            for seq in train_seqs:
                if label in variants:
                    break
                if not budget_left(1200):
                    log.append(f"train:{impl}@{seq}: skipped (budget exhausted)")
                    continue
                payload, err = _run_attempt(
                    impl, seq, "train", min(1200, deadline - time.monotonic()),
                    {"remat_policy": policy},
                )
                if payload is not None:
                    variants[label] = payload
                    # per-variant keys carry their own seq so a fallback-
                    # sized variant can never masquerade as the north star
                    result[f"tokens_per_sec_{label}"] = payload["tokens_per_sec"]
                    result[f"train_seq_len_{label}"] = payload["train_seq_len"]
                    result[f"train_ms_per_step_{label}"] = payload[
                        "train_ms_per_step"
                    ]
                    log.append(f"train:{impl}@{seq}[{label}]: ok")
                else:
                    log.append(err)
        if variants:
            # headline: largest measured seq wins; tokens/sec breaks ties
            # (tokens/sec at a shorter seq is not comparable for O(n^2) work)
            winner = max(
                variants.values(),
                key=lambda p: (p["train_seq_len"], p["tokens_per_sec"]),
            )
            result.update(winner)

    # phase 3b — packed-sequence (segment-id) train throughput vs the
    # padded recipe at the same position budget (~25% pad): the packed
    # entry (`packed262k` at the north-star seq) sits next to the train
    # tokens/sec entries; `packed_speedup` is the pad-waste recovery
    if best is not None:
        impl = best[0]
        packed_seqs = []
        for s in (TARGET_SEQ, best[1], 8192):
            if s >= 1024 and s not in packed_seqs:
                packed_seqs.append(s)
        for seq in packed_seqs:
            if not budget_left(1200):
                log.append(f"packed:{impl}@{seq}: skipped (budget exhausted)")
                continue
            payload, err = _run_attempt(
                impl, seq, "packed", min(1200, deadline - time.monotonic())
            )
            if payload is not None:
                key = "packed262k" if seq == TARGET_SEQ else f"packed{seq}"
                result[key] = payload["packed_tokens_per_sec"]
                result["packed_seq_len"] = payload["packed_seq_len"]
                result["padded_tokens_per_sec"] = payload["padded_tokens_per_sec"]
                result["packed_speedup"] = payload["packed_speedup"]
                result["packed_pad_frac"] = payload["packed_pad_frac"]
                log.append(f"packed:{impl}@{seq}: ok")
                break
            log.append(err)

    # phase 4 — ring-hop sequence on one chip: the per-device span calls a
    # real causal ring makes (resume + fused last hop).  Done criterion:
    # >= 95% of the static single-sweep fwd rate (VERDICT r2 #1).
    if got_target and budget_left(900):
        payload, err = _run_attempt(
            "pallas", TARGET_SEQ, "hops",
            min(900, deadline - time.monotonic()), {"ring": 4},
        )
        if payload is not None:
            result["ring_hops_tflops"] = payload["value"]
            result["ring_hops_ms"] = payload["ms_per_step"]
            if result.get("value"):
                result["ring_hops_frac_of_fwd"] = round(
                    payload["value"] / result["value"], 4
                )
            log.append(f"hops:pallas@{TARGET_SEQ}: ok")
        else:
            log.append(err)

    # phase 4c — hybrid Ulysses x Ring hop sequence at the same world as
    # phase 4's pure ring: world/ulysses hops on h/ulysses heads (the
    # Ulysses all-to-all legs are latency-flat; this measures the kernel
    # hop chain that remains).  `hybrid262k` sits next to the ring/ulysses
    # entries with its hop count and whole-slice tokens/sec.
    if got_target and budget_left(900):
        payload, err = _run_attempt(
            "pallas", TARGET_SEQ, "hybrid",
            min(900, deadline - time.monotonic()),
            {"world": 4, "ulysses": 2},
        )
        if payload is not None:
            result["hybrid262k"] = payload["value"]
            result["hybrid_hops"] = payload["hops"]
            result["hybrid_pure_ring_hops"] = payload["pure_ring_hops"]
            result["hybrid_ulysses"] = payload["ulysses"]
            result["hybrid_tokens_per_sec"] = payload["tokens_per_sec"]
            result["hybrid_ms"] = payload["ms_per_step"]
            if result.get("ring_hops_tflops"):
                result["hybrid_vs_ring_hops"] = round(
                    payload["value"] / result["ring_hops_tflops"], 4
                )
            log.append(f"hybrid:pallas@{TARGET_SEQ}[u2]: ok")
        else:
            log.append(err)

    # phase 4d — TokenRing counter-rotation hop chain with int8-compressed
    # KV payloads, at the same ring degree as phase 4's baseline.  The
    # compute chain includes the per-hop dequant the compressed ring pays;
    # bytes/hop + fwd/bwd collective counts ride along analytically, and
    # phase 0's collective fingerprint pins the counter/compressed hop
    # counts from compiled HLO.
    if got_target and budget_left(900):
        payload, err = _run_attempt(
            "pallas", TARGET_SEQ, "counter",
            min(900, deadline - time.monotonic()),
            {"ring": 4, "hop_compression": "int8"},
        )
        if payload is not None:
            result["counter262k"] = payload["value"]
            result["counter_hop_bytes"] = payload["hop_bytes"]
            result["counter_q_pack_bytes"] = payload["q_pack_bytes"]
            result["counter_fwd_collectives"] = payload["fwd_collectives"]
            result["counter_bwd_collectives"] = payload["bwd_collectives"]
            result["counter_tokens_per_sec"] = payload["tokens_per_sec"]
            result["counter_ms"] = payload["ms_per_step"]
            if result.get("ring_hops_tflops"):
                # dequant overhead of the compressed hop chain vs the
                # model-dtype baseline hop chain on the same device
                result["counter_vs_ring_hops"] = round(
                    payload["value"] / result["ring_hops_tflops"], 4
                )
            log.append(f"counter:pallas@{TARGET_SEQ}[int8]: ok")
        else:
            log.append(err)

    # phase 4e — fwd262k_q8: the int8 COMPUTE hop chain (PR 13) at the
    # same ring degree — quantized QK^T/PV kernels fed directly from the
    # once-quantized hop payload (no per-hop dequant), f32 accumulators
    # resumed in-kernel.  ROADMAP item 3's acceptance number: on silicon
    # this should beat the fused bf16 fwd (int8 MXU ~2x peak); operand/
    # accumulator byte accounting rides the JSON, the counter_q8 HLO
    # fingerprint (phase 0) and the ring8_262k_q8 comms row are the
    # CPU-side signals.
    if got_target and budget_left(900):
        payload, err = _run_attempt(
            "pallas", TARGET_SEQ, "q8",
            min(900, deadline - time.monotonic()),
            {"ring": 4},
        )
        if payload is not None:
            result["fwd262k_q8"] = payload["value"]
            result["fwd262k_q8_tokens_per_sec"] = payload["tokens_per_sec"]
            result["fwd262k_q8_ms"] = payload["ms_per_step"]
            result["fwd262k_q8_hop_bytes"] = payload["hop_bytes"]
            result["fwd262k_q8_operand_bytes"] = (
                payload["matmul_operand_bytes"]
            )
            result["fwd262k_q8_accumulator_bytes"] = (
                payload["accumulator_bytes"]
            )
            if result.get("ring_hops_tflops"):
                # the int8-vs-bf16 matmul-feed speedup on the same device
                # and hop schedule (>1 = the MXU rate win materialized)
                result["fwd262k_q8_vs_ring_hops"] = round(
                    payload["value"] / result["ring_hops_tflops"], 4
                )
            log.append(f"q8:pallas@{TARGET_SEQ}[int8-compute]: ok")
        else:
            log.append(err)

    # phase 4f — fused262k (PR 18): the same hop chain as phase 4, ONE
    # kernel launch — ops/pallas_ring.py sweeps every hop's span with the
    # f32 carry resident in VMEM, so fused_vs_ring_hops is the measured
    # launch-boundary cost the fused path deletes.  The analytic row
    # (kernel_launches=1, dispatch overhead 0, fwd_collectives=0, overlap
    # ~1.0) rides along; phase 0's fused_ring fingerprint pins the
    # in-kernel remote-DMA counts (zero ppermutes) from lowered Mosaic.
    if got_target and budget_left(900):
        payload, err = _run_attempt(
            "pallas", TARGET_SEQ, "fused",
            min(900, deadline - time.monotonic()),
            {"ring": 4},
        )
        if payload is not None:
            result["fused262k"] = payload["value"]
            result["fused_kernel_launches"] = payload["kernel_launches"]
            result["fused_fwd_collectives"] = payload["fwd_collectives"]
            result["fused_overlap_fraction"] = payload["hop_overlap_fraction"]
            result["fused_tokens_per_sec"] = payload["tokens_per_sec"]
            result["fused_ms"] = payload["ms_per_step"]
            if result.get("ring_hops_tflops"):
                # launch-free-hops dividend: one launch vs ring launches
                # on the identical span schedule and device
                result["fused_vs_ring_hops"] = round(
                    payload["value"] / result["ring_hops_tflops"], 4
                )
            log.append(f"fused:pallas@{TARGET_SEQ}[1-launch]: ok")
        else:
            log.append(err)

    # phase 5 — BASELINE.json config-4 GQA shape (heads=32, kv 4) and a
    # d=128 variant.  h=32 x seq 262144 first, then 131072.
    for extra, key, seqs in (
        ({"heads": 32, "kv_heads": 4}, "gqa32_tflops", (TARGET_SEQ, 131072)),
        ({"dim_head": 128}, "d128_tflops", (TARGET_SEQ, 131072)),
    ):
        for seq in seqs:
            if key in result:
                break
            if not budget_left(900):
                log.append(f"fwd:pallas@{seq}[{key}]: skipped (budget)")
                continue
            payload, err = _run_attempt(
                "pallas", seq, "fwd",
                min(900, deadline - time.monotonic()), extra,
            )
            if payload is not None:
                result[key] = payload["value"]
                result[key.replace("_tflops", "_seq_len")] = seq
                result[key.replace("_tflops", "_mfu")] = payload["vs_baseline"]
                log.append(f"fwd:pallas@{seq}[{key}]: ok")
            else:
                log.append(err)

    # phase 6 — million-token decode (BASELINE config 5): ms/token against
    # a 2^20-token GQA cache — decode kernel, int8-cache kernel, dense tile
    for impl in ("pallas", "pallas_q8", "dense"):
        if not budget_left(600):
            log.append(f"decode:{impl}: skipped (budget)")
            continue
        payload, err = _run_attempt(
            impl, 1 << 20, "decode", min(600, deadline - time.monotonic())
        )
        if payload is not None:
            suffix = {"pallas": "", "pallas_q8": "_q8", "dense": "_dense"}[impl]
            for key in ("decode_ms_per_token", "decode_kv_gbps"):
                result[key + suffix] = payload[key]
            for key in ("decode_ms_p50", "decode_ms_p95", "decode_ms_p99"):
                if key in payload:
                    result[key + suffix] = payload[key]
            if impl == "pallas":
                result["decode_seq_len"] = payload["decode_seq_len"]
                result["decode_kv_heads"] = payload["decode_kv_heads"]
            log.append(f"decode:{impl}@{1 << 20}: ok")
        else:
            log.append(err)

    # phase 7 — train1m (ROADMAP item 4): the 2^20-token train step on one
    # chip — blockwise FFN + chunked CE + save_attn, the configuration the
    # memory phase (0c) proves fits.  tokens/sec plus the compiled
    # peak-memory fields land next to counter262k.
    if best is not None and budget_left(1800):
        payload, err = _run_attempt(
            best[0], 1 << 20, "train",
            min(1800, deadline - time.monotonic()),
            {"remat_policy": "save_attn", "loss_chunk_size": 2048,
             "ff_chunk_size": 2048},
        )
        if payload is not None:
            result["train1m"] = payload["tokens_per_sec"]
            result["train1m_tokens_per_sec"] = payload["tokens_per_sec"]
            result["train1m_ms_per_step"] = payload["train_ms_per_step"]
            result["train1m_compile_s"] = payload["train_compile_s"]
            for key in ("temp_bytes", "argument_bytes"):
                if key in payload:
                    result[f"train1m_{key}"] = payload[key]
            log.append(f"train1m:{best[0]}@{1 << 20}: ok")
        else:
            log.append(err)

    # keep the attempt trail even on success so a fallback-sized result is
    # never mistaken for a clean north-star run round-over-round
    result["attempts"] = " | ".join(log)[-900:]
    if best is None:
        print(f"bench: every device phase failed: {result['attempts']}",
              file=sys.stderr)
        sys.exit(1)
    print(json.dumps(result))


if __name__ == "__main__":
    if len(sys.argv) > 1 and sys.argv[1] == "--worker":
        mode = sys.argv[4] if len(sys.argv) > 4 else "fwd"
        extra = json.loads(sys.argv[5]) if len(sys.argv) > 5 else {}
        if mode == "device":
            _device_worker()
        elif mode == "fingerprint":
            # env setup must precede the first jax import (see the worker)
            _fingerprint_worker()
        elif mode == "multihost":
            _multihost_worker()
        elif mode == "protocol":
            _protocol_worker()
        elif mode == "coverage":
            _coverage_worker()
        elif mode == "window262k":
            _window262k_worker(extra)
        elif mode == "train1m_mem":
            # likewise CPU-forced before the first jax import
            _train1m_mem_worker(extra)
        else:
            _worker(sys.argv[2], int(sys.argv[3]), mode, extra)
    else:
        main()
