#!/usr/bin/env python
"""First real use of the profiling subsystem (VERDICT r3 next #6).

Captures XProf traces of (a) the fused forward kernel and (b) a full
train step on the live chip via ``ring_attention_tpu.utils.profiling``,
then parses the xplane protobuf to report where device time goes (the
MXU/VPU/DMA split that directs the next MFU push).  Traces land in
``docs/hwlogs/xprof/``, the summary in ``docs/hwlogs/xprof_summary.txt``.

Run through the chip tool (only the process that holds the chip can
trace it).
"""

from __future__ import annotations

import glob
import os
import sys
from collections import defaultdict

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TRACE_ROOT = os.path.join(REPO, "docs", "hwlogs", "xprof")
SUMMARY = os.path.join(REPO, "docs", "hwlogs", "xprof_summary.txt")

SEQ = 65536  # warm-compile shape with known rates (68.7 TFLOPs fwd)
HEADS, DIM_HEAD = 8, 64


def _parse_args():
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--seq", type=int, default=SEQ,
                    help="trace shape; the CPU preflight shrinks this so "
                         "the capture path is launchable without silicon "
                         "(kernels auto-select interpret mode off-TPU)")
    ap.add_argument("--out-dir", default=None,
                    help="trace/summary root override (the CPU preflight "
                         "points this at a temp dir so docs/hwlogs/ only "
                         "ever holds real silicon traces)")
    return ap.parse_args()


def _categorize(name: str) -> str:
    n = name.lower()
    if any(t in n for t in ("dot", "convolution", "matmul", "mxu")):
        return "MXU (dot/conv)"
    if "custom-call" in n or "mosaic" in n or "tpu_custom_call" in n:
        return "Pallas kernel (custom-call)"
    if any(t in n for t in ("copy", "dynamic-update", "dynamic-slice",
                            "transpose", "reshape", "broadcast", "pad",
                            "concatenate", "slice")):
        return "data movement"
    if any(t in n for t in ("all-reduce", "all-gather", "collective",
                            "permute", "reduce-scatter")):
        return "collectives"
    if "fusion" in n:
        return "XLA fusion (VPU/elementwise)"
    if "infeed" in n or "outfeed" in n or "host" in n:
        return "host transfer"
    return "other"


def summarize(trace_dir: str, tag: str, out: list[str]) -> None:
    # parsing is best-effort: the traces on disk are the scarce artifact
    # (captured in a healthy TPU window); a missing/broken proto parser
    # must not fail the step and burn a re-capture on the next window
    try:
        from tensorflow.tsl.profiler.protobuf import xplane_pb2  # noqa: E501 (the one xplane proto in this image)
    except Exception as e:  # ImportError or any TF-init failure
        out.append(
            f"[{tag}] xplane parser unavailable ({type(e).__name__}: {e}); "
            f"traces saved under {trace_dir} — parse offline"
        )
        return

    paths = glob.glob(
        os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True
    )
    if not paths:
        out.append(f"[{tag}] no .xplane.pb produced under {trace_dir}")
        return
    space = xplane_pb2.XSpace()
    with open(max(paths, key=os.path.getmtime), "rb") as f:
        space.ParseFromString(f.read())

    device_planes = [
        p for p in space.planes
        if "TPU" in p.name or "/device:" in p.name
    ] or list(space.planes)
    out.append(f"[{tag}] planes: {[p.name for p in space.planes]}")
    for plane in device_planes:
        # "XLA Modules" / "Steps" lines nest the "XLA Ops" line's events;
        # summing every line would double-count, so keep only the op line
        # when the plane has one (the TPU device-plane convention)
        op_lines = [l for l in plane.lines if "XLA Ops" in l.name]
        lines = op_lines or plane.lines
        per_op: dict[str, float] = defaultdict(float)
        span_lo, span_hi = float("inf"), 0.0
        for line in lines:
            for ev in line.events:
                meta = plane.event_metadata.get(ev.metadata_id)
                name = meta.name if meta else str(ev.metadata_id)
                dur = ev.duration_ps / 1e9  # -> ms
                per_op[name] += dur
                span_lo = min(span_lo, ev.offset_ps / 1e9)
                span_hi = max(span_hi, (ev.offset_ps + ev.duration_ps) / 1e9)
        if not per_op:
            continue
        busy = sum(per_op.values())
        span = max(span_hi - span_lo, 1e-9)
        cats: dict[str, float] = defaultdict(float)
        for name, ms in per_op.items():
            cats[_categorize(name)] += ms
        out.append(
            f"[{tag}] plane '{plane.name}': busy {busy:.2f} ms over a "
            f"{span:.2f} ms span ({100 * busy / span:.1f}% occupancy)"
        )
        for cat, ms in sorted(cats.items(), key=lambda kv: -kv[1]):
            out.append(f"[{tag}]   {cat:32s} {ms:10.3f} ms "
                       f"({100 * ms / busy:5.1f}% of busy)")
        top = sorted(per_op.items(), key=lambda kv: -kv[1])[:12]
        out.append(f"[{tag}]   top ops:")
        for name, ms in top:
            out.append(f"[{tag}]     {ms:9.3f} ms  {name[:90]}")


def main() -> int:
    import jax
    import jax.numpy as jnp

    from ring_attention_tpu.ops.pallas_flash import pallas_flash_fused
    from ring_attention_tpu.utils import enable_compile_cache
    from ring_attention_tpu.utils.profiling import trace

    args = _parse_args()
    seq = args.seq
    trace_root, summary = TRACE_ROOT, SUMMARY
    if args.out_dir:
        trace_root = os.path.join(args.out_dir, "xprof")
        summary = os.path.join(args.out_dir, "xprof_summary.txt")
    enable_compile_cache()

    os.makedirs(trace_root, exist_ok=True)
    out: list[str] = []
    dev = jax.devices()[0]
    out.append(f"device: {dev.device_kind} ({dev.platform})")

    # --- phase 1: fused fwd kernel ------------------------------------
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (1, HEADS, seq, DIM_HEAD), jnp.bfloat16)
    k = jax.random.normal(ks[1], (1, HEADS, seq, DIM_HEAD), jnp.bfloat16)
    v = jax.random.normal(ks[2], (1, HEADS, seq, DIM_HEAD), jnp.bfloat16)

    @jax.jit
    def fwd(q, k, v):
        o, _ = pallas_flash_fused(
            q, k, v, scale=DIM_HEAD**-0.5, causal_offset=0,
            block_q=1024, block_k=1024,
        )
        return o

    compiled = fwd.lower(q, k, v).compile()
    ca = compiled.cost_analysis()
    if ca:
        ca = ca[0] if isinstance(ca, (list, tuple)) else ca
        out.append(
            f"fwd cost_analysis: flops={ca.get('flops', 0):.3e} "
            f"bytes accessed={ca.get('bytes accessed', 0):.3e}"
        )
    jax.block_until_ready(fwd(q, k, v))  # warm outside the trace
    fwd_dir = os.path.join(trace_root, "fwd")
    with trace(fwd_dir):
        for _ in range(5):
            r = fwd(q, k, v)
        jax.block_until_ready(r)
    summarize(fwd_dir, "fwd-kernel", out)

    # --- phase 2: train step (flagship config, save_attn remat) -------
    import optax

    from ring_attention_tpu.models import RingTransformer
    from ring_attention_tpu.utils import make_train_step

    model = RingTransformer(
        num_tokens=256, dim=512, depth=2, causal=True, heads=HEADS,
        dim_head=DIM_HEAD, bucket_size=min(2048, max(seq // 4, 8)),
        rotary=True, use_pallas=True,
        remat=True, remat_policy="save_attn", dtype=jnp.bfloat16,
    )
    params = model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 129), jnp.int32),
        return_loss=True,
    )
    opt = optax.adam(1e-3)
    opt_state = opt.init(params)
    tokens = jax.random.randint(
        jax.random.PRNGKey(1), (1, seq + 1), 0, 256, jnp.int32
    )
    step = jax.jit(make_train_step(
        lambda p, t: model.apply(p, t, return_loss=True), opt
    ))
    params, opt_state, loss = step(params, opt_state, tokens)  # warm
    jax.block_until_ready(loss)
    train_dir = os.path.join(trace_root, "train")
    with trace(train_dir):
        params, opt_state, loss = step(params, opt_state, tokens)
        jax.block_until_ready(loss)
    out.append(f"train step loss={float(loss):.4f}")
    summarize(train_dir, "train-step", out)

    text = "\n".join(out)
    print(text)
    with open(summary, "w") as f:
        f.write(text + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
