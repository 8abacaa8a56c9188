"""One-shot chip validation + block sweep for the Pallas kernels.

Run through the chip tool (one chip is enough):

    python tools/tpu_kernel_validate.py [--seq 262144] [--sweep]

Prints JSON lines: a parity check of the compact causal grid against the
rectangular grid and the dense oracle, then timed fwd / fwd+bwd
measurements (chained timing, ``utils/benchtime.py``), and optionally a
block-size sweep.  ``chip_smoke.py`` is the quick did-it-start check;
this is the longer per-kernel one.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

try:  # prefer the installed package (pip install -e .)
    import ring_attention_tpu  # noqa: F401
except ModuleNotFoundError:  # running from a source checkout, any cwd
    sys.path.insert(
        0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    )


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seq", type=int, default=262144)
    ap.add_argument("--sweep", action="store_true")
    ap.add_argument("--heads", type=int, default=8)
    ap.add_argument("--kv-heads", type=int, default=None,
                    help="GQA: fewer KV heads (BASELINE config 4 is 32/4)")
    ap.add_argument("--dim-head", type=int, default=64)
    ap.add_argument("--interpret", action="store_true",
                    help="run kernels in interpret mode (CPU preflight of "
                         "this tool's queued invocations; no Mosaic)")
    ap.add_argument("--segments", type=int, default=None, metavar="N",
                    help="packed-sequence sweep: N equal block-aligned "
                         "documents — parity vs the per-document oracle, "
                         "compact-grid tile counts (trace-time doc skip), "
                         "and timed fwd packed vs plain causal")
    ap.add_argument("--q8", action="store_true",
                    help="int8 compute sweep (PR 13): parity of the "
                         "quantized QK^T/PV kernels vs bf16 at the small "
                         "shape, then timed int8 fwd per (block, head-dim) "
                         "next to the bf16 rows — on silicon the int8 MXU "
                         "rate is ~2x bf16 peak (docs/precision.md)")
    ap.add_argument("--fused", action="store_true",
                    help="fused-ring sweep (PR 18): parity of the single-"
                         "launch fused hop chain (ops/pallas_ring.py, "
                         "in-kernel carry across hops) vs the scan-path "
                         "span sequence and the dense oracle at the small "
                         "shape, then a timed fused fwd per block size at "
                         "--seq — the launch-boundary cost the fused path "
                         "deletes, readable against the plain fwd rows")
    ap.add_argument("--hybrid", type=int, default=None, metavar="U",
                    help="hybrid Ulysses x Ring sweep: for every factoring "
                         "(u, r) of the available devices with u <= U, "
                         "oracle parity of the 2-D factored attention at "
                         "the small shape plus a timed fwd at --seq — on a "
                         "multi-chip slice this measures the real "
                         "all-to-all + shortened-ring collectives "
                         "(docs/hybrid_parallelism.md)")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    from ring_attention_tpu.utils import enable_compile_cache

    # persistent executable cache, shared by a chip call's processes
    enable_compile_cache()

    from ring_attention_tpu.ops.attention import default_attention
    from ring_attention_tpu.ops.pallas_flash import (
        finalize_partials,
        pallas_flash_attention,
        pallas_flash_partials,
    )
    from ring_attention_tpu.utils.benchtime import timed_chained

    dev = jax.devices()[0]
    print(json.dumps({"device": getattr(dev, "device_kind", str(dev))}))
    h, d = args.heads, args.dim_head
    hk = args.kv_heads or h
    scale = d**-0.5

    # ---- parity at a small shape: compact grid vs rectangular vs oracle
    n0 = 2048
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (1, h, n0, d), jnp.bfloat16)
    k, v = (jax.random.normal(kk, (1, hk, n0, d), jnp.bfloat16) for kk in ks[1:])
    compact = finalize_partials(
        pallas_flash_partials(q, k, v, scale=scale, causal_offset=0,
                              interpret=args.interpret)
    )[0]
    rect = finalize_partials(
        jax.jit(
            lambda q, k, v, o: pallas_flash_partials(
                q, k, v, scale=scale, causal_offset=o, interpret=args.interpret
            )
        )(q, k, v, jnp.int32(0))
    )[0]
    oracle = default_attention(
        q.astype(jnp.float32), k.astype(jnp.float32), v.astype(jnp.float32),
        causal=True,
    )
    print(json.dumps({
        "parity_seq": n0,
        "compact_vs_rect_max_err": float(jnp.abs(compact - rect).max()),
        "compact_vs_oracle_max_err": float(jnp.abs(compact - oracle).max()),
    }))

    # ---- packed-sequence (--segments N) sweep
    if args.segments:
        import numpy as np

        from ring_attention_tpu.ops.pallas_flash import band_plan

        n_docs = args.segments
        if n0 % n_docs:
            # a scarce TPU window must not die on an unlucky N: report and
            # continue with the rest of the sweep (same convention as the
            # tile-accounting section below)
            print(json.dumps({
                "segments": n_docs, "parity_seq": n0,
                "note": f"--segments must divide the parity length {n0}; "
                        f"skipping the packed parity check",
            }))
            n_docs = None
    if args.segments and n_docs:
        # parity at the small shape: N equal docs, runtime segment ids AND
        # the trace-time doc-skip tables, both vs the per-document oracle
        doc_len = n0 // n_docs
        starts = tuple(range(0, n0, doc_len))
        seg = jnp.asarray(
            np.repeat(np.arange(n_docs, dtype=np.int32), doc_len)[None, :]
        )
        packed_rt = finalize_partials(
            pallas_flash_partials(q, k, v, scale=scale, causal_offset=0,
                                  segment_ids=seg, interpret=args.interpret)
        )[0]
        packed_tt = finalize_partials(
            pallas_flash_partials(q, k, v, scale=scale, causal_offset=0,
                                  doc_starts=starts, interpret=args.interpret)
        )[0]
        per_doc = jnp.concatenate(
            [
                default_attention(
                    q[:, :, s:s + doc_len].astype(jnp.float32),
                    k[:, :, s:s + doc_len].astype(jnp.float32),
                    v[:, :, s:s + doc_len].astype(jnp.float32),
                    causal=True,
                )
                for s in starts
            ],
            axis=2,
        )
        print(json.dumps({
            "segments": n_docs, "parity_seq": n0,
            "runtime_vs_per_doc_max_err":
                float(jnp.abs(packed_rt - per_doc).max()),
            "tables_vs_per_doc_max_err":
                float(jnp.abs(packed_tt - per_doc).max()),
        }))

        # tile accounting at the target shape: how much of the compact
        # causal grid the declared packing drops at trace time
        bq = bk = 1024
        if args.seq % n_docs == 0 and (args.seq // n_docs) % bq == 0:
            starts_t = tuple(range(0, args.seq, args.seq // n_docs))
            plain = band_plan((args.seq, args.seq), (bq, bk), 0)
            docs_p = band_plan((args.seq, args.seq), (bq, bk), 0,
                               doc_starts=starts_t)
            print(json.dumps({
                "segments": n_docs, "seq": args.seq, "block": bq,
                "work_tiles_plain": plain.work_tiles,
                "work_tiles_docs": docs_p.work_tiles,
                "tiles_dropped_frac": round(
                    1 - docs_p.work_tiles / plain.work_tiles, 4
                ),
                "compact": docs_p.compact,
                "doc_aligned": docs_p.doc_aligned,
            }))
        else:
            print(json.dumps({
                "segments": n_docs, "seq": args.seq,
                "note": "seq must split into N block-aligned docs for the "
                        "tile accounting",
            }))

    # ---- int8 compute sweep (--q8): parity at the small shape, then the
    # timed section below adds int8 rows per (block, head-dim)
    if args.q8:
        q8_small = finalize_partials(
            pallas_flash_partials(q, k, v, scale=scale, causal_offset=0,
                                  compute_dtype="int8",
                                  interpret=args.interpret)
        )[0]
        print(json.dumps({
            "mode": "q8-parity", "parity_seq": n0,
            "q8_vs_bf16_max_err": float(jnp.abs(
                q8_small.astype(jnp.float32) - compact.astype(jnp.float32)
            ).max()),
            "q8_vs_oracle_max_err": float(jnp.abs(
                q8_small.astype(jnp.float32) - oracle
            ).max()),
        }))

    # ---- fused-ring parity (--fused): the single-launch hop chain for the
    # causal last rank of a ring=4 slice of the parity shape, vs the same
    # rows of the scan-path compact grid (both f32-accumulated Pallas —
    # expected bit-exact) and the dense oracle
    if args.fused:
        from ring_attention_tpu.ops.pallas_ring import fused_ring_local
        from ring_attention_tpu.parallel.ring import _fused_tables

        f_ring = 4
        f_n = n0 // f_ring
        origins, his, los, works = _fused_tables(
            f_ring - 1, f_ring, f_n, True, False, None, f_ring
        )
        fused_small = fused_ring_local(
            q[:, :, -f_n:], k, v,
            origins=origins, his=his, los=los, works=works,
            n_local=f_n, scale=scale, interpret=args.interpret,
        )[0]
        print(json.dumps({
            "mode": "fused-parity", "parity_seq": n0, "ring": f_ring,
            "fused_vs_scan_max_err": float(jnp.abs(
                fused_small.astype(jnp.float32)
                - compact[:, :, -f_n:].astype(jnp.float32)
            ).max()),
            "fused_vs_oracle_max_err": float(jnp.abs(
                fused_small.astype(jnp.float32) - oracle[:, :, -f_n:]
            ).max()),
        }))

    # ---- hybrid Ulysses x Ring sweep (--hybrid U): parity + timed fwd at
    # each factoring of the available devices.  u == 1 is the pure-ring
    # baseline the other rows are read against; each row reports its ring
    # hop count so the hop-chain shrinkage is visible next to the timing.
    if args.hybrid:
        from functools import partial

        from jax.sharding import NamedSharding, PartitionSpec as P

        from ring_attention_tpu.parallel import (
            create_mesh,
            hybrid_attention,
            ring_flash_attention,
            seq_partition,
        )
        from ring_attention_tpu.utils.compat import shard_map

        n_dev = len(jax.devices())
        factorings = [
            (u, n_dev // u)
            for u in range(1, min(args.hybrid, n_dev) + 1)
            if n_dev % u == 0
        ]
        # the functional hybrid/ring entry points pick interpret mode from
        # the platform, not per-call — so --interpret (the no-Mosaic
        # preflight contract) routes the sweep through the XLA compute
        # path instead; without it the real Mosaic kernels run on TPU
        sweep_impl = "xla" if args.interpret else "pallas"
        ksp = jax.random.split(jax.random.PRNGKey(3), 3)
        qs = jax.random.normal(ksp[0], (1, h, n0, d), jnp.bfloat16)
        ks_, vs = (
            jax.random.normal(kk, (1, hk, n0, d), jnp.bfloat16)
            for kk in ksp[1:]
        )
        oracle_s = default_attention(
            qs.astype(jnp.float32), ks_.astype(jnp.float32),
            vs.astype(jnp.float32), causal=True,
        )
        seq_flops = 2 * 2 * args.seq * args.seq * h * d * 0.5
        for u, r in factorings:
            if h % u:
                print(json.dumps({
                    "mode": "hybrid", "ulysses": u, "ring": r,
                    "note": f"{h} heads do not divide over u={u}; skipped",
                }))
                continue
            try:
                mesh = (
                    create_mesh(ulysses_size=u, ring_size=r, data_size=1)
                    if u > 1 else create_mesh(ring_size=r, data_size=1)
                )
                spec = P("data", None, seq_partition(mesh), None)
                if u > 1:
                    core = partial(
                        hybrid_attention, kv_mask=None,
                        ulysses_axis="ulysses", ring_axis="ring",
                        causal=True, impl=sweep_impl,
                    )
                else:
                    core = partial(
                        ring_flash_attention, kv_mask=None, axis_name="seq",
                        causal=True, impl=sweep_impl,
                    )
                attn = shard_map(
                    core, mesh=mesh, in_specs=(spec,) * 3, out_specs=spec,
                    check_vma=False,
                )
                err = float(jnp.abs(
                    attn(qs, ks_, vs).astype(jnp.float32) - oracle_s
                ).max())
                print(json.dumps({
                    "mode": "hybrid-parity", "ulysses": u, "ring": r,
                    "impl": sweep_impl, "parity_seq": n0, "hops": r - 1,
                    "max_err_vs_oracle": err,
                }))

                sharding = NamedSharding(mesh, spec)
                kst = jax.random.split(jax.random.PRNGKey(4), 3)
                qt = jax.device_put(jax.random.normal(
                    kst[0], (1, h, args.seq, d), jnp.bfloat16), sharding)
                kt = jax.device_put(jax.random.normal(
                    kst[1], (1, hk, args.seq, d), jnp.bfloat16), sharding)
                vt = jax.device_put(jax.random.normal(
                    kst[2], (1, hk, args.seq, d), jnp.bfloat16), sharding)

                @jax.jit
                def chained(q, k, v, attn=attn):
                    def body(c, _):
                        o = attn(c, k, v)
                        return c + 1e-3 * o.astype(c.dtype), o[0, 0, 0, 0]
                    _, ys = jax.lax.scan(body, q, None, length=3)
                    return ys.astype(jnp.float32).sum()

                compile_s, secs = timed_chained(chained, (qt, kt, vt), 3)
                print(json.dumps({
                    "mode": "hybrid-fwd", "seq": args.seq,
                    "ulysses": u, "ring": r, "hops": r - 1,
                    "impl": sweep_impl,
                    # 4 decimals: CPU-backend preflights land in the 1e-3
                    # TFLOPs range and must not round to zero
                    "tflops": round(seq_flops / secs / 1e12, 4),
                    "ms": round(secs * 1e3, 1),
                    "compile_s": round(compile_s, 1),
                }))
            except Exception as e:  # noqa: BLE001 - sweep survives rejects
                print(json.dumps({
                    "mode": "hybrid", "ulysses": u, "ring": r,
                    "error": f"{type(e).__name__}: {str(e)[:160]}",
                }))

    # ---- timing at the target shape
    seq = args.seq
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (1, h, seq, d), jnp.bfloat16)
    k, v = (jax.random.normal(kk, (1, hk, seq, d), jnp.bfloat16) for kk in ks[1:])
    flops_fwd = 2 * 2 * seq * seq * h * d * 0.5

    def fwd_chained(bq, bk, iters, doc_starts=None, compute_dtype=None,
                    sweep_scale=None):
        # one timing harness for every fwd row (bf16, packed, q8, d128):
        # rows are read against each other, so they must measure the
        # same chained computation
        row_scale = scale if sweep_scale is None else sweep_scale

        @jax.jit
        def chained(q, k, v):
            def body(c, _):
                p = pallas_flash_partials(
                    c, k, v, scale=row_scale, causal_offset=0,
                    block_q=bq, block_k=bk, interpret=args.interpret,
                    doc_starts=doc_starts, compute_dtype=compute_dtype,
                )
                o = finalize_partials(p)[0]
                return c + 1e-3 * o.astype(c.dtype), p.m[0, 0, 0]
            _, ys = jax.lax.scan(body, q, None, length=iters)
            return ys.sum()
        return chained

    iters = 3
    pairs = (
        [(512, 512), (512, 1024), (1024, 1024), (1024, 2048), (2048, 512)]
        if args.sweep
        else [(None, None)]
    )
    for bq, bk in pairs:
        try:
            compile_s, secs = timed_chained(
                fwd_chained(bq, bk, iters), (q, k, v), iters
            )
            print(json.dumps({
                "mode": "fwd", "seq": seq, "block_q": bq, "block_k": bk,
                "tflops": round(flops_fwd / secs / 1e12, 1),
                "ms": round(secs * 1e3, 1), "compile_s": round(compile_s, 1),
            }))
        except Exception as e:  # noqa: BLE001 - sweep must survive rejects
            print(json.dumps({
                "mode": "fwd", "seq": seq, "block_q": bq, "block_k": bk,
                "error": f"{type(e).__name__}: {str(e)[:160]}",
            }))

    # ---- int8 timed fwd (--q8): same (block_q, block_k) grid as the
    # bf16 sweep above at the configured head dim, plus a d=128 row —
    # "per (block, head-dim)" so the int8 MXU win is readable against the
    # bf16 rows it sits next to (vs_bf16_peak > 1.0 is the win, not an
    # accounting error: the TFLOPs are counted against useful flops)
    if args.q8:
        for bq, bk in pairs:
            try:
                compile_s, secs = timed_chained(
                    fwd_chained(bq, bk, iters, compute_dtype="int8"),
                    (q, k, v), iters,
                )
                print(json.dumps({
                    "mode": "fwd-q8", "seq": seq, "dim_head": d,
                    "block_q": bq, "block_k": bk,
                    "tflops": round(flops_fwd / secs / 1e12, 1),
                    "ms": round(secs * 1e3, 1),
                    "compile_s": round(compile_s, 1),
                }))
            except Exception as e:  # noqa: BLE001 - sweep survives rejects
                print(json.dumps({
                    "mode": "fwd-q8", "seq": seq, "dim_head": d,
                    "block_q": bq, "block_k": bk,
                    "error": f"{type(e).__name__}: {str(e)[:160]}",
                }))
        d128 = 128
        ks128 = jax.random.split(jax.random.PRNGKey(5), 3)
        q128 = jax.random.normal(ks128[0], (1, h, seq, d128), jnp.bfloat16)
        k128, v128 = (
            jax.random.normal(kk, (1, hk, seq, d128), jnp.bfloat16)
            for kk in ks128[1:]
        )

        try:
            compile_s, secs = timed_chained(
                fwd_chained(1024, 1024, iters, compute_dtype="int8",
                            sweep_scale=d128**-0.5),
                (q128, k128, v128), iters,
            )
            print(json.dumps({
                "mode": "fwd-q8", "seq": seq, "dim_head": d128,
                "block_q": 1024, "block_k": 1024,
                "tflops": round(
                    2 * 2 * seq * seq * h * d128 * 0.5 / secs / 1e12, 1
                ),
                "ms": round(secs * 1e3, 1),
                "compile_s": round(compile_s, 1),
            }))
        except Exception as e:  # noqa: BLE001
            print(json.dumps({
                "mode": "fwd-q8", "seq": seq, "dim_head": d128,
                "error": f"{type(e).__name__}: {str(e)[:160]}",
            }))

    # ---- fused-ring timed fwd (--fused): the ONE-launch hop chain at the
    # target shape per (block_q, block_k), same span schedule and flop
    # accounting as the plain fwd rows above — the row-to-row delta is
    # the measured launch-boundary + carry-rematerialization cost the
    # fused kernel deletes
    if args.fused:
        f_ring = 4
        if seq % f_ring or (seq // f_ring) % 1024:
            print(json.dumps({
                "mode": "fused-fwd", "seq": seq,
                "note": f"--seq must split into {f_ring} block-aligned "
                        "shards for the fused timing",
            }))
        else:
            f_n = seq // f_ring
            tables_t = _fused_tables(
                f_ring - 1, f_ring, f_n, True, False, None, f_ring
            )

            def fused_chained(bq, bk):
                @jax.jit
                def chained(qf, k, v):
                    def body(c, _):
                        o, _lse = fused_ring_local(
                            c, k, v, origins=tables_t[0], his=tables_t[1],
                            los=tables_t[2], works=tables_t[3],
                            n_local=f_n, scale=scale, block_q=bq, block_k=bk,
                            interpret=args.interpret,
                        )
                        return c + 1e-3 * o.astype(c.dtype), o[0, 0, 0, 0]
                    _, ys = jax.lax.scan(body, qf, None, length=iters)
                    return ys.astype(jnp.float32).sum()
                return chained

            qf = jax.random.normal(
                jax.random.PRNGKey(6), (1, h, f_n, d), jnp.bfloat16
            )
            # last-rank causal work: half the diagonal span + R-1 full spans
            flops_fused = 2 * 2 * h * d * f_n * f_n * (f_ring - 0.5)
            for bq, bk in pairs:
                try:
                    compile_s, secs = timed_chained(
                        fused_chained(bq, bk), (qf, k, v), iters
                    )
                    print(json.dumps({
                        "mode": "fused-fwd", "seq": seq, "ring": f_ring,
                        "block_q": bq, "block_k": bk, "kernel_launches": 1,
                        "tflops": round(flops_fused / secs / 1e12, 4),
                        "ms": round(secs * 1e3, 1),
                        "compile_s": round(compile_s, 1),
                    }))
                except Exception as e:  # noqa: BLE001 - sweep survives rejects
                    print(json.dumps({
                        "mode": "fused-fwd", "seq": seq, "ring": f_ring,
                        "block_q": bq, "block_k": bk,
                        "error": f"{type(e).__name__}: {str(e)[:160]}",
                    }))

    # ---- packed fwd timing: the trace-time doc skip vs plain causal at
    # the same shape (useful FLOPs shrink to the per-document triangles)
    if args.segments and seq % args.segments == 0 and (
        (seq // args.segments) % 1024 == 0
    ):
        starts_t = tuple(range(0, seq, seq // args.segments))
        doc_flops = flops_fwd / args.segments  # N equal causal triangles
        try:
            compile_s, secs = timed_chained(
                fwd_chained(1024, 1024, iters, doc_starts=starts_t),
                (q, k, v), iters,
            )
            print(json.dumps({
                "mode": "fwd-packed", "seq": seq, "segments": args.segments,
                "tflops_useful": round(doc_flops / secs / 1e12, 1),
                "ms": round(secs * 1e3, 1), "compile_s": round(compile_s, 1),
            }))
        except Exception as e:  # noqa: BLE001
            print(json.dumps({
                "mode": "fwd-packed", "seq": seq,
                "error": f"{type(e).__name__}: {str(e)[:160]}",
            }))

    # ---- fwd+bwd at default blocks
    do = jax.random.normal(jax.random.PRNGKey(2), q.shape, jnp.bfloat16)
    grad_fn = jax.grad(
        lambda q, k, v, do: (
            pallas_flash_attention(q, k, v, causal=True,
                                   interpret=args.interpret).astype(jnp.bfloat16)
            * do
        ).astype(jnp.float32).sum(),
        argnums=(0, 1, 2),
    )

    @jax.jit
    def bwd_chained(q, k, v, do):
        def body(c, _):
            dq, dk, dv = grad_fn(c, k, v, do)
            nxt = (c + 1e-6 * dq.astype(c.dtype)
                   + (dk.mean() + dv.mean()).astype(c.dtype) * 1e-9)
            return nxt, dq[0, 0, 0, 0]
        _, ys = jax.lax.scan(body, q, None, length=iters)
        return ys.sum()

    try:
        compile_s, secs = timed_chained(bwd_chained, (q, k, v, do), iters)
        flops_fb = 7 * 2 * seq * seq * h * d * 0.5
        print(json.dumps({
            "mode": "fwdbwd", "seq": seq,
            "tflops": round(flops_fb / secs / 1e12, 1),
            "ms": round(secs * 1e3, 1), "compile_s": round(compile_s, 1),
        }))
    except Exception as e:  # noqa: BLE001
        print(json.dumps({
            "mode": "fwdbwd", "seq": seq,
            "error": f"{type(e).__name__}: {str(e)[:160]}",
        }))


if __name__ == "__main__":
    main()
