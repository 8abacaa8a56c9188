"""Incremental decoding demo: prefill a prompt, then stream tokens.

The KV cache is sharded over the mesh's seq axis; every step merges shard
partials with tree attention (arXiv 2408.04093).  Uses every device jax
reports and says which at start (platform, device_kind, device count);
pass --fake-devices 8 for a simulated CPU mesh:

  python examples/generate.py --fake-devices 8 --steps 16

A 2^20-token decode on one v5e chip (measured cells: PERF.md, benchmarks/):

  python examples/generate.py --dim 512 --depth 2 --heads 8 --kv-heads 2 \
      --dim-head 64 --bf16 --use-pallas --max-len 1048576 --prompt-len 4096
"""

from __future__ import annotations

import argparse
import os
import sys
import time

try:  # prefer the installed package (pip install -e .)
    import ring_attention_tpu  # noqa: F401
except ModuleNotFoundError:  # running from a source checkout, any cwd
    sys.path.insert(
        0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    )


def main(argv: list[str] | None = None) -> dict:
    """Run the decoder; returns what it measured (``chip_smoke.py`` drives
    this in-process): the ``tokens``, ``compile_seconds`` (prefill and
    decode step, ahead of time), ``prefill_seconds``, the gaps between
    tokens in seconds and the cache ``.sharding``."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--fake-devices", type=int, default=0)
    ap.add_argument("--devices", type=int, default=None,
                    help="use only the first N devices jax reports "
                         "(default: all) — 1 runs the one-chip path on a "
                         "multi-chip host")
    ap.add_argument("--config", default=None,
                    help="build the model from a configuration file "
                         "(benchmarks/configs/*.json: a published "
                         "config.json's keys) instead of --dim/--depth/"
                         "--heads/--dim-head/--kv-heads and 256 tokens; "
                         "with --bf16 the weights are bfloat16 too")
    ap.add_argument("--dim", type=int, default=128)
    ap.add_argument("--depth", type=int, default=2)
    ap.add_argument("--heads", type=int, default=4)
    ap.add_argument("--dim-head", type=int, default=32)
    ap.add_argument("--kv-heads", type=int, default=None,
                    help="grouped-query attention: kv heads (default: "
                         "heads)")
    ap.add_argument("--bf16", action="store_true")
    ap.add_argument("--steps", type=int, default=16)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--use-pallas", action="store_true",
                    help="decode with the pallas decode kernel (each cache "
                         "byte read once per kv head; interpret mode on CPU)")
    ap.add_argument("--q8-cache", action="store_true",
                    help="store the decode KV cache as per-token int8 "
                         "(1.88x fewer cache HBM bytes at d=64)")
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="sample with this temperature via the scan-based "
                         "generate() (0 = greedy token-by-token streaming)")
    ap.add_argument("--top-k", type=int, default=None)
    ap.add_argument("--top-p", type=float, default=None)
    ap.add_argument("--metrics-dir", default=None,
                    help="telemetry: append decode-throughput JSONL rows "
                         "(tok/s, ms/token, prefill length) for "
                         "tools/trace_report.py (docs/observability.md)")
    ap.add_argument("--trace-dir", default=None,
                    help="span tracing: one span per decoded token plus "
                         "prefill, merged with tools/cluster_timeline.py "
                         "(docs/observability.md §6)")
    args = ap.parse_args(argv)

    if args.temperature <= 0.0 and (args.top_k is not None
                                    or args.top_p is not None):
        ap.error("--top-k/--top-p need --temperature > 0 (sampling mode)")
    if args.prompt_len + args.steps - 1 > args.max_len:
        ap.error(
            f"--max-len {args.max_len} too small for prompt {args.prompt_len} "
            f"+ {args.steps} steps (cache writes would clamp silently)"
        )

    if args.fake_devices:
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={args.fake_devices}"
        )
        import jax

        jax.config.update("jax_platforms", "cpu")
    import warnings

    import jax
    import jax.numpy as jnp
    import numpy as np

    from ring_attention_tpu import RingTransformer, create_mesh
    from ring_attention_tpu.models import ModelConfig
    from ring_attention_tpu.utils import compat, enable_compile_cache

    # before any jit: every compile from here on lands in the cache
    # (placed by JAX_COMPILATION_CACHE_DIR, else <checkout>/.jax_cache_tpu)
    enable_compile_cache()
    # CPU dev boxes can't honor donation; the hint is still correct on TPU
    warnings.filterwarnings(
        "ignore", message="Some donated buffers were not usable"
    )

    from ring_attention_tpu.utils import tracing

    if args.trace_dir:
        tracing.configure(args.trace_dir, process=jax.process_index())
    tracer = tracing.get_tracer()

    devices = jax.devices()[:args.devices] if args.devices else None
    n_dev = len(devices or jax.devices())
    dev0 = jax.devices()[0]
    print(f"platform={dev0.platform} device_kind={dev0.device_kind!r} "
          f"devices={n_dev} (of {len(jax.devices())})")
    mesh = (create_mesh(ring_size=n_dev, devices=devices)
            if n_dev > 1 else None)
    run = dict(
        bucket_size=64, mesh=mesh, use_ring=mesh is not None,
        use_pallas=args.use_pallas, quantize_cache=args.q8_cache,
        dtype=jnp.bfloat16 if args.bf16 else None,
    )
    if args.config:
        model = RingTransformer.from_config(
            ModelConfig.from_file(args.config), **run)
    else:
        model = RingTransformer(
            num_tokens=256, dim=args.dim, depth=args.depth, heads=args.heads,
            dim_head=args.dim_head, kv_heads=args.kv_heads, causal=True,
            **run)
    rng = np.random.default_rng(0)
    prompt = jnp.asarray(
        rng.integers(0, model.num_tokens, (1, args.prompt_len)), jnp.int32)
    init = model.init
    if args.config and args.bf16:
        # a configuration is served from bfloat16 weights (its file's
        # `assumed.dtype`).  flax draws float32; the cast rides in the same
        # jit, leaf by leaf, so the float32 draws never stand side by side
        # (trinity-large-preview: 17 GB of them, on a 16 GB chip)
        def init(key, tokens):
            return jax.tree.map(lambda w: w.astype(jnp.bfloat16),
                                model.init(key, tokens))
    params = jax.jit(init)(jax.random.PRNGKey(0), prompt)

    def log_decode(**fields):
        if args.metrics_dir is None:
            return
        from ring_attention_tpu.utils import MetricsLogger

        with MetricsLogger(args.metrics_dir) as logger:
            logger.log(0, mode="decode", devices=n_dev,
                       prompt_len=args.prompt_len,
                       use_pallas=bool(args.use_pallas),
                       q8_cache=bool(args.q8_cache), **fields)

    if args.temperature > 0.0:
        # whole loop as ONE compiled scan (models/transformer.py generate)
        t0 = time.perf_counter()
        out = model.apply(
            params, prompt, args.max_len, args.steps,
            method=RingTransformer.generate,
            temperature=args.temperature, top_k=args.top_k,
            top_p=args.top_p, rng=jax.random.PRNGKey(1),
        )
        dt = time.perf_counter() - t0
        toks = [int(t) for t in np.asarray(out[0])]
        print(f"devices={n_dev}  sampled {len(toks)} tokens in one "
              f"compile+scan ({len(toks) / dt:.1f} tok/s incl. compile)")
        print("tokens:", toks)
        log_decode(tokens=len(toks), seconds=round(dt, 4),
                   tokens_per_sec=round(len(toks) / dt, 2),
                   sampled=True, compile_included=True)
        if args.trace_dir:
            tracing.shutdown()
        return {"tokens": toks}

    # prefill and the decode step are each compiled once, ahead of time
    # (compile seconds are set-up, reported on their own) and DONATE the KV
    # cache: the updated cache reuses the previous buffers instead of
    # double-allocating the whole cache
    cache = model.apply(params, 1, args.max_len, method=RingTransformer.init_cache)
    cache_shardings = sorted(
        {str(x.sharding) for x in jax.tree.leaves(cache)})
    print(f"sharding cache: {'; '.join(cache_shardings)}")
    t0 = time.perf_counter()
    prefill = compat.jit(
        lambda p, t, c: model.apply(p, t, c, method=RingTransformer.prefill),
        donate_argnums=(2,),
    ).lower(params, prompt, cache).compile()
    tok0 = jnp.zeros((1,), jnp.int32)
    step = compat.jit(
        lambda p, tok, c, i: model.apply(
            p, tok, c, i, method=RingTransformer.decode_step
        ),
        donate_argnums=(2,),
    ).lower(params, tok0, cache, jnp.int32(0)).compile()
    compile_seconds = time.perf_counter() - t0
    print(f"compile: {compile_seconds:.1f} s (prefill + decode step, cache "
          f"{jax.config.jax_compilation_cache_dir})")

    t0 = time.perf_counter()
    with tracer.span("decode/prefill", prompt_len=args.prompt_len):
        logits, cache = prefill(params, prompt, cache)
        tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        toks = [int(tok[0])]  # the value fetch waits for the device
    prefill_seconds = time.perf_counter() - t0
    print(f"prefill: {args.prompt_len} tokens in {prefill_seconds:.3f} s")
    # per-token latency distribution: each iteration is a traced span
    # AND a histogram sample (the `int(tok[0])` conversion syncs on the
    # device, so the span covers the real token latency)
    hist = tracing.LatencyHistogram()
    gaps = []
    t0 = time.perf_counter()
    for i in range(args.steps - 1):
        ts = time.perf_counter()
        with tracer.span("decode/token", index=i):
            logits, cache = step(params, tok, cache,
                                 jnp.int32(args.prompt_len + i))
            tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            toks.append(int(tok[0]))
        gaps.append(time.perf_counter() - ts)
        hist.record(gaps[-1])
    dt = time.perf_counter() - t0
    print(f"devices={n_dev}  generated {len(toks)} tokens "
          f"({(len(toks) - 1) / dt:.1f} tok/s after prefill)")
    print("tokens:", toks)
    if hist.n:
        print(f"token latency: p50 {hist.percentile_ms(50):.2f} ms  "
              f"p95 {hist.percentile_ms(95):.2f} ms  "
              f"p99 {hist.percentile_ms(99):.2f} ms")
    if len(toks) > 1:
        log_decode(tokens=len(toks), seconds=round(dt, 4),
                   tokens_per_sec=round((len(toks) - 1) / dt, 2),
                   ms_per_token=round(dt * 1e3 / (len(toks) - 1), 3),
                   decode_ms_p50=round(hist.percentile_ms(50), 3),
                   decode_ms_p95=round(hist.percentile_ms(95), 3),
                   decode_ms_p99=round(hist.percentile_ms(99), 3),
                   latency_hist=hist.to_dict(),
                   sampled=False, compile_included=False)
    if args.trace_dir:
        tracing.shutdown()
    return {"tokens": toks, "compile_seconds": compile_seconds,
            "prefill_seconds": prefill_seconds, "token_gaps": gaps,
            "cache_shardings": cache_shardings}


if __name__ == "__main__":
    main()
