"""Minimal end-to-end training example: striped ring attention on a mesh.

Uses every device jax reports (data x ring mesh) and says which at start:
the first line printed names platform, device_kind and device count.  On
a CPU dev box pass --fake-devices 8 to simulate the mesh.  Trains a
char-level model on synthetic data and prints loss + throughput.

  python examples/train.py --fake-devices 8 --steps 20

The flagship model every on-chip number came from (one TPU v5e chip):

  python examples/train.py --dim 512 --depth 2 --heads 8 --dim-head 64 \
      --bf16 --use-pallas --remat-policy save_attn --batch 1 --seq-len 262144
"""

from __future__ import annotations

import argparse
import contextlib
import os
import sys
import time
import warnings

try:  # prefer the installed package (pip install -e .)
    import ring_attention_tpu  # noqa: F401
except ModuleNotFoundError:  # running from a source checkout, any cwd
    sys.path.insert(
        0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    )


def main(argv: list[str] | None = None) -> dict:
    """Run the trainer; returns what it measured (``chip_smoke.py`` drives
    this in-process): ``losses`` at every logged step, ``compile_seconds``
    of the train step, the per-step seconds by ``block_until_ready`` and by
    value fetch, and the ``.sharding`` of params, optimizer state and
    batch."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--fake-devices", type=int, default=0,
                    help="simulate N CPU devices (for dev boxes)")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq-len", type=int, default=512)
    ap.add_argument("--dim", type=int, default=128)
    ap.add_argument("--depth", type=int, default=2)
    ap.add_argument("--heads", type=int, default=4)
    ap.add_argument("--dim-head", type=int, default=None,
                    help="head width (default: dim // heads)")
    ap.add_argument("--kv-heads", type=int, default=None,
                    help="grouped-query attention: kv heads (default: "
                         "heads)")
    ap.add_argument("--ring-size", type=int, default=None)
    ap.add_argument("--devices", type=int, default=None,
                    help="use only the first N devices jax reports "
                         "(default: all) — 1 runs the one-chip path on a "
                         "multi-chip host")
    ap.add_argument("--multihost", action="store_true",
                    help="join a multi-process cluster via "
                         "jax.distributed (coordinator discovered from "
                         "the environment on TPU pods; set "
                         "JAX_COORDINATOR_ADDRESS etc. elsewhere) — "
                         "meshes then span every host and the elastic "
                         "checkpoint writes one shard group per process "
                         "(docs/resilience.md §multi-host)")
    ap.add_argument("--dcn-data-size", type=int, default=None,
                    help="hierarchical mesh: outermost pure-data-"
                         "parallel axis over the slow DCN links between "
                         "slices/processes; rings and ulysses groups "
                         "then live strictly inside one group (defaults "
                         "to the process count under --multihost; "
                         "contract-proven by check_contracts.py)")
    ap.add_argument("--ulysses-size", type=int, default=None,
                    help="factor the sequence axis as ulysses x ring and "
                         "train with sequence_parallel='hybrid': all-to-all "
                         "head parallelism over the inner (fastest) axis, "
                         "KV-rotation ring over the outer one — "
                         "ulysses-size x fewer ring hops at equal world "
                         "size (docs/hybrid_parallelism.md)")
    ap.add_argument("--bf16", action="store_true")
    ap.add_argument("--accum-steps", type=int, default=1,
                    help="gradient-accumulation microbatches per update")
    ap.add_argument("--remat", action="store_true",
                    help="rematerialize blocks (activation memory savings)")
    ap.add_argument("--remat-policy", default=None,
                    help="what each rematted block may KEEP instead of "
                         "recomputing (implies --remat): nothing_saveable, "
                         "everything_saveable, checkpoint_dots, "
                         "checkpoint_dots_no_batch, save_attn, "
                         "save_ffn_inputs, save_attn_and_ffn_inputs, "
                         "offload_attn — the policy table is "
                         "docs/memory.md; validation lists the registry")
    ap.add_argument("--ff-chunk-size", type=int, default=None,
                    help="blockwise feedforward: run each FFN as a "
                         "rematted scan over sequence chunks of this size "
                         "so the (seq, mult*dim) intermediate never exists "
                         "at full extent (Ring Attention's blockwise FFN; "
                         "docs/memory.md)")
    ap.add_argument("--loss-chunk-size", type=int, default=None,
                    help="chunked cross-entropy: at most (batch, chunk, "
                         "vocab) logits materialize per device (on a mesh "
                         "the chunk is rows per sequence shard) — required "
                         "at real LM vocabularies with long sequences")
    ap.add_argument("--offload-opt-state", action="store_true",
                    help="host offload of the optimizer state (Adam "
                         "moments leave HBM between steps); a no-op on "
                         "backends without an addressable host memory "
                         "space, e.g. jax 0.4.x CPU (docs/memory.md)")
    ap.add_argument("--shard-opt-state", action="store_true",
                    help="ZeRO-1: shard the optimizer state (Adam "
                         "moments) over the data axes — both tiers on a "
                         "hierarchical --dcn-data-size mesh — so per-"
                         "chip moment memory divides by the data-"
                         "parallel world; composes with "
                         "--offload-opt-state (docs/resilience.md)")
    ap.add_argument("--watchdog-deadline", type=float, default=None,
                    help="heartbeat watchdog: abort (exit 114, flight "
                         "incident dumped) when a step boundary takes "
                         "longer than this many seconds — a wedged "
                         "collective (dead peer, hung device) becomes a "
                         "bounded restart instead of an eternal hang")
    ap.add_argument("--use-pallas", action="store_true",
                    help="Mosaic kernels (TPU; interpreter elsewhere)")
    ap.add_argument("--impl", choices=["auto", "fused", "pallas", "xla"],
                    default=None,
                    help="kernel path with graceful degradation (overrides "
                         "--use-pallas): fused = single-launch fused-ring "
                         "kernel with in-kernel remote KV DMA "
                         "(ops/pallas_ring.py); auto prefers fused, then "
                         "pallas, then xla, recording each fallback")
    ap.add_argument("--bidirectional", action="store_true",
                    help="circulate KV halves both ring directions (duplex ICI)")
    ap.add_argument("--counter-rotate", action="store_true",
                    help="TokenRing full-duplex schedule: the Q shard + its "
                         "online-softmax accumulators rotate one ring "
                         "direction while KV rotates the other; the backward "
                         "keeps KV/dKV resident (docs/ring_overlap.md)")
    ap.add_argument("--hop-compression", choices=["int8"], default=None,
                    help="ship forward KV ring hops int8-quantized (per-"
                         "token absmax values + bitcast f32 scales in one "
                         "payload); accumulators and grads stay exact-dtype")
    ap.add_argument("--compute-dtype", choices=["int8"], default=None,
                    help="run the forward's QK^T/PV matmuls on int8 "
                         "operands (pallas kernels; ~2x MXU rate on "
                         "v5e/v5p); backward stays bf16 from exact "
                         "residuals; composes with --hop-compression int8 "
                         "into the dequant-free ring (docs/precision.md)")
    ap.add_argument("--pack", action="store_true",
                    help="packed-sequence training: concatenate variable-"
                         "length documents per row with segment ids — "
                         "attention stays within each document and no "
                         "position is padding (docs/packing.md)")
    ap.add_argument("--docs-per-seq", type=int, default=4,
                    help="documents packed into each row with --pack")
    ap.add_argument("--ckpt-dir", default=None,
                    help="checkpoint directory: saves every --ckpt-every "
                         "steps and resumes from the last good checkpoint "
                         "(kill the run mid-way and rerun the same command)")
    ap.add_argument("--ckpt-every", type=int, default=5)
    ap.add_argument("--ckpt-keep", type=int, default=3,
                    help="keep-last-N checkpoint retention")
    ap.add_argument("--elastic-ckpt", action="store_true",
                    help="elastic runtime (docs/resilience.md): sharded "
                         "ASYNC checkpoints (one file per shard group, "
                         "atomic manifest commit), SIGTERM/SIGINT drain "
                         "(finish the step, save, dump a flight "
                         "incident, exit cleanly), and re-mesh resume — "
                         "restart this command at a DIFFERENT device "
                         "count and it reshards the checkpoint onto the "
                         "new mesh (requires --ckpt-dir)")
    ap.add_argument("--skip-nonfinite", action="store_true",
                    help="guarded train step: skip (don't apply) optimizer "
                         "updates whose loss/grads are non-finite")
    ap.add_argument("--clip-grad-norm", type=float, default=None,
                    help="clip gradients to this global L2 norm")
    ap.add_argument("--metrics-dir", default=None,
                    help="telemetry: write one schema-versioned JSONL row "
                         "per --log-every window (loss, grad_norm, "
                         "tokens_per_sec, step p50/p95, mfu, ring hop/byte "
                         "accounting, skipped-step counts) — render with "
                         "tools/trace_report.py (docs/observability.md)")
    ap.add_argument("--log-every", type=int, default=5,
                    help="steps between metric rows / console lines")
    ap.add_argument("--flight-window", type=int, default=64,
                    help="numerics flight recorder: keep the last N metric "
                         "rows in memory and dump them as JSON on a "
                         "nonfinite step, kernel degradation, or crash — "
                         "a NaN arrives with its preceding trajectory, "
                         "not a bare counter (needs --metrics-dir; 0 "
                         "disables; docs/observability.md §Observatory)")
    ap.add_argument("--flight-dir", default=None,
                    help="flight-dump directory (default: "
                         "METRICS_DIR/flight)")
    ap.add_argument("--trace-dir", default=None,
                    help="span tracing: write one per-process span JSONL "
                         "file (step phases, checkpoint save/commit, "
                         "barrier waits, watchdog beats) — merge across "
                         "processes with tools/cluster_timeline.py "
                         "(docs/observability.md §6)")
    args = ap.parse_args(argv)
    if args.log_every < 1:
        ap.error("--log-every must be >= 1")
    if args.elastic_ckpt and not args.ckpt_dir:
        ap.error("--elastic-ckpt needs --ckpt-dir")

    if args.fake_devices:
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={args.fake_devices}"
        )
        import jax

        jax.config.update("jax_platforms", "cpu")
    import jax

    if args.multihost:
        # join the cluster before ANY device query: jax.devices() must be
        # the global list when the meshes are built (retry ladder + one-
        # line coordinator diagnostics live in parallel/mesh.py)
        from ring_attention_tpu.parallel import initialize_multihost

        initialize_multihost()
        print(f"multihost: process {jax.process_index()}/"
              f"{jax.process_count()}, "
              f"{len(jax.local_devices())} local devices")
    import jax.numpy as jnp
    import numpy as np
    import optax

    from ring_attention_tpu import RingTransformer, create_mesh
    from ring_attention_tpu.parallel import shard_batch
    from ring_attention_tpu.utils import (
        CheckpointManager,
        MetricsLogger,
        StepTimer,
        enable_compile_cache,
        init_step_stats,
        init_train_metrics,
        make_train_step,
        ring_comms_accounting,
        transformer_step_flops,
    )
    from ring_attention_tpu.utils.telemetry import PEAK_TFLOPS

    # before any jit: every compile from here on lands in the cache
    # (placed by JAX_COMPILATION_CACHE_DIR, else <checkout>/.jax_cache_tpu)
    enable_compile_cache()
    # CPU dev boxes can't honor donation; the hint is still correct on TPU
    warnings.filterwarnings(
        "ignore", message="Some donated buffers were not usable"
    )

    devices = jax.devices()[:args.devices] if args.devices else None
    n_dev = len(devices or jax.devices())
    n_proc = jax.process_count()
    dev0 = jax.devices()[0]
    print(f"platform={dev0.platform} device_kind={dev0.device_kind!r} "
          f"devices={n_dev} (of {len(jax.devices())})")

    # span tracing (docs/observability.md §6): each process appends to
    # its own spans_pNNNNN.jsonl; tools/cluster_timeline.py merges them
    # into one clock-corrected cluster timeline
    if args.trace_dir:
        from ring_attention_tpu.utils import tracing

        tracing.configure(args.trace_dir, process=jax.process_index())
    if args.dcn_data_size is None and n_proc > 1:
        # multihost default: one dcn group per process, rings inside
        args.dcn_data_size = n_proc

    # elastic resume plans the mesh BEFORE building it: when the job
    # comes back at a different device count and no explicit factoring
    # was requested, the checkpoint manifest's mesh descriptor + the new
    # world pick the closest factoring (ring absorbs the change, the
    # dcn tier re-plans to the current process count)
    elastic_mgr = None
    guard = None
    if args.elastic_ckpt:
        from ring_attention_tpu.elastic import (
            ElasticCheckpointManager,
            PreemptionGuard,
        )
        from ring_attention_tpu.parallel import remesh_plan

        elastic_mgr = ElasticCheckpointManager(
            args.ckpt_dir, keep=args.ckpt_keep
        )
        manifest = elastic_mgr.latest_manifest()
        if (manifest is not None and args.ring_size is None
                and args.ulysses_size is None):
            plan, diags = remesh_plan(
                manifest.get("mesh"), n_dev,
                dcn_data_size=args.dcn_data_size or n_proc,
            )
            for line in diags:
                print(f"  {line}")
            args.ring_size = plan.get("ring_size")
            args.ulysses_size = plan.get("ulysses_size")
            args.dcn_data_size = plan.get("dcn_data_size")
        # constructed here, INSTALLED just before the train loop: during
        # the multi-minute init/compile/restore window a latched signal
        # would get no drain check, so the default Ctrl-C behavior is
        # the right response there.  The handler prints on first signal
        # so a drain never looks like a hang.
        guard = PreemptionGuard(on_preempt=lambda sig: print(
            f"\n{sig} received: finishing the in-flight step, then "
            f"draining (save + incident dump); signal again to abort"
        ))

    ulysses = args.ulysses_size or 1
    hybrid = ulysses > 1
    dcn = args.dcn_data_size or 1
    inner_dev = n_dev // dcn  # per-dcn-group world
    if hybrid:
        ring = args.ring_size or inner_dev // ulysses
        mesh = create_mesh(ring_size=ring, ulysses_size=ulysses,
                           dcn_data_size=args.dcn_data_size, devices=devices)
        seq_shards = ulysses * ring
    else:
        ring = args.ring_size or inner_dev
        mesh = create_mesh(
            ring_size=ring, dcn_data_size=args.dcn_data_size, devices=devices
        ) if n_dev > 1 else None
        seq_shards = ring
    print(f"devices={n_dev} mesh={dict(mesh.shape) if mesh else None}")
    if mesh is not None:
        # ring order beside each device's physical coordinates: a ring
        # that does not follow the torus still trains, only slower
        for idx in np.ndindex(mesh.devices.shape):
            d = mesh.devices[idx]
            print(f"  mesh{list(idx)} = device {d.id} "
                  f"coords={getattr(d, 'coords', None)}")

    dim_head = args.dim_head or args.dim // args.heads
    kv_heads = args.kv_heads or args.heads
    model = RingTransformer(
        num_tokens=256,
        dim=args.dim,
        depth=args.depth,
        heads=args.heads,
        dim_head=dim_head,
        kv_heads=args.kv_heads,
        causal=True,
        striped=True,
        bucket_size=max(args.seq_len // max(seq_shards, 1), 1),
        mesh=mesh,
        use_ring=mesh is not None,
        sequence_parallel="hybrid" if hybrid else "ring",
        use_pallas=args.use_pallas,
        impl=args.impl,
        ring_bidirectional=args.bidirectional,
        ring_counter_rotate=args.counter_rotate,
        ring_hop_compression=args.hop_compression,
        compute_dtype=args.compute_dtype,
        remat=args.remat or args.remat_policy is not None,
        remat_policy=args.remat_policy,
        ff_chunk_size=args.ff_chunk_size,
        loss_chunk_size=args.loss_chunk_size,
        dtype=jnp.bfloat16 if args.bf16 else None,
    )

    rng = np.random.default_rng(0)
    segments = None
    if args.pack:
        # packed batches: each row concatenates --docs-per-seq variable-
        # length "copy task" documents; segment ids keep attention (and
        # the loss) within each document — zero positions are padding
        tokens = np.empty((args.batch, args.seq_len), np.int32)
        segments = np.empty((args.batch, args.seq_len), np.int32)
        for row in range(args.batch):
            cuts = np.sort(rng.choice(
                np.arange(2, args.seq_len - 1, 2),
                size=args.docs_per_seq - 1, replace=False,
            ))
            bounds = [0, *cuts.tolist(), args.seq_len]
            for doc, (lo, hi) in enumerate(zip(bounds[:-1], bounds[1:])):
                half = (hi - lo) // 2
                piece = rng.integers(0, 256, half + ((hi - lo) % 2))
                tokens[row, lo:hi] = np.concatenate([piece, piece[:half]])
                segments[row, lo:hi] = doc
    else:
        # synthetic "copy task" data: predictable structure so loss falls fast
        base = rng.integers(0, 256, (args.batch, args.seq_len // 2))
        tokens = np.concatenate([base, base], axis=1).astype(np.int32)

    if n_proc > 1:
        # every process passes only ITS rows of the global batch: the
        # batch dimension shards over (dcn_data, data) with one dcn
        # group per process, so the local slab is a contiguous row range
        if args.batch % n_proc:
            ap.error(f"--batch {args.batch} must divide by the "
                     f"{n_proc}-process cluster")
        rows = args.batch // n_proc
        row0 = jax.process_index() * rows
        tokens = tokens[row0:row0 + rows]
        if segments is not None:
            segments = segments[row0:row0 + rows]
    if mesh is not None:
        # host array straight onto the mesh: batch over data, sequence over
        # the ring, one per-shard transfer (multi-host: each process passes
        # its local slice)
        tokens = shard_batch(tokens, mesh)
        if segments is not None:
            segments = shard_batch(segments, mesh)
    else:
        tokens = jnp.asarray(tokens)
        if segments is not None:
            segments = jnp.asarray(segments)
    # parameter shapes do not depend on the sequence length: init on one
    # tile per shard, jitted, instead of an eager pass over the full batch
    init_tokens = jnp.zeros((1, 128 * max(seq_shards, 1)), jnp.int32)
    params = jax.jit(model.init)(jax.random.PRNGKey(0), init_tokens)
    opt = optax.adamw(3e-4)
    opt_state = opt.init(params)
    if mesh is not None:
        # replicated over the whole mesh, explicitly: the compiled step is
        # specialised to its input shardings and hands the same ones back
        from jax.sharding import NamedSharding, PartitionSpec

        replicated = NamedSharding(mesh, PartitionSpec())
        params, opt_state = jax.device_put((params, opt_state), replicated)
    if args.shard_opt_state:
        if mesh is None:
            ap.error("--shard-opt-state needs a mesh (more than 1 device)")
        # seed the loop sharded; the step's in-graph constraint keeps the
        # updated state sharded (utils/train.py)
        from ring_attention_tpu.parallel import data_partition
        from ring_attention_tpu.utils.train import shard_optimizer_state

        opt_state = shard_optimizer_state(
            opt_state, mesh, axis=data_partition(mesh)
        )
    if args.offload_opt_state:
        # seed the loop host-side; the step keeps it there (utils/train.py)
        from ring_attention_tpu.utils import compat

        opt_state = compat.host_device_put(opt_state)

    if args.pack:
        def loss_fn(p, t, s):
            return model.apply(p, t, return_loss=True, segment_ids=s)
        batch = (tokens, segments)
    else:
        def loss_fn(p, t):
            return model.apply(p, t, return_loss=True)
        batch = (tokens,)

    guarded = args.skip_nonfinite
    collect = args.metrics_dir is not None
    # jit_donate: (params, opt_state) buffers are donated so XLA updates
    # them in place instead of double-allocating model + Adam state.
    # collect_metrics extends the carry to TrainMetrics (loss, grad_norm,
    # skipped/nonfinite counters) with no extra collectives in the step.
    train_step = make_train_step(
        loss_fn, opt,
        accum_steps=args.accum_steps,
        skip_nonfinite=guarded,
        clip_grad_norm=args.clip_grad_norm,
        jit_donate=True,
        collect_metrics=collect,
        offload_opt_state=args.offload_opt_state,
        shard_opt_state=args.shard_opt_state,
        shard_mesh=mesh,
    )

    # preemption-safe resume: atomic saves, keep-last-N, corrupt-checkpoint
    # fallback — kill this process at any point and rerun the same command
    # to continue from the last good step (see docs/resilience.md)
    mgr = None
    start = 0
    stats = init_step_stats()
    nonfinite = jnp.asarray(0, jnp.int32)
    if args.ckpt_dir:
        mgr = elastic_mgr or CheckpointManager(
            args.ckpt_dir, keep=args.ckpt_keep
        )
        # stats ride along in the checkpoint so a resumed guarded run
        # keeps its skipped-step telemetry (a growing skip streak is the
        # "this run diverged" signal and must survive preemption).  With
        # metrics on, the nonfinite counter rides too — unguarded runs
        # have skipped == 0, so losing it would silently reset the "run
        # is corrupting itself" alarm across preemption.
        def fresh():
            state = {"params": params, "opt_state": opt_state,
                     "stats": stats}
            if collect:
                state["nonfinite"] = nonfinite
            return state

        if elastic_mgr is not None:
            # elastic resume: resharded-loads the checkpoint onto the
            # CURRENT mesh (whatever factoring it was written at) and
            # revalidates seq_len divisibility with a one-line error
            state, start = mgr.resume_or_init(
                fresh, mesh=mesh, seq_len=args.seq_len
            )
            if mgr.last_resume is not None:
                for line in mgr.last_resume["diagnostics"]:
                    print(f"  {line}")
        else:
            state, start = mgr.resume_or_init(fresh)
        params, opt_state = state["params"], state["opt_state"]
        stats = state["stats"]
        nonfinite = state.get("nonfinite", nonfinite)
        if start:
            print(f"resumed from checkpoint (continuing at step {start})")

    # telemetry (docs/observability.md): the instrumented step carries
    # TrainMetrics; the logger writes one schema-versioned JSONL row per
    # --log-every window, with MFU and ring-hop/byte accounting computed
    # analytically once (they derive from shapes and the mesh factoring)
    metrics = None
    logger = None
    mfu_flops = 0.0
    comms = {}
    # utilization needs a listed peak: a device_kind the table does not
    # hold (the CPU) gets no mfu field rather than one against a guess
    peak = PEAK_TFLOPS.get(dev0.device_kind)
    if peak is not None:
        peak *= n_dev
    step_args = (params, opt_state)
    if collect:
        # a resumed run continues its counters in the metrics carry
        metrics = init_train_metrics(skipped=int(stats.skipped),
                                     nonfinite=int(nonfinite))
        step_args += (metrics,)
        logger = MetricsLogger(args.metrics_dir)
        n_params = sum(x.size for x in jax.tree.leaves(params))
        mfu_flops = transformer_step_flops(
            n_params, tokens.size, depth=args.depth, heads=args.heads,
            dim_head=dim_head, seq_len=args.seq_len, causal=True,
            batch=args.batch,
        )
        if mesh is not None:
            pad_seq = args.seq_len + (-args.seq_len) % seq_shards
            comms = ring_comms_accounting(
                ring_size=ring, ulysses_size=ulysses, seq_len=pad_seq,
                heads=args.heads, kv_heads=kv_heads, dim_head=dim_head,
                dtype_bytes=2 if args.bf16 else 4, batch=args.batch,
                depth=args.depth, counter_rotate=args.counter_rotate,
                hop_compression=args.hop_compression,
                compute_dtype=args.compute_dtype,
            )
        else:
            comms = {"ring_hops": 0, "ring_hops_per_step": 0, "hop_bytes": 0}
    elif guarded:
        step_args += (stats,)

    # AOT-compile the step that is about to run, once, and drive the loop
    # on that executable: compile seconds are set-up time reported on
    # their own, and a step that does not compile stops the run here
    t0 = time.perf_counter()
    train_step = train_step.lower(*step_args, *batch).compile()
    compile_seconds = time.perf_counter() - t0
    print(f"compile: {compile_seconds:.1f} s (train step, cache "
          f"{jax.config.jax_compilation_cache_dir})")
    if collect:
        # compiled peak-memory accounting of the step that actually runs
        # (telemetry.compiled_memory): temp/argument bytes next to the
        # analytic comms numbers
        from ring_attention_tpu.utils.telemetry import compiled_memory

        comms.update(compiled_memory(train_step))

    def shardings(tree):
        return sorted({str(x.sharding) for x in jax.tree.leaves(tree)})

    placement = {"params": shardings(params),
                 "opt_state": shardings(opt_state),
                 "batch": shardings(batch)}
    for name, shs in placement.items():
        print(f"sharding {name}: {'; '.join(shs)}")

    # numerics flight recorder (docs/observability.md §Observatory): the
    # last --flight-window metric rows ride in memory; a nonfinite step,
    # kernel degradation, exhausted retry ladder, or crash dumps them as
    # JSON next to the metrics — the NaN arrives with its trajectory
    recorder = None
    if collect and args.flight_window > 0:
        from ring_attention_tpu.utils import FlightRecorder

        recorder = FlightRecorder(
            args.flight_dir or os.path.join(args.metrics_dir, "flight"),
            window=args.flight_window,
            context={
                "mesh": dict(mesh.shape) if mesh is not None else None,
                "seq_len": args.seq_len, "batch": args.batch,
                "dim": args.dim, "depth": args.depth,
                "ulysses": ulysses, "ring": ring,
                "counter_rotate": args.counter_rotate,
                "hop_compression": args.hop_compression,
                "compute_dtype": args.compute_dtype,
                "remat_policy": args.remat_policy,
                "ff_chunk_size": args.ff_chunk_size,
                "skip_nonfinite": guarded,
            },
        ).install()

    # heartbeat watchdog (docs/resilience.md): a step boundary further
    # apart than the deadline means a wedged collective — abort with a
    # flight incident so the supervisor restarts from the checkpoint
    dog = None
    if args.watchdog_deadline:
        from ring_attention_tpu.elastic import Watchdog

        dog = Watchdog(args.watchdog_deadline, recorder=recorder).start()

    timer = StepTimer(tokens_per_step=tokens.size * max(n_proc, 1))
    loop_guard = recorder.guard() if recorder is not None else (
        contextlib.nullcontext()
    )
    result = {"compile_seconds": compile_seconds, "losses": [],
              "step_seconds": [], "fetch_seconds": [],
              "shardings": placement, "mesh": mesh}
    try:
        if guard is not None:
            guard.install()  # compile/init/restore are behind us
        with loop_guard:
            _train_loop(args, recorder, timer, train_step, params,
                        opt_state, metrics, stats, batch, collect, guarded,
                        mgr, logger, start, mfu_flops, comms, peak, guard,
                        n_proc=n_proc, dog=dog, result=result)
    finally:
        if dog is not None:
            dog.stop()
        if elastic_mgr is not None:
            elastic_mgr.close()  # flush any in-flight async save
        if guard is not None:
            guard.uninstall()
        if args.trace_dir:
            from ring_attention_tpu.utils import tracing

            tracing.shutdown()
    if logger is not None:
        logger.close()
        print(f"metrics: {logger.path} (render with tools/trace_report.py)")
    if recorder is not None and recorder.dumps:
        print("flight dumps: " + ", ".join(recorder.dumps))
    return result


def _train_loop(args, recorder, timer, train_step, params, opt_state,
                metrics, stats, batch, collect, guarded, mgr, logger,
                start, mfu_flops, comms, peak, guard=None, n_proc=1,
                dog=None, *, result):
    from ring_attention_tpu.utils import achieved_mfu, tracing
    from ring_attention_tpu.utils.train import StepStats

    def make_ckpt():
        ckpt = {"params": params, "opt_state": opt_state, "stats": stats}
        if collect:
            ckpt["nonfinite"] = metrics.nonfinite
        return ckpt

    def drain_requested(step: int) -> bool:
        if guard is None:
            return False
        if n_proc > 1:
            # one host's SIGTERM drains the whole pod: the flag OR-reduces
            # across processes at the step boundary — the train step's
            # own compiled program is untouched (elastic/preemption.py)
            return guard.should_stop_cluster(step=step)
        return guard.should_stop()

    tracer = tracing.get_tracer()
    for step in range(start, args.steps):
        # the step-phase span measures host-side dispatch + the loss
        # sync inside timer.step; the compiled program itself is pinned
        # untraced (tests/test_tracing.py HLO pin)
        t_dispatch = time.perf_counter()
        with tracer.span("train/step", step=step):
            if collect:
                params, opt_state, metrics, loss = train_step(
                    params, opt_state, metrics, *batch
                )
                # checkpointed StepStats stays structure-compatible with
                # uninstrumented runs; it mirrors the metrics counters
                stats = StepStats(step_ok=metrics.step_ok,
                                  skipped=metrics.skipped)
                if recorder is not None:
                    dump = recorder.observe_step(step, metrics)
                    if dump:
                        print(f"flight recorder: nonfinite step {step} "
                              f"-> {dump}")
            elif guarded:
                params, opt_state, stats, loss = train_step(
                    params, opt_state, stats, *batch
                )
            else:
                params, opt_state, loss = train_step(
                    params, opt_state, *batch
                )
            timer.step(loss)  # jax.block_until_ready(loss)
        t_ready = time.perf_counter()
        result["step_seconds"].append(t_ready - t_dispatch)
        if dog is not None:
            dog.beat(step)
        if step % args.log_every == 0 or step == args.steps - 1:
            with tracer.span("train/log", step=step):
                loss_value = float(loss)
                # what the value fetch still waited for after
                # block_until_ready returned (chip_smoke.py's sync check)
                result["fetch_seconds"].append(
                    time.perf_counter() - t_ready)
                result["losses"].append(loss_value)
                skipped = int(stats.skipped) if (guarded or collect) else 0
                print(
                    f"step {step:4d}  loss {loss_value:.4f}  "
                    f"{result['step_seconds'][-1]:.3f} s/step  "
                    f"{timer.tokens_per_sec:,.0f} tok/s"
                    + (f"  [skipped {skipped}]" if skipped else "")
                )
                if logger is not None:
                    sps = timer.steps_per_sec
                    logger.log(
                        step,
                        loss=loss_value,
                        grad_norm=float(metrics.grad_norm),
                        step_ok=bool(metrics.step_ok),
                        skipped=int(metrics.skipped),
                        nonfinite=int(metrics.nonfinite),
                        tokens_per_sec=round(timer.tokens_per_sec, 1),
                        steps_per_sec=round(sps, 4),
                        step_ms_p50=round(timer.step_ms_p50, 2),
                        step_ms_p95=round(timer.step_ms_p95, 2),
                        **({"mfu": round(
                            achieved_mfu(mfu_flops, 1.0 / sps, peak), 6
                        )} if sps > 0 and peak is not None else {}),
                        **comms,
                    )
        if drain_requested(step):
            # preemption drain: this step FINISHED (we're at the step
            # boundary); save synchronously, dump the incident with its
            # trajectory, and leave the loop cleanly — the restarted job
            # resumes at step + 1, possibly at another device count
            guard.drain(
                lambda: mgr.save(step, make_ckpt(), block=True),
                recorder=recorder, step=step,
            )
            print(f"preemption ({guard.signal_name}): drained and saved "
                  f"step {step}; exiting cleanly")
            break
        if mgr is not None and (
            step % args.ckpt_every == 0 or step == args.steps - 1
        ):
            with tracer.span("train/ckpt", step=step):
                mgr.save(step, make_ckpt())


if __name__ == "__main__":
    main()
