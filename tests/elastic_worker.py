"""Victim process for the elastic chaos matrix (tests/test_elastic.py,
tests/test_multihost.py).

One tiny ring-attention training run on virtual CPU devices, wired
exactly the way a production job would be: elastic sharded checkpoints
(async saves, manifest commit), re-mesh resume planned from the latest
manifest, a PreemptionGuard drain (cluster-broadcast when multi-process),
and an optional heartbeat watchdog.  The parent kills it anywhere —
chaos faults arrive via ``RING_ATTN_CHAOS`` (armed at startup), the
device count via ``RING_ATTN_CHAOS_DEVICES`` — restarts it at any
device count, and audits the per-step loss log this worker appends
(one fsync'd JSON line per completed step, so a hard death can never
lose or tear the evidence).

Multi-process mode: ``RING_ATTN_CLUSTER="<pid>:<nproc>:<port>"`` joins a
``jax.distributed`` cluster (``ChaosWorker.run_cluster`` sets it); the
mesh grows the ``dcn_data`` level (one group per process, rings strictly
inside), every process writes its own checkpoint shard group, process 0
commits the manifest behind the cross-process barrier, and process 0
alone appends the loss log.

    python tests/elastic_worker.py --ckpt-dir D --loss-log L [--steps 10]
"""

import argparse
import json
import os
import sys


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--ckpt-dir", required=True)
    ap.add_argument("--loss-log", required=True)
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument("--seq-len", type=int, default=64)
    ap.add_argument("--save-every", type=int, default=3)
    ap.add_argument("--sync-save", action="store_true",
                    help="synchronous saves (the chaos kill points then "
                         "fire on the main thread, deterministically "
                         "ordered against the loss log)")
    ap.add_argument("--barrier-timeout", type=float, default=60.0,
                    help="cross-process checkpoint barrier budget: a dead "
                         "peer costs this many seconds, never a hang")
    ap.add_argument("--watchdog-deadline", type=float, default=None,
                    help="arm the heartbeat watchdog: a step boundary "
                         "further apart than this aborts the process "
                         "(exit 114) with a watchdog_abort flight "
                         "incident — the wedged-collective conversion")
    ap.add_argument("--flight-dir", default=None,
                    help="FlightRecorder dump directory (watchdog/"
                         "preemption incidents land here)")
    args = ap.parse_args()

    n_dev = int(os.environ.get("RING_ATTN_CHAOS_DEVICES", "4"))
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + f" --xla_force_host_platform_device_count={n_dev}"
    )
    import jax

    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_default_matmul_precision", "highest")
    sys.path.insert(
        0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    )
    # share the test suite's persistent compile cache: repeat chaos runs
    # pay XLA compilation once per (device count, shape), not per run
    from ring_attention_tpu.utils import enable_compile_cache

    enable_compile_cache(
        os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     ".jax_cache")
    )
    import numpy as np
    import optax

    from ring_attention_tpu.elastic import (
        ElasticCheckpointManager,
        PreemptionGuard,
        Watchdog,
        chaos,
    )
    from ring_attention_tpu.models import RingTransformer
    from ring_attention_tpu.parallel import (
        create_mesh,
        initialize_multihost,
        remesh_plan,
        shard_batch,
    )
    from ring_attention_tpu.utils import (
        FlightRecorder,
        make_train_step,
        resilience,
        tracing,
    )

    cluster = chaos.cluster_from_env()
    if cluster is not None:
        pid, nproc, port = cluster
        initialize_multihost(
            coordinator_address=f"127.0.0.1:{port}",
            num_processes=nproc,
            process_id=pid,
        )
    proc = int(jax.process_index())
    # span tracing (env-armed like chaos): every barrier wait, save
    # phase, watchdog beat, and chaos kill from this process lands in
    # its own spans_pNNNNN.jsonl for the merged cluster timeline
    tracing.configure_from_env(process=proc)
    nproc = int(jax.process_count())
    world = int(jax.device_count())  # global across the cluster

    armed = chaos.arm_from_env()
    if armed:
        print(f"chaos armed: {armed}", flush=True)

    mgr = ElasticCheckpointManager(
        args.ckpt_dir, keep=3, async_save=not args.sync_save,
        barrier_timeout_s=args.barrier_timeout,
    )
    manifest = mgr.latest_manifest()
    if manifest is not None:
        plan, diags = remesh_plan(
            manifest.get("mesh"), world, dcn_data_size=nproc
        )
        for line in diags:
            print(line, flush=True)
    elif nproc > 1:
        # fresh multi-process start: the dcn level is the process count,
        # each process's devices form one ring strictly inside it
        plan = {"ring_size": world // nproc, "dcn_data_size": nproc}
    else:
        plan = {"ring_size": world}
    mesh = create_mesh(**plan)
    ring = plan["ring_size"] * (plan.get("ulysses_size") or 1)

    model = RingTransformer(
        num_tokens=64, dim=16, depth=1, heads=2, dim_head=8, causal=True,
        striped=True, bucket_size=args.seq_len // ring, mesh=mesh,
        use_ring=True,
    )
    # the SAME synthetic batch every step and every run: loss
    # trajectories are then comparable across kills and device counts
    rng = np.random.default_rng(0)
    base = rng.integers(0, 64, (2, args.seq_len // 2))
    full = np.concatenate([base, base], axis=1).astype(np.int32)
    if nproc > 1:
        # each process passes only ITS dcn group's batch rows
        rows = full.shape[0] // nproc
        local = full[proc * rows:(proc + 1) * rows]
    else:
        local = full
    tokens = shard_batch(local, mesh)
    opt = optax.adamw(1e-2)

    def fresh():
        params = model.init(jax.random.PRNGKey(0), tokens)
        return {"params": params, "opt_state": opt.init(params)}

    state, start = mgr.resume_or_init(
        fresh, mesh=mesh, seq_len=args.seq_len
    )
    if mgr.last_resume is not None:
        for line in mgr.last_resume["diagnostics"]:
            print(line, flush=True)

    def loss_fn(p, t):
        loss = model.apply(p, t, return_loss=True)
        # wedge simulation point: armed hang_collective stalls the
        # compiled step at RUN time (chaos.delay_tap) — the watchdog's
        # prey.  Disarmed it is an exact multiply by 1.0.
        return chaos.delay_tap(loss)

    # ZeRO-1: optimizer moments sharded over the full data-parallel
    # world, both tiers (utils/train.py).  Multi-process this is what
    # makes every process OWN part of the checkpoint — the per-process
    # shard write sets are disjoint and NON-EMPTY (a replicated state
    # would dedupe every leaf onto process 0's lowest device), so the
    # mid-shard chaos window exists on every worker.  Single-process
    # meshes here keep data=1, where the constraint is a no-op.
    step_fn = jax.jit(make_train_step(
        loss_fn, opt, shard_opt_state=True, shard_mesh=mesh
    ))

    recorder = None
    if args.flight_dir:
        recorder = FlightRecorder(args.flight_dir, window=16)
    dog = None
    if args.watchdog_deadline:
        dog = Watchdog(
            args.watchdog_deadline, recorder=recorder
        ).start()

    log = open(args.loss_log, "a") if proc == 0 else None

    def log_row(step: int, loss: float) -> None:
        if log is None:
            return
        log.write(json.dumps(
            {"step": step, "loss": loss, "world": world}
        ) + "\n")
        log.flush()
        os.fsync(log.fileno())

    def should_stop(guard, step: int) -> bool:
        if nproc > 1:
            return guard.should_stop_cluster(step=step)
        return guard.should_stop()

    params, opt_state = state["params"], state["opt_state"]
    injector = resilience.get_injector()
    with PreemptionGuard() as guard:
        for step in range(start, args.steps):
            # step-gated wedge (chaos env "wedge_at_step=K"): arm the
            # in-graph delay at exactly step K, so earlier steps beat
            # the watchdog normally and THEN the compiled step stalls —
            # the deterministic wedged-collective simulation
            if injector.armed("wedge_at_step") and step == int(
                injector.value("wedge_at_step")
            ):
                injector.arm("hang_collective", float(
                    injector.value("wedge_seconds", 120) or 120
                ))
            with tracing.get_tracer().span("train/step", step=step):
                params, opt_state, loss = step_fn(
                    params, opt_state, tokens
                )
                loss = float(loss)  # sync: the step genuinely finished
            if dog is not None:
                dog.beat(step)
            # mid-run hard death (kill_at_step=K): after the step
            # computed, before anything was saved or logged
            chaos.chaos_point(chaos.KILL_AT_STEP, step=step)
            log_row(step, loss)
            if should_stop(guard, step):
                mgr.save(
                    step,
                    {"params": params, "opt_state": opt_state},
                    block=True,
                )
                print(f"DRAINED {guard.signal_name} step={step}",
                      flush=True)
                break
            if step % args.save_every == 0 or step == args.steps - 1:
                mgr.save(step, {"params": params, "opt_state": opt_state})
    mgr.close()
    if dog is not None:
        dog.stop()
    tracing.shutdown()
    if log is not None:
        log.close()
    print(f"ELASTIC-OK start={start} world={world} proc={proc}",
          flush=True)


if __name__ == "__main__":
    main()
