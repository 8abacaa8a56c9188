"""Chaos matrix for the elastic runtime (docs/resilience.md).

The acceptance property is *kill-anywhere safety*: a process killed at
any injected point — mid-step, mid-shard-write, with a complete staging
dir but no commit, mid-resume — comes back from a valid checkpoint with
no torn state, and a resume at a CHANGED device count (4 -> 2 and
2 -> 4) reproduces the uninterrupted run's loss trajectory within
:data:`TOL` (same-world resumes restore params bit-exactly; cross-world
differences are reduction-order noise, measured ~2e-7 on this suite's
model).  All of it on CPU virtual devices, with real OS processes dying
real deaths (``tests/elastic_worker.py`` + ``elastic/chaos.py``).

Plus the in-process halves: PreemptionGuard drain under a real SIGTERM
and under the fault injector, async-save double buffering and error
propagation, the ``on_step_end`` hook HLO pin, the wedge-simulation
delay tap under a ``with_retries`` deadline.
"""

import json
import os
import signal
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from ring_attention_tpu.elastic import (
    AsyncSaveError,
    ElasticCheckpointManager,
    PreemptionGuard,
    chaos,
)
from ring_attention_tpu.utils import make_train_step
from ring_attention_tpu.utils import resilience

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WORKER = os.path.join(REPO, "tests", "elastic_worker.py")

# loss-trajectory parity tolerance across a re-mesh resume (documented
# in docs/resilience.md): params restore bit-exactly, so the only drift
# is reduction order at the new mesh factoring
TOL = 1e-4


def _run_worker(ckpt_dir, loss_log, *, devices, steps=8, chaos_faults=None,
                sync=False, timeout=280):
    w = chaos.ChaosWorker(
        [sys.executable, WORKER, "--ckpt-dir", str(ckpt_dir),
         "--loss-log", str(loss_log), "--steps", str(steps)]
        + (["--sync-save"] if sync else []),
        cwd=REPO, timeout=timeout,
    )
    return w.run(devices=devices, chaos=chaos_faults)


def _read_log(path) -> dict[int, float]:
    out: dict[int, float] = {}
    try:
        with open(path) as f:
            for line in f:
                if line.strip():
                    row = json.loads(line)
                    out[row["step"]] = row["loss"]
    except FileNotFoundError:
        pass
    return out


def _committed_steps(ckpt_dir) -> list[int]:
    return ElasticCheckpointManager(ckpt_dir).all_steps()


@pytest.fixture(scope="module")
def baseline4(tmp_path_factory):
    """Uninterrupted 8-step run at world 4: the parity reference."""
    d = tmp_path_factory.mktemp("elastic_baseline")
    log = d / "loss.jsonl"
    r = _run_worker(d / "ck", log, devices=4, steps=8)
    assert r.returncode == 0, r.stdout + r.stderr
    losses = _read_log(log)
    assert sorted(losses) == list(range(8)), losses
    return losses


# ----------------------------------------------------------------------
# The kill-anywhere matrix (real process deaths, subprocess worker)
# ----------------------------------------------------------------------


def test_kill_anywhere_matrix_then_remesh_4_to_2(tmp_path, baseline4):
    """One checkpoint directory survives four consecutive violent deaths
    — mid-step, mid-shard-write, staged-but-uncommitted, mid-resume —
    and the fifth run finishes at HALF the device count with the
    baseline's loss trajectory."""
    ck, log = tmp_path / "ck", tmp_path / "loss.jsonl"

    # (1) die mid-run at step 3, after step 0's checkpoint committed
    r = _run_worker(ck, log, devices=4, sync=True,
                    chaos_faults={chaos.KILL_AT_STEP: 3})
    assert r.returncode == chaos.CHAOS_EXIT_CODE, r.stdout + r.stderr
    assert _committed_steps(ck) == [0]

    # (2) die mid-shard-write: some shard files durable, no manifest —
    # the step must NOT become visible, step 0 stays the resume point
    r = _run_worker(ck, log, devices=4, sync=True,
                    chaos_faults=[chaos.KILL_MID_SHARD])
    assert r.returncode == chaos.CHAOS_EXIT_CODE, r.stdout + r.stderr
    assert _committed_steps(ck) == [0], (
        "a torn save leaked into the committed steps"
    )
    assert any(".writing-" in n for n in os.listdir(ck)), (
        "expected the dead writer's staging debris"
    )

    # (3) die with the staging dir COMPLETE (manifest written) but the
    # commit rename not executed: still not a committed checkpoint
    r = _run_worker(ck, log, devices=4, sync=True,
                    chaos_faults=[chaos.KILL_PRE_COMMIT])
    assert r.returncode == chaos.CHAOS_EXIT_CODE, r.stdout + r.stderr
    assert _committed_steps(ck) == [0]

    # (4) die mid-resume: restore is read-only — the checkpoint must
    # survive a killed reader fully intact
    r = _run_worker(ck, log, devices=4, sync=True,
                    chaos_faults=[chaos.KILL_MID_RESUME])
    assert r.returncode == chaos.CHAOS_EXIT_CODE, r.stdout + r.stderr
    assert _committed_steps(ck) == [0]

    # (5) come back at HALF the world and finish; every step any run
    # logged must match the uninterrupted baseline (re-executed steps
    # restore bit-exact params; world-2 steps differ only by reduction
    # order).  The staging debris from (2)/(3) is swept by the saves.
    r = _run_worker(ck, log, devices=2)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "re-mesh: ring 4 -> 2" in r.stdout
    assert "re-mesh resume" in r.stdout
    losses = _read_log(log)
    assert sorted(losses) == list(range(8))
    for step, loss in losses.items():
        assert abs(loss - baseline4[step]) < TOL, (
            f"step {step}: {loss} vs baseline {baseline4[step]}"
        )
    assert not any(".writing-" in n for n in os.listdir(ck)), (
        "staging debris survived the post-resume saves"
    )


def test_remesh_2_to_4_matches_baseline(tmp_path, baseline4):
    """Grow the world mid-run: 4 steps at world 2, then resume at world
    4 — the full trajectory still matches the world-4 baseline."""
    ck, log = tmp_path / "ck", tmp_path / "loss.jsonl"
    r = _run_worker(ck, log, devices=2, steps=4)
    assert r.returncode == 0, r.stdout + r.stderr
    r = _run_worker(ck, log, devices=4, steps=8)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "re-mesh: ring 2 -> 4" in r.stdout
    losses = _read_log(log)
    assert sorted(losses) == list(range(8))
    for step, loss in losses.items():
        assert abs(loss - baseline4[step]) < TOL, (
            f"step {step}: {loss} vs baseline {baseline4[step]}"
        )


def test_sigterm_drain_end_to_end(tmp_path):
    """A real SIGTERM mid-run: the worker finishes its in-flight step,
    saves synchronously, reports the drain, and exits 0; the checkpoint
    holds the drained step."""
    ck, log = tmp_path / "ck", tmp_path / "loss.jsonl"
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["JAX_PLATFORMS"] = "cpu"
    env["RING_ATTN_CHAOS_DEVICES"] = "4"
    # output goes to a FILE: nobody reads a pipe while this test polls the
    # loss log, and a worker that fills the pipe buffer (jaxlib 0.9.0 logs
    # ~10 KB to stderr per CPU compile-cache hit) would block on the write
    # before ever installing its signal handler
    out_path = tmp_path / "worker.out"
    with open(out_path, "w") as out_file:
        proc = subprocess.Popen(
            [sys.executable, WORKER, "--ckpt-dir", str(ck),
             "--loss-log", str(log), "--steps", "2000",
             "--save-every", "100000"],
            stdout=out_file, stderr=subprocess.STDOUT, env=env, cwd=REPO,
        )
        try:
            deadline = time.monotonic() + 240
            while time.monotonic() < deadline:
                if len(_read_log(log)) >= 3:  # compiled and stepping
                    break
                if proc.poll() is not None:
                    break
                time.sleep(0.05)
            assert proc.poll() is None, out_path.read_text()[-4000:]
            proc.send_signal(signal.SIGTERM)
            proc.wait(timeout=120)
        finally:
            if proc.poll() is None:
                proc.kill()
                proc.wait()
    out = out_path.read_text()
    assert proc.returncode == 0, out
    assert "DRAINED SIGTERM step=" in out, out
    drained = int(out.split("DRAINED SIGTERM step=")[1].split()[0])
    steps = _committed_steps(ck)
    assert drained in steps, (drained, steps, out)
    # and the drained checkpoint actually resumes one step later
    r = _run_worker(ck, log, devices=4, steps=drained + 2)
    assert r.returncode == 0, r.stdout + r.stderr
    assert f"ELASTIC-OK start={drained + 1}" in r.stdout, r.stdout


# ----------------------------------------------------------------------
# PreemptionGuard, in process
# ----------------------------------------------------------------------


@pytest.fixture(autouse=True)
def _clean_faults():
    resilience.get_injector().clear()
    yield
    resilience.get_injector().clear()


def test_preemption_guard_fault_injector_drain(tmp_path, devices):
    """The signal-free chaos path: arming PREEMPT_FAULT trips
    should_stop, and drain saves + dumps a 'preemption' incident with
    the run's trajectory attached."""
    from ring_attention_tpu.elastic.preemption import PREEMPT_FAULT
    from ring_attention_tpu.utils import FlightRecorder, read_flight_dump

    recorder = FlightRecorder(str(tmp_path / "flight"), window=8)
    recorder.record(1, loss=2.0)
    recorder.record(2, loss=1.5)
    saved = []
    with PreemptionGuard() as guard:
        assert not guard.should_stop()
        with resilience.inject(PREEMPT_FAULT):
            assert guard.should_stop()
            guard.drain(lambda: saved.append(True), recorder=recorder,
                        step=2)
    assert saved == [True]
    assert guard.signal_name == "injected"
    assert len(recorder.dumps) == 1
    dump = read_flight_dump(recorder.dumps[0])
    assert dump["trigger"]["kind"] == "preemption"
    assert dump["trigger"]["step"] == 2
    assert [r["loss"] for r in dump["rows"]] == [2.0, 1.5]


def test_preemption_guard_real_signal_and_escalation():
    with PreemptionGuard() as guard:
        os.kill(os.getpid(), signal.SIGTERM)
        time.sleep(0.05)  # let the handler run at a bytecode boundary
        assert guard.requested and guard.signal_name == "SIGTERM"
        # a second signal during the drain escalates to KeyboardInterrupt
        with pytest.raises(KeyboardInterrupt, match="second SIGTERM"):
            os.kill(os.getpid(), signal.SIGTERM)
            time.sleep(1.0)
    # handlers restored: a guard-less process keeps default behavior
    assert signal.getsignal(signal.SIGTERM) is not guard._handler


def test_preemption_guard_drain_is_idempotent_and_save_first(tmp_path):
    calls = []
    guard = PreemptionGuard()
    guard.drain(lambda: calls.append("save"))
    guard.drain(lambda: calls.append("save"))
    assert calls == ["save"]  # latched


# ----------------------------------------------------------------------
# Async saves: double buffering + error propagation
# ----------------------------------------------------------------------


def _mesh(n):
    from ring_attention_tpu.parallel.mesh import create_mesh

    return create_mesh(ring_size=n, devices=jax.devices()[:n])


def _sharded_state(mesh, scale=1.0):
    from jax.sharding import NamedSharding, PartitionSpec as P

    x = jnp.arange(64.0).reshape(4, 16) * scale
    return {
        "x": jax.device_put(x, NamedSharding(mesh, P(None, "seq"))),
        "step": jnp.asarray(7, jnp.int32),
    }


def test_async_save_returns_before_write_and_snapshot_is_isolated(tmp_path):
    """save() must return after the host snapshot, not the file write —
    and the snapshot must be insulated from later mutation of the live
    state (the double-buffer contract donated buffers rely on)."""
    import threading

    mesh = _mesh(4)
    state = _sharded_state(mesh, scale=1.0)
    mgr = ElasticCheckpointManager(str(tmp_path), async_save=True)
    gate = threading.Event()
    real_write = mgr._write

    def slow_write(step, snap):
        assert gate.wait(timeout=60)
        return real_write(step, snap)

    mgr._write = slow_write
    t0 = time.monotonic()
    mgr.save(5, state)
    assert mgr.all_steps() == []  # returned while the write is gated
    assert time.monotonic() - t0 < 30
    gate.set()
    mgr.wait()
    assert mgr.all_steps() == [5]
    restored = mgr.restore(_sharded_state(mesh, 0.0), mesh=mesh)
    np.testing.assert_array_equal(
        np.asarray(restored[0]["x"]), np.arange(64.0).reshape(4, 16)
    )


def test_async_save_error_surfaces_on_next_call(tmp_path):
    mesh = _mesh(2)
    mgr = ElasticCheckpointManager(str(tmp_path), async_save=True)

    def boom(step, snap):
        raise OSError("disk full")

    mgr._write = boom
    mgr.save(1, _sharded_state(mesh))
    with pytest.raises(AsyncSaveError, match="disk full"):
        mgr.wait()
    # the error is consumed: the manager stays usable
    mgr.wait()


def test_elastic_contract_suite_is_clean():
    """The --elastic CLI checks, in-process: manifest round-trip,
    resharded == direct load, corrupt-shard fallback, debris sweep."""
    from ring_attention_tpu.elastic import run_elastic_suite

    for name, violations in run_elastic_suite():
        assert not violations, f"{name}: {violations}"


def test_elastic_explicit_corrupt_step_raises(tmp_path):
    """restore(step=N) on a corrupt elastic step raises instead of
    returning None (which callers read as 'cold start')."""
    from ring_attention_tpu.utils.checkpoint import CheckpointCorruptError

    mesh = _mesh(2)
    mgr = ElasticCheckpointManager(str(tmp_path), async_save=False)
    mgr.save(3, _sharded_state(mesh))
    step3 = mgr._step_dir(3)
    shard = sorted(n for n in os.listdir(step3)
                   if n.startswith("shard_"))[0]
    chaos.corrupt_file(os.path.join(step3, shard), "truncate")
    with pytest.raises(CheckpointCorruptError):
        mgr.restore(_sharded_state(mesh), mesh=mesh, step=3)


def test_corrupted_shard_garbage_falls_back(tmp_path):
    """Bit-rot (not just truncation) in a shard file fails the digest
    and falls back to the previous step."""
    mesh = _mesh(4)
    mgr = ElasticCheckpointManager(str(tmp_path), async_save=False)
    mgr.save(1, _sharded_state(mesh, 1.0))
    mgr.save(2, _sharded_state(mesh, 2.0))
    step2 = mgr._step_dir(2)
    shard = sorted(n for n in os.listdir(step2)
                   if n.startswith("shard_"))[0]
    chaos.corrupt_file(os.path.join(step2, shard), "garbage")
    with pytest.warns(UserWarning, match="corrupt"):
        restored = mgr.restore(_sharded_state(mesh, 0.0), mesh=mesh)
    assert restored is not None and restored[1] == 1
    np.testing.assert_array_equal(
        np.asarray(restored[0]["x"]), np.arange(64.0).reshape(4, 16)
    )


# ----------------------------------------------------------------------
# Re-mesh planning + divisibility diagnostics
# ----------------------------------------------------------------------


def test_remesh_plan_preserves_data_and_ulysses():
    from ring_attention_tpu.parallel import remesh_plan

    old = {"axes": ["data", "ring", "ulysses"], "shape": [2, 4, 2]}
    plan, diags = remesh_plan(old, 8)
    assert plan == {"ring_size": 2, "data_size": 2, "ulysses_size": 2}
    assert any("world 16 -> 8" in d for d in diags)
    assert any("ring 4 -> 2" in d for d in diags)
    # same world: no diagnostics, same factoring
    plan, diags = remesh_plan(old, 16)
    assert plan == {"ring_size": 4, "data_size": 2, "ulysses_size": 2}
    assert diags == []
    # data no longer divides: shrink to gcd, say so
    plan, diags = remesh_plan(
        {"axes": ["data", "seq"], "shape": [4, 2]}, 2
    )
    assert plan["data_size"] == 2 and plan["ring_size"] == 1
    assert any("does not divide" in d for d in diags)


def test_remesh_plan_dcn_tier():
    """The pod-scale re-mesh rules: the dcn tier tracks the CURRENT
    process count when given (dropping to 1 removes the axis), is
    preserved-while-dividing otherwise, and divisibility violations are
    one-line errors."""
    from ring_attention_tpu.parallel import remesh_plan

    old = {"axes": ["dcn_data", "data", "seq"], "shape": [2, 1, 4]}
    # lost a host: re-plan at 1 process, half the world — dcn drops
    plan, diags = remesh_plan(old, 4, dcn_data_size=1)
    assert plan == {"ring_size": 4, "data_size": 1}
    assert any("dcn_data 2 -> 1 (process count changed)" in d
               for d in diags)
    # same cluster shape: same factoring, no diagnostics
    plan, diags = remesh_plan(old, 8, dcn_data_size=2)
    assert plan == {"ring_size": 4, "data_size": 1, "dcn_data_size": 2}
    assert diags == []
    # no process count given: dcn preserved while it divides
    plan, _ = remesh_plan(old, 16)
    assert plan["dcn_data_size"] == 2 and plan["ring_size"] == 8
    # grew the pod: 1 -> 2 processes over a flat checkpoint
    plan, diags = remesh_plan(
        {"axes": ["data", "seq"], "shape": [1, 4]}, 4, dcn_data_size=2
    )
    assert plan == {"ring_size": 2, "data_size": 1, "dcn_data_size": 2}
    assert any("dcn_data 1 -> 2" in d for d in diags)
    # indivisible process count is a one-line error
    with pytest.raises(ValueError, match="dcn_data_size 3"):
        remesh_plan(old, 8, dcn_data_size=3)


def test_create_mesh_dcn_shape_and_validation(devices):
    """The hierarchical mesh: dcn_data outermost, inner axes unchanged,
    divisibility violations one-line."""
    from ring_attention_tpu.parallel import (
        create_mesh,
        data_partition,
        data_world,
        has_dcn,
        mesh_descriptor,
        seq_world,
    )

    mesh = create_mesh(dcn_data_size=2, ring_size=2, data_size=2)
    assert tuple(mesh.axis_names) == ("dcn_data", "data", "seq")
    assert dict(mesh.shape) == {"dcn_data": 2, "data": 2, "seq": 2}
    assert has_dcn(mesh) and data_partition(mesh) == ("dcn_data", "data")
    assert data_world(mesh) == 4 and seq_world(mesh) == 2
    assert mesh_descriptor(mesh)["axes"] == ["dcn_data", "data", "seq"]
    factored = create_mesh(dcn_data_size=2, ring_size=2, ulysses_size=2)
    assert tuple(factored.axis_names) == (
        "dcn_data", "data", "ring", "ulysses"
    )
    # flat meshes are unchanged by the new axis machinery
    flat = _mesh(4)
    assert not has_dcn(flat) and data_partition(flat) == "data"
    with pytest.raises(ValueError, match="dcn_data_size 3"):
        create_mesh(dcn_data_size=3)


def test_validate_seq_len_one_line_diagnostic(devices):
    from ring_attention_tpu.parallel import validate_seq_len

    mesh = _mesh(4)
    validate_seq_len(64, mesh)  # divisible: fine
    with pytest.raises(ValueError, match=r"seq_len 66 % sequence world 4"):
        validate_seq_len(66, mesh)


# ----------------------------------------------------------------------
# on_step_end hook
# ----------------------------------------------------------------------


def _tiny_problem():
    def loss_fn(p, x):
        return jnp.sum((p["w"] * x - 1.0) ** 2)

    params = {"w": jnp.arange(1.0, 5.0)}
    opt = optax.sgd(1e-2)
    return loss_fn, params, opt


def test_on_step_end_unset_is_strict_noop():
    loss_fn, params, opt = _tiny_problem()
    step = make_train_step(loss_fn, opt)
    assert not hasattr(step, "__wrapped__")  # the same bare callable


def test_on_step_end_fires_with_outputs():
    loss_fn, params, opt = _tiny_problem()
    seen = []
    step = make_train_step(loss_fn, opt, on_step_end=seen.append)
    out = step(params, opt.init(params), jnp.ones(4))
    assert len(seen) == 1 and seen[0] is out
    assert len(out) == 3  # (params, opt_state, loss) handed over intact


def test_on_step_end_rejects_outer_jit_instead_of_silently_dropping():
    """jitting the HOOKED wrapper would bake the host hook away at trace
    time (it would fire once, on tracers, then never again) — the
    wrapper must refuse loudly and point at the supported patterns."""
    loss_fn, params, opt = _tiny_problem()
    hooked = make_train_step(loss_fn, opt, on_step_end=lambda out: None)
    jitted = jax.jit(hooked)
    with pytest.raises(RuntimeError, match="__wrapped__|jit_donate"):
        jitted(params, opt.init(params), jnp.ones(4))
    # the supported patterns still work (donating call LAST: it deletes
    # the donated params/opt_state buffers)
    jax.jit(hooked.__wrapped__)(params, opt.init(params), jnp.ones(4))
    make_train_step(
        loss_fn, opt, jit_donate=True, on_step_end=lambda out: None
    )(params, opt.init(params), jnp.ones(4))


def test_on_step_end_adds_zero_collectives(rng, devices):
    """The HLO pin: the hook's inner (lowerable) step compiles to the
    IDENTICAL collective sequence as the hookless step — the hook lives
    entirely outside the compiled program."""
    from ring_attention_tpu import RingTransformer, create_mesh
    from ring_attention_tpu.analysis.contracts import hlo_collective_sequence

    mesh = create_mesh(ring_size=4)
    model = RingTransformer(
        num_tokens=64, dim=32, depth=1, heads=4, dim_head=8, causal=True,
        striped=True, bucket_size=8, mesh=mesh, use_ring=True,
    )
    toks = jnp.asarray(rng.integers(0, 64, (2, 64)), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), toks, return_loss=True)
    opt = optax.adam(1e-3)
    opt_state = opt.init(params)

    def loss_fn(p, t):
        return model.apply(p, t, return_loss=True)

    plain = make_train_step(loss_fn, opt)
    hooked = make_train_step(loss_fn, opt, on_step_end=lambda out: None)
    args = (params, opt_state, toks)
    txt_plain = jax.jit(plain).lower(*args).compile().as_text()
    txt_hooked = jax.jit(hooked.__wrapped__).lower(*args).compile().as_text()
    seq_plain = hlo_collective_sequence(txt_plain)
    seq_hooked = hlo_collective_sequence(txt_hooked)
    assert seq_plain, "expected ring collectives in the train step"
    assert seq_hooked == seq_plain


# ----------------------------------------------------------------------
# Wedge simulation: injected delay
# ----------------------------------------------------------------------


def test_delay_tap_simulates_hung_step():
    """The SAME compiled step runs fast when disarmed and stalls for the
    armed delay — and a with_retries deadline cuts the stall off."""
    @jax.jit
    def step(x):
        return jnp.sum(chaos.delay_tap(x, "hang_collective"))

    x = jnp.ones(16)
    float(step(x))  # compile, disarmed
    t0 = time.monotonic()
    float(step(x))
    assert time.monotonic() - t0 < 0.2
    with resilience.inject("hang_collective", 0.6):
        t0 = time.monotonic()
        float(step(x))
        assert time.monotonic() - t0 >= 0.5
    # keep the armed hang short: inject()'s exit drains pending jax
    # callbacks (effects_barrier), so the abandoned sleeper still runs
    # to completion before the block closes
    with resilience.inject("hang_collective", 2.0):
        with pytest.raises(resilience.RetryError):
            resilience.with_retries(
                lambda: float(step(x)),
                timeout=0.3, max_attempts=1, backoff=0.0,
            )


# ----------------------------------------------------------------------
# Watchdog: a wedged step becomes a bounded abort (in-process half; the
# spawned-cluster pin lives in tests/test_multihost.py)
# ----------------------------------------------------------------------


def test_watchdog_fires_after_deadline_with_incident(tmp_path):
    """A heartbeat that goes stale past the deadline fires the abort
    exactly once, with the stalled step named in the message AND in a
    ``watchdog_abort`` flight incident — the conversion that turns an
    eternal hang into a restartable death."""
    from ring_attention_tpu.elastic import Watchdog
    from ring_attention_tpu.utils import FlightRecorder, read_flight_dump

    recorder = FlightRecorder(str(tmp_path), window=4)
    fired = []
    dog = Watchdog(0.3, recorder=recorder, abort=fired.append,
                   poll_s=0.05)
    with dog:
        dog.beat(7)
        deadline = time.monotonic() + 10
        while not fired and time.monotonic() < deadline:
            time.sleep(0.05)
    assert dog.fired and len(fired) == 1, fired
    assert "watchdog: no heartbeat" in fired[0]
    assert "step 7" in fired[0]
    dumps = sorted(os.listdir(tmp_path))
    assert dumps, "watchdog fired without dumping the incident"
    dump = read_flight_dump(os.path.join(tmp_path, dumps[-1]))
    assert dump["trigger"]["kind"] == "watchdog_abort"
    assert dump["trigger"]["step"] == 7
    assert dump["trigger"]["deadline_s"] == 0.3


def test_watchdog_not_armed_before_first_beat_and_beats_reset():
    """No abort before the first beat (the compile window is legal), and
    regular beats keep the clock fresh forever."""
    from ring_attention_tpu.elastic import Watchdog

    fired = []
    with Watchdog(0.25, abort=fired.append, poll_s=0.05) as dog:
        time.sleep(0.6)          # unarmed: way past the deadline
        assert not fired and not dog.fired
        for step in range(8):    # armed, but never stale
            dog.beat(step)
            time.sleep(0.05)
        assert not fired
    with pytest.raises(ValueError, match="deadline_s"):
        Watchdog(0.0)


def test_watchdog_exit_code_is_distinct():
    """114 collides with nothing the harness already distinguishes:
    success, crash, and the chaos kill code."""
    from ring_attention_tpu.elastic import WATCHDOG_EXIT_CODE

    assert WATCHDOG_EXIT_CODE not in (0, 1, chaos.CHAOS_EXIT_CODE)


# ----------------------------------------------------------------------
# Cluster-wide drain + cross-process barrier: single-process halves
# (the live two-process forms run in tests/test_multihost.py)
# ----------------------------------------------------------------------


def test_broadcast_drain_single_process_is_identity():
    from ring_attention_tpu.elastic import broadcast_drain

    assert broadcast_drain(False) is False
    assert broadcast_drain(True) is True


def test_should_stop_cluster_drains_and_thins(tmp_path):
    """``should_stop_cluster`` sees the injector-driven preemption like
    ``should_stop`` does, and the ``every`` thinning defers the check to
    aligned boundaries only — the alignment that keeps every process's
    broadcast schedule identical."""
    from ring_attention_tpu.elastic import PREEMPT_FAULT

    with PreemptionGuard() as guard:
        assert guard.should_stop_cluster(step=0) is False
        with resilience.inject(PREEMPT_FAULT):
            assert guard.should_stop()  # latch the injected drain
        # thinned: step 3 is not a multiple of every=4
        assert guard.should_stop_cluster(every=4, step=3) is False
        assert guard.should_stop_cluster(every=4, step=4) is True
        assert guard.should_stop_cluster(step=5) is True


def test_cross_process_barrier_single_process_noop():
    from ring_attention_tpu.elastic import cross_process_barrier

    t0 = time.monotonic()
    cross_process_barrier("test:solo", timeout_s=0.1)
    assert time.monotonic() - t0 < 0.1


# ----------------------------------------------------------------------
# ZeRO-1 optimizer-state sharding (the in-step knob; the base helper is
# pinned in tests/test_utils.py)
# ----------------------------------------------------------------------


def test_shard_opt_state_knob_shards_moments_and_matches(rng, devices):
    """``make_train_step(shard_opt_state=True)``: the returned Adam
    moments carry a data-axis sharding (both tiers on a hierarchical
    mesh), values match the unsharded step bit-for-bit on CPU, the
    donation/offload audits cover the composed program, and the analytic
    memory model divides the moment bytes."""
    import optax

    from ring_attention_tpu.analysis import (
        audit_donation,
        audit_host_offload,
    )
    from ring_attention_tpu.parallel import create_mesh, data_partition
    from ring_attention_tpu.utils import train_memory_estimate
    from ring_attention_tpu.utils.train import shard_optimizer_state

    mesh = create_mesh(dcn_data_size=2, ring_size=2, data_size=2)
    assert data_partition(mesh) == ("dcn_data", "data")
    w = {"w": jnp.asarray(rng.standard_normal((16, 8)), jnp.float32)}
    x = jnp.asarray(rng.standard_normal((8, 16)), jnp.float32)

    def loss_fn(params, x):
        return jnp.mean((x @ params["w"]) ** 2)

    opt = optax.adam(1e-2)
    with pytest.raises(ValueError, match="shard_mesh"):
        make_train_step(loss_fn, opt, shard_opt_state=True)
    plain = jax.jit(make_train_step(loss_fn, opt))
    step = make_train_step(loss_fn, opt, shard_opt_state=True,
                           shard_mesh=mesh, jit_donate=True)

    state0 = shard_optimizer_state(
        opt.init(w), mesh, axis=data_partition(mesh)
    )
    p1, s1, l1 = step(w, state0, x)
    mu = s1[0].mu["w"]
    assert "dcn_data" in str(mu.sharding.spec) and "data" in str(
        mu.sharding.spec
    ), mu.sharding
    # the constraint never changes semantics (the partitioned program
    # may re-associate reductions: tolerance, not bit-equality)
    p0, s0, l0 = plain(w, opt.init(w), x)
    np.testing.assert_allclose(float(l0), float(l1), rtol=1e-6)
    np.testing.assert_allclose(
        np.asarray(p0["w"]), np.asarray(p1["w"]), atol=1e-6
    )
    np.testing.assert_allclose(
        np.asarray(s0[0].mu["w"]), np.asarray(mu), atol=1e-6
    )
    assert audit_donation(step, w, state0, x, label="zero1") == []
    assert audit_host_offload(step, w, state0, x, label="zero1") == []

    n_params = 1_000_000
    kw = dict(n_params=n_params, batch=1, seq_len=4096, dim=256,
              heads=8, depth=4, vocab=256)
    base = train_memory_estimate(**kw)
    div = train_memory_estimate(**kw, shard_opt_data=4)
    # Adam moments (2x f32) divide 4-ways; everything else is untouched
    moments = 2 * n_params * 4
    assert base["params_bytes"] - div["params_bytes"] == (
        moments - moments // 4
    )
    assert div["peak_hbm_bytes"] < base["peak_hbm_bytes"]
