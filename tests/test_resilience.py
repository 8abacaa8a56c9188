"""Fault-injection harness for the resilience layer (docs/resilience.md).

Every failure mode the subsystem exists to survive is INJECTED here and
the recovery behavior asserted, all on the 8-virtual-device CPU mesh
(fast tier — no TPU, no `slow` marks except the subprocess kill/resume
end-to-end check):

- NaN gradients at step k -> the guarded step skips the update and the
  params are bit-identical to the pre-NaN state.
- A checkpoint truncated mid-write -> restore falls back to the previous
  good step (and an empty directory / changed optimizer structure give
  the documented cold-start / clear-error behaviors).
- A forced Pallas failure -> ``impl="auto"`` degrades to the XLA path
  with parity, a one-shot warning, and a queryable record.
- A hung probe -> ``with_retries`` times the attempt out and backs off
  exponentially.
"""

import glob
import os
import time
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from ring_attention_tpu.utils import (
    CheckpointManager,
    CheckpointStructureError,
    init_step_stats,
    make_train_step,
)
from ring_attention_tpu.utils import resilience


@pytest.fixture(autouse=True)
def _clean_resilience_state():
    """Armed faults and degradation records are process-global; never let
    one test's injection leak into the next."""
    resilience.reset()
    yield
    resilience.reset()


# ----------------------------------------------------------------------
# with_retries: timeout + exponential backoff
# ----------------------------------------------------------------------


def test_with_retries_passthrough():
    assert resilience.with_retries(lambda: 41 + 1) == 42


def test_with_retries_retries_then_succeeds():
    sleeps = []
    calls = []

    def flaky():
        calls.append(1)
        if len(calls) < 3:
            raise OSError("transient")
        return "ok"

    out = resilience.with_retries(
        flaky, backoff=0.5, max_attempts=5, sleep=sleeps.append
    )
    assert out == "ok"
    assert len(calls) == 3
    # exponential: backoff * 2**attempt for each failed attempt
    assert sleeps == [0.5, 1.0]


def test_with_retries_hung_callable_times_out_and_backs_off():
    """The round 3-5 wedge mode: a probe that simply never returns."""
    sleeps = []
    t0 = time.monotonic()
    with pytest.raises(resilience.RetryError) as ei:
        resilience.with_retries(
            lambda: time.sleep(30),
            timeout=0.05,
            backoff=0.01,
            max_attempts=3,
            sleep=sleeps.append,
        )
    # all three attempts timed out, each followed by doubled backoff
    assert isinstance(ei.value.last, resilience.RetryTimeout)
    assert sleeps == [0.01, 0.02]
    # wall time is attempts * timeout, NOT attempts * 30s: the hang was cut
    assert time.monotonic() - t0 < 5.0


def test_with_retries_respects_retry_on():
    with pytest.raises(KeyError):
        resilience.with_retries(
            lambda: (_ for _ in ()).throw(KeyError("boom")),
            retry_on=(OSError,),
            max_attempts=3,
        )


def test_with_retries_exhaustion_raises_retry_error():
    sleeps = []
    with pytest.raises(resilience.RetryError) as ei:
        resilience.with_retries(
            lambda: (_ for _ in ()).throw(OSError("down")),
            backoff=1.0,
            max_attempts=2,
            sleep=sleeps.append,
        )
    assert isinstance(ei.value.last, OSError)
    assert sleeps == [1.0]  # no sleep after the final attempt


def test_with_retries_validates_args():
    with pytest.raises(ValueError):
        resilience.with_retries(lambda: 1, max_attempts=0)
    with pytest.raises(ValueError):
        resilience.with_retries(lambda: 1, backoff=-1.0)


# ----------------------------------------------------------------------
# Guarded train step: NaN-grad injection
# ----------------------------------------------------------------------


def _tiny_problem():
    def loss_fn(p, x):
        return jnp.sum((p["w"] * x - 1.0) ** 2) + jnp.sum(p["b"] ** 2)

    params = {"w": jnp.arange(1.0, 5.0), "b": jnp.zeros(2)}
    opt = optax.adam(1e-2)
    return loss_fn, params, opt


def test_guarded_step_skips_nan_and_keeps_params_bit_identical():
    loss_fn, params, opt = _tiny_problem()
    # the injection hook: a pure_callback tap on the loss, so the SAME
    # compiled step can be poisoned at exactly step k from the host
    step = jax.jit(
        make_train_step(
            resilience.faulty_loss(loss_fn), opt, skip_nonfinite=True
        )
    )
    opt_state = opt.init(params)
    stats = init_step_stats()
    x = jnp.ones(4)

    for _ in range(3):  # healthy steps compile + move the params
        params, opt_state, stats, loss = step(params, opt_state, stats, x)
    assert bool(stats.step_ok) and int(stats.skipped) == 0

    pre_params = jax.device_get(params)
    pre_opt = jax.device_get(opt_state)
    with resilience.inject("nan_loss"):  # step k is poisoned
        params, opt_state, stats, loss = step(params, opt_state, stats, x)

    assert not bool(stats.step_ok)
    assert int(stats.skipped) == 1
    assert np.isnan(float(loss))  # the loss is reported, not masked
    post_params = jax.device_get(params)
    post_opt = jax.device_get(opt_state)
    for pre, post in ((pre_params, post_params), (pre_opt, post_opt)):
        for a, b in zip(jax.tree_util.tree_leaves(pre),
                        jax.tree_util.tree_leaves(post)):
            np.testing.assert_array_equal(a, b)  # bit-identical, not close

    # the run RESUMES: the next healthy step applies normally
    params, opt_state, stats, loss = step(params, opt_state, stats, x)
    assert bool(stats.step_ok)
    assert int(stats.skipped) == 1
    assert np.isfinite(float(loss))
    changed = any(
        not np.array_equal(a, b)
        for a, b in zip(jax.tree_util.tree_leaves(post_params),
                        jax.tree_util.tree_leaves(jax.device_get(params)))
    )
    assert changed, "healthy step after a skip must update params"


def test_guarded_step_matches_unguarded_when_healthy():
    loss_fn, params, opt = _tiny_problem()
    x = jnp.full(4, 0.5)
    plain = jax.jit(make_train_step(loss_fn, opt))
    guarded = jax.jit(make_train_step(loss_fn, opt, skip_nonfinite=True))
    p1, o1 = params, opt.init(params)
    p2, o2, stats = params, opt.init(params), init_step_stats()
    for _ in range(4):
        p1, o1, l1 = plain(p1, o1, x)
        p2, o2, stats, l2 = guarded(p2, o2, stats, x)
    assert int(stats.skipped) == 0
    np.testing.assert_allclose(float(l1), float(l2), rtol=0, atol=0)
    for a, b in zip(jax.tree_util.tree_leaves(jax.device_get(p1)),
                    jax.tree_util.tree_leaves(jax.device_get(p2))):
        np.testing.assert_array_equal(a, b)


def test_clip_grad_norm_bounds_the_update():
    def loss_fn(p, x):
        return 1e6 * jnp.sum(p["w"] * x)  # huge constant gradient

    params = {"w": jnp.zeros(4)}
    opt = optax.sgd(1.0)
    x = jnp.ones(4)
    step = jax.jit(make_train_step(loss_fn, opt, clip_grad_norm=1.0))
    new_params, _, _ = step(params, opt.init(params), x)
    gnorm = float(optax.global_norm(
        jax.tree_util.tree_map(
            lambda a, b: a - b, params, new_params
        )
    ))
    assert gnorm <= 1.0 + 1e-5, gnorm


def test_make_train_step_validates_clip():
    loss_fn, params, opt = _tiny_problem()
    with pytest.raises(ValueError):
        make_train_step(loss_fn, opt, clip_grad_norm=0.0)


def test_guarded_step_with_accumulation():
    loss_fn, params, opt = _tiny_problem()
    step = jax.jit(
        make_train_step(
            resilience.faulty_loss(loss_fn), opt,
            accum_steps=2, skip_nonfinite=True,
        )
    )
    opt_state, stats = opt.init(params), init_step_stats()
    x = jnp.ones((2, 4))  # leading batch dim splits into 2 microbatches
    params, opt_state, stats, loss = step(params, opt_state, stats, x)
    assert bool(stats.step_ok)
    pre = jax.device_get(params)
    with resilience.inject("nan_loss"):
        params, opt_state, stats, loss = step(params, opt_state, stats, x)
    assert not bool(stats.step_ok) and int(stats.skipped) == 1
    for a, b in zip(jax.tree_util.tree_leaves(pre),
                    jax.tree_util.tree_leaves(jax.device_get(params))):
        np.testing.assert_array_equal(a, b)


# ----------------------------------------------------------------------
# Checkpoints: truncation, fallback, retention, structure, resume
# ----------------------------------------------------------------------


def _make_state(seed: float = 0.0):
    params = {"w": jnp.arange(4.0) + seed, "b": jnp.zeros((2, 3)) + seed}
    opt = optax.adam(1e-3)
    return {"params": params, "opt_state": opt.init(params)}


def test_checkpoint_truncated_mid_write_falls_back(tmp_path):
    mgr = CheckpointManager(tmp_path, keep=3)
    s1, s2 = _make_state(1.0), _make_state(2.0)
    mgr.save(10, s1)
    mgr.save(20, s2)

    # the preemption: the newest checkpoint's payload is cut mid-file
    npz = os.path.join(str(tmp_path), "step_00000020", "arrays.npz")
    with open(npz, "r+b") as f:
        f.truncate(os.path.getsize(npz) // 2)

    with pytest.warns(UserWarning, match="corrupt"):
        restored = mgr.restore(_make_state())
    assert restored is not None
    state, step = restored
    assert step == 10  # fell back to the previous good step
    np.testing.assert_array_equal(
        np.asarray(state["params"]["w"]), np.asarray(s1["params"]["w"])
    )


def test_checkpoint_unreadable_manifest_falls_back(tmp_path):
    mgr = CheckpointManager(tmp_path, keep=3)
    mgr.save(1, _make_state(1.0))
    mgr.save(2, _make_state(2.0))
    man = os.path.join(str(tmp_path), "step_00000002", "manifest.json")
    with open(man, "w") as f:
        f.write("{not json")
    with pytest.warns(UserWarning, match="corrupt"):
        restored = mgr.restore(_make_state())
    assert restored is not None and restored[1] == 1


def test_checkpoint_restore_missing_and_empty_dir(tmp_path):
    # missing: the manager creates the dir, restore finds nothing
    mgr = CheckpointManager(os.path.join(str(tmp_path), "never_written"))
    assert mgr.restore(_make_state()) is None
    assert mgr.latest_step() is None
    state, start = mgr.resume_or_init(lambda: _make_state(5.0))
    assert start == 0
    np.testing.assert_array_equal(
        np.asarray(state["params"]["w"]), np.asarray(_make_state(5.0)["params"]["w"])
    )


def test_checkpoint_changed_optimizer_structure_is_a_clear_error(tmp_path):
    mgr = CheckpointManager(tmp_path)
    mgr.save(0, _make_state())
    params = {"w": jnp.arange(4.0), "b": jnp.zeros((2, 3))}
    changed = {"params": params, "opt_state": optax.sgd(1e-3).init(params)}
    with pytest.raises(CheckpointStructureError, match="structure"):
        mgr.restore(changed)


def test_checkpoint_keep_last_n_retention(tmp_path):
    mgr = CheckpointManager(tmp_path, keep=2)
    for step in range(5):
        mgr.save(step, _make_state(float(step)))
    assert mgr.all_steps() == [3, 4]
    # the pruned directories are actually gone from disk
    dirs = sorted(glob.glob(os.path.join(str(tmp_path), "step_*")))
    assert [os.path.basename(d) for d in dirs] == [
        "step_00000003", "step_00000004"
    ]


def test_checkpoint_save_is_atomic_no_partial_step_dirs(tmp_path):
    mgr = CheckpointManager(tmp_path, keep=3)
    mgr.save(0, _make_state())
    # a stale temp dir from a preempted writer is swept by the next save
    stale = os.path.join(str(tmp_path), ".tmp-step_00000099-1234")
    os.makedirs(stale)
    mgr.save(1, _make_state(1.0))
    assert not os.path.exists(stale)
    assert mgr.all_steps() == [0, 1]


def test_resume_or_init_roundtrip_matches_uninterrupted_training(tmp_path):
    """Kill/resume equivalence on the real train-step machinery: a run
    resumed from step k's checkpoint reaches the same loss (bit-equal
    params) as one that never stopped."""
    loss_fn, params0, opt = _tiny_problem()
    step = jax.jit(make_train_step(loss_fn, opt))
    x = jnp.full(4, 0.5)

    # uninterrupted: 6 steps
    p, o = params0, opt.init(params0)
    for _ in range(6):
        p, o, loss_full = step(p, o, x)

    # interrupted: 3 steps, checkpoint, "crash", resume, 3 more
    mgr = CheckpointManager(tmp_path)
    p1, o1 = params0, opt.init(params0)
    for i in range(3):
        p1, o1, _ = step(p1, o1, x)
        mgr.save(i, {"params": p1, "opt_state": o1})
    del p1, o1  # the crash

    mgr2 = CheckpointManager(tmp_path)
    state, start = mgr2.resume_or_init(
        lambda: {"params": params0, "opt_state": opt.init(params0)}
    )
    assert start == 3
    p2, o2 = state["params"], state["opt_state"]
    for _ in range(start, 6):
        p2, o2, loss_resumed = step(p2, o2, x)

    np.testing.assert_array_equal(float(loss_full), float(loss_resumed))
    for a, b in zip(jax.tree_util.tree_leaves(jax.device_get(p)),
                    jax.tree_util.tree_leaves(jax.device_get(p2))):
        np.testing.assert_array_equal(a, b)


# ----------------------------------------------------------------------
# Kernel degradation: impl="auto" Pallas -> XLA fallback
# ----------------------------------------------------------------------


def _qkv():
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(1, 2, 64, 16)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(1, 2, 64, 16)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(1, 2, 64, 16)), jnp.float32)
    return q, k, v


def test_impl_auto_falls_back_with_xla_parity():
    from ring_attention_tpu.ops import attention, flash_attention

    q, k, v = _qkv()
    ref = flash_attention(q, k, v, causal=True)
    with pytest.warns(UserWarning, match="degraded"):
        with resilience.inject(resilience.PALLAS_FAULT):
            out = attention(q, k, v, causal=True, impl="auto")
    assert resilience.degradation.is_degraded(resilience.PALLAS_COMPONENT)
    events = resilience.degradation.events()
    assert events and events[0].component == resilience.PALLAS_COMPONENT
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-6)

    # the degradation is sticky: later auto calls take XLA silently
    with warnings.catch_warnings():
        warnings.simplefilter("error")  # one-shot: no second warning
        out2 = attention(q, k, v, causal=True, impl="auto")
    np.testing.assert_allclose(np.asarray(out2), np.asarray(ref), atol=1e-6)


def test_impl_pallas_explicit_fails_loudly():
    from ring_attention_tpu.ops import attention

    q, k, v = _qkv()
    with resilience.inject(resilience.PALLAS_FAULT):
        with pytest.raises(resilience.InjectedFault):
            attention(q, k, v, causal=True, impl="pallas")


def test_impl_xla_never_touches_pallas():
    from ring_attention_tpu.ops import attention, flash_attention

    q, k, v = _qkv()
    with resilience.inject(resilience.PALLAS_FAULT):
        out = attention(q, k, v, causal=True, impl="xla")
    assert not resilience.degradation.is_degraded(resilience.PALLAS_COMPONENT)
    np.testing.assert_allclose(
        np.asarray(out),
        np.asarray(flash_attention(q, k, v, causal=True)),
        atol=0,
    )


def test_impl_auto_rejects_unknown():
    from ring_attention_tpu.ops import attention

    q, k, v = _qkv()
    with pytest.raises(ValueError, match="impl"):
        attention(q, k, v, impl="tpu_magic")


def test_model_impl_auto_parity_under_forced_pallas_failure():
    """End-to-end: a RingTransformer configured impl='auto' produces the
    same loss whether the Pallas path works or is forced to fail."""
    from ring_attention_tpu.models import RingTransformer

    rng = np.random.default_rng(1)
    toks = jnp.asarray(rng.integers(0, 64, (1, 33)), jnp.int32)
    model = RingTransformer(
        num_tokens=64, dim=32, depth=1, causal=True, heads=2, dim_head=16,
        bucket_size=32, use_ring=False, impl="auto",
    )
    params = model.init(jax.random.PRNGKey(0), toks, return_loss=True)
    baseline = float(model.apply(params, toks, return_loss=True))

    resilience.reset()
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", UserWarning)
        with resilience.inject(resilience.PALLAS_FAULT):
            degraded = float(model.apply(params, toks, return_loss=True))
    assert resilience.degradation.is_degraded(resilience.PALLAS_COMPONENT)
    np.testing.assert_allclose(baseline, degraded, atol=3e-5)


# ----------------------------------------------------------------------
# Satellite: loss_chunk_size validation
# ----------------------------------------------------------------------


@pytest.mark.parametrize("bad", [0, -1, -64])
def test_loss_chunk_size_validation(bad):
    from ring_attention_tpu.models import RingTransformer

    model = RingTransformer(
        num_tokens=16, dim=8, depth=1, causal=True, heads=1, dim_head=8,
        use_ring=False, loss_chunk_size=bad,
    )
    toks = jnp.zeros((1, 9), jnp.int32)
    with pytest.raises(ValueError, match="loss_chunk_size"):
        model.init(jax.random.PRNGKey(0), toks, return_loss=True)


def test_loss_chunk_size_valid_values_still_work():
    from ring_attention_tpu.models import RingTransformer

    toks = jnp.zeros((1, 9), jnp.int32)
    for ok in (None, 4):
        model = RingTransformer(
            num_tokens=16, dim=8, depth=1, causal=True, heads=1, dim_head=8,
            use_ring=False, loss_chunk_size=ok,
        )
        params = model.init(jax.random.PRNGKey(0), toks, return_loss=True)
        assert np.isfinite(float(model.apply(params, toks, return_loss=True)))


def test_impl_auto_input_error_does_not_degrade():
    """A caller's input mistake must raise as itself and must NOT mark the
    Pallas path degraded (the fallback is for kernel failures only)."""
    from ring_attention_tpu.ops import attention

    q, k, v = _qkv()
    bad_mask = jnp.ones((1, 7), bool)  # wrong kv length
    with pytest.raises(ValueError):
        attention(q, k, v, bad_mask, impl="auto")
    assert not resilience.degradation.is_degraded(resilience.PALLAS_COMPONENT)


def test_checkpoint_resave_same_step_is_atomic(tmp_path):
    mgr = CheckpointManager(tmp_path, keep=3)
    mgr.save(5, _make_state(1.0))
    mgr.save(5, _make_state(2.0))  # re-save over the existing step
    restored = mgr.restore(_make_state())
    assert restored is not None and restored[1] == 5
    np.testing.assert_array_equal(
        np.asarray(restored[0]["params"]["w"]),
        np.asarray(_make_state(2.0)["params"]["w"]),
    )
    # no .old backup lingers after a clean re-save
    assert not glob.glob(os.path.join(str(tmp_path), "*.old"))


def test_checkpoint_orphaned_backup_is_recovered(tmp_path):
    """Crash window between rename-aside and rename-into-place: the .old
    backup is a complete checkpoint and restore must recover it."""
    mgr = CheckpointManager(tmp_path, keep=3)
    mgr.save(7, _make_state(3.0))
    live = os.path.join(str(tmp_path), "step_00000007")
    os.replace(live, live + ".old")  # the simulated crash state
    restored = mgr.restore(_make_state())
    assert restored is not None and restored[1] == 7
    np.testing.assert_array_equal(
        np.asarray(restored[0]["params"]["w"]),
        np.asarray(_make_state(3.0)["params"]["w"]),
    )


def test_impl_auto_bad_head_chunks_raises_not_degrades():
    """A Pallas-only kwarg error is a caller mistake: it must raise, not
    silently return an un-chunked XLA result while degrading Pallas."""
    from ring_attention_tpu.ops import attention

    q, k, v = _qkv()  # 2 heads
    with pytest.raises(ValueError, match="head_chunks"):
        attention(q, k, v, causal=True, impl="auto", head_chunks=3)
    assert not resilience.degradation.is_degraded(resilience.PALLAS_COMPONENT)


def test_impl_auto_on_non_tpu_backend_prefers_xla_silently():
    """On a CPU backend 'auto' must resolve to XLA without any
    degradation record — interpret-mode Pallas would be a pessimization,
    and a warning would cry wolf on every CPU box."""
    assert jax.devices()[0].platform != "tpu"  # this suite forces CPU
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        assert resilience.resolve_attention_impl("auto") == "xla"
    assert not resilience.degradation.is_degraded(resilience.PALLAS_COMPONENT)


def test_checkpoint_explicit_missing_step_is_not_found_not_corrupt(tmp_path):
    mgr = CheckpointManager(tmp_path)
    mgr.save(1, _make_state())
    with pytest.raises(FileNotFoundError, match="step 42"):
        mgr.restore(_make_state(), step=42)


# ----------------------------------------------------------------------
# Cross-process manager races (PR 12 satellite): pid-aware sweep + the
# watcher-protocol directory lock around save/prune
# ----------------------------------------------------------------------


def test_sweep_spares_live_concurrent_writers_tmp_dir(tmp_path):
    """The pre-fix _sweep_tmp deleted ANY .tmp-* dir — including a
    concurrent manager's live in-flight save.  Now only dead writers'
    debris is swept: a temp dir stamped with a LIVE pid (another
    process's save in progress) survives, a dead pid's is removed."""
    mgr = CheckpointManager(tmp_path, keep=3)
    mgr.save(0, _make_state())
    live_pid = os.getppid()  # alive and not us: a concurrent writer
    live = os.path.join(str(tmp_path), f".tmp-step_00000099-{live_pid}")
    dead = os.path.join(str(tmp_path), ".tmp-step_00000098-999999999")
    os.makedirs(live)
    os.makedirs(dead)
    mgr.save(1, _make_state(1.0))  # save sweeps first
    assert os.path.isdir(live), "live concurrent writer's temp dir deleted"
    assert not os.path.isdir(dead), "dead writer's temp dir survived"
    # unparsable writer pid: only swept past the minimum age
    odd = os.path.join(str(tmp_path), ".tmp-whatever")
    os.makedirs(odd)
    mgr.save(2, _make_state(2.0))
    assert os.path.isdir(odd), "young unparsable temp dir swept too eagerly"


def test_keep_vs_concurrent_save_never_loses_the_latest(tmp_path):
    """Two managers (keep=2) hammering ONE directory from threads — the
    interleaving that used to let one manager's retention prune race
    another's rename window.  Under the directory lock every save+prune
    is a critical section: afterwards exactly the newest steps remain,
    every surviving step restores intact, and no .tmp debris is left."""
    import threading

    errors: list[BaseException] = []

    def writer(offset: int) -> None:
        try:
            mgr = CheckpointManager(tmp_path, keep=2, lock_stale_age=5.0)
            for i in range(4):
                mgr.save(offset + 2 * i, _make_state(float(offset + i)))
        except BaseException as e:  # noqa: BLE001 — surfaced below
            errors.append(e)

    threads = [threading.Thread(target=writer, args=(off,))
               for off in (0, 1)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors, errors
    mgr = CheckpointManager(tmp_path, keep=2)
    steps = mgr.all_steps()
    assert len(steps) <= 2 and max(steps) == 7, steps
    restored = mgr.restore(_make_state())
    assert restored is not None and restored[1] == max(steps)
    assert not glob.glob(os.path.join(str(tmp_path), ".tmp-*"))


def test_directory_lock_stale_takeover_and_contention(tmp_path):
    """The watcher protocol, in library form: takeover requires pid file
    + dead pid + minimum age; a LIVE holder is never stolen from."""
    from ring_attention_tpu.utils.resilience import (
        DirectoryLock,
        LockTimeout,
    )

    # stale lock (dead pid, old): a contender takes over
    lock_dir = os.path.join(str(tmp_path), ".ckpt.lock")
    os.makedirs(lock_dir)
    with open(os.path.join(lock_dir, "pid"), "w") as f:
        f.write("999999999")
    old = time.time() - 60
    os.utime(lock_dir, (old, old))
    lock = DirectoryLock(str(tmp_path), stale_age=1.0)
    assert lock.acquire(timeout=5.0)
    lock.release()

    # live holder: a second contender times out instead of stealing
    holder = DirectoryLock(str(tmp_path), stale_age=30.0)
    assert holder.acquire(timeout=1.0)
    try:
        thief = DirectoryLock(str(tmp_path), stale_age=30.0)
        with pytest.raises(LockTimeout):
            thief.acquire(timeout=0.3)
        assert thief.acquire(timeout=0) is False  # nonblocking miss
    finally:
        holder.release()
    # released: immediately acquirable again
    assert DirectoryLock(str(tmp_path)).acquire(timeout=1.0)


def test_directory_lock_not_shared_across_threads(tmp_path):
    """A sibling thread holding the SAME DirectoryLock instance is
    contention, not ownership: the async checkpoint writer must never
    have its lock 'acquired' and released out from under it by a
    concurrent restore on the main thread."""
    import threading

    from ring_attention_tpu.utils.resilience import DirectoryLock

    lock = DirectoryLock(str(tmp_path))
    entered = threading.Event()
    done = threading.Event()

    def writer():
        with lock.locked():
            entered.set()
            done.wait(timeout=30)

    t = threading.Thread(target=writer)
    t.start()
    try:
        assert entered.wait(timeout=10)
        with lock.locked(timeout=0) as held:
            assert held is False  # busy, not re-entrant ownership
        assert os.path.isdir(lock.path), (
            "the writer's lock dir was released by another thread"
        )
    finally:
        done.set()
        t.join()
    # after the writer released, nonblocking acquire succeeds
    with lock.locked(timeout=0) as held:
        assert held is True


def test_directory_lock_pidless_debris_taken_over_by_age(tmp_path):
    """A holder killed between mkdir and the pid stamp leaves a pid-less
    lock dir; past stale_age that is debris, not a writer — it must not
    block the directory forever."""
    from ring_attention_tpu.utils.resilience import DirectoryLock

    lock_dir = os.path.join(str(tmp_path), ".ckpt.lock")
    os.makedirs(lock_dir)  # no pid file inside
    old = time.time() - 60
    os.utime(lock_dir, (old, old))
    lock = DirectoryLock(str(tmp_path), stale_age=1.0)
    assert lock.acquire(timeout=5.0)
    lock.release()


def test_restore_recovers_old_backup_despite_crashed_lock_holder(tmp_path):
    """The worst crash window: the writer died between rename-aside and
    rename-into-place WHILE HOLDING the directory lock.  Restore must
    still take the stale lock over (pid-dead + stale_age), run the
    sweep, recover the .old backup — never cold-start over it."""
    mgr = CheckpointManager(tmp_path, keep=3, lock_stale_age=0.5)
    mgr.save(7, _make_state(3.0))
    live = os.path.join(str(tmp_path), "step_00000007")
    os.replace(live, live + ".old")  # crash state: only the backup left
    lock_dir = os.path.join(str(tmp_path), ".ckpt.lock")
    os.makedirs(lock_dir)  # ...and the dead writer still "holds" the lock
    with open(os.path.join(lock_dir, "pid"), "w") as f:
        f.write("999999999")
    old = time.time() - 60
    os.utime(lock_dir, (old, old))
    restored = CheckpointManager(tmp_path, lock_stale_age=0.5).restore(
        _make_state()
    )
    assert restored is not None and restored[1] == 7
    np.testing.assert_array_equal(
        np.asarray(restored[0]["params"]["w"]),
        np.asarray(_make_state(3.0)["params"]["w"]),
    )


def test_checkpoint_explicit_corrupt_step_raises_not_cold_start(tmp_path):
    """restore(step=N) on a corrupt step must raise, not warn-and-return
    None: None reads as 'cold start' and would silently reinitialize
    over the history the operator explicitly named."""
    from ring_attention_tpu.utils.checkpoint import CheckpointCorruptError

    mgr = CheckpointManager(tmp_path, keep=3)
    mgr.save(1, _make_state(1.0))
    mgr.save(2, _make_state(2.0))
    npz = os.path.join(str(tmp_path), "step_00000002", "arrays.npz")
    with open(npz, "r+b") as f:
        f.truncate(os.path.getsize(npz) // 2)
    with pytest.raises(CheckpointCorruptError):
        mgr.restore(_make_state(), step=2)
    # without step=, the documented fallback still works
    with pytest.warns(UserWarning, match="corrupt"):
        restored = mgr.restore(_make_state())
    assert restored is not None and restored[1] == 1
