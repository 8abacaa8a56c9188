"""Training-step composition: gradient accumulation + optimizer sharding.

The reference leaves the training loop to the user (its ``assert.py`` test
driver wraps models in DDP and calls ``loss.backward()`` by hand,
ref ``assert.py:97-137``); at long-context scale the loop itself becomes
framework territory — a quarter-million-token batch rarely fits activation
memory at the global batch size the optimizer wants, and Adam moments for
a replicated model are the next thing to blow HBM after activations.

Two composable pieces, both pure functions over pytrees so they nest
inside ``jit``/``shard_map`` like everything else here:

- :func:`make_train_step` — one optimizer step over ``accum_steps``
  microbatches, grads averaged in f32 via a ``lax.scan`` (sequential
  activation peaks, one weight update).
- :func:`shard_optimizer_state` — ZeRO-1-style: spread optimizer-moment
  arrays across a mesh axis with ``with_sharding_constraint`` (parameters
  stay replicated; XLA inserts the gather around the update).
"""

from __future__ import annotations

from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp
import optax
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


class StepStats(NamedTuple):
    """Per-step resilience telemetry carried through the guarded step.

    ``step_ok`` — whether THIS step's update was applied (False: non-finite
    loss or gradients were detected and the optimizer update was skipped).
    ``skipped`` — running count of skipped steps since
    :func:`init_step_stats`; a handful per multi-hour run is survivable
    noise, a growing streak means the run has diverged and should stop.
    """

    step_ok: jax.Array  # bool scalar
    skipped: jax.Array  # int32 scalar


def init_step_stats() -> StepStats:
    return StepStats(
        step_ok=jnp.asarray(True), skipped=jnp.asarray(0, jnp.int32)
    )


def make_train_step(
    loss_fn: Callable[..., jax.Array],
    optimizer: Any,
    *,
    accum_steps: int = 1,
    skip_nonfinite: bool = False,
    clip_grad_norm: float | None = None,
    jit_donate: bool = False,
    collect_metrics: bool = False,
    offload_opt_state: bool = False,
    shard_opt_state: bool = False,
    shard_mesh: Mesh | None = None,
    on_step_end: Callable[..., None] | None = None,
) -> Callable:
    """Build ``step(params, opt_state, *batch) -> (params, opt_state, loss)``.

    ``loss_fn(params, *microbatch)`` must return a scalar.  Each array in
    ``batch`` is split along its leading axis into ``accum_steps`` equal
    microbatches; gradients are accumulated in float32 and averaged, then
    applied in ONE optimizer update — the activation-memory peak is one
    microbatch's, the optimizer sees the full-batch gradient.  With
    ``accum_steps=1`` this is a plain fused value-and-grad step.

    The returned step is jit-compatible and mesh-agnostic: microbatching
    slices the leading (batch) axis only, so data/sequence shardings on
    the non-leading axes pass through untouched.

    Resilience options (``utils/resilience.py`` is the companion test
    harness; see ``docs/resilience.md``):

    - ``clip_grad_norm`` — clip the (full-batch) gradient to this global
      L2 norm before the update, the standard guard against loss spikes.
    - ``skip_nonfinite=True`` — the guarded step: when the loss or any
      gradient is non-finite the optimizer update is SKIPPED inside the
      jitted step (params and optimizer state pass through bit-identical)
      instead of corrupting the parameters; one poisoned batch then costs
      one step, not the run.  The step signature changes to
      ``step(params, opt_state, stats, *batch) ->
      (params, opt_state, stats, loss)`` where ``stats`` is a
      :class:`StepStats` carry seeded by :func:`init_step_stats` —
      ``stats.step_ok`` reports this step, ``stats.skipped`` counts all
      skips.  The returned loss is NOT masked on a skipped step, so logs
      show the offending value.
    - ``jit_donate=True`` — return the step already jit-compiled with
      ``(params, opt_state)`` donated (``utils/compat.py jit``): XLA
      reuses their buffers for the updated state instead of
      double-allocating — at long context the Adam moments are the next
      HBM cliff after activations.  Callers jitting by hand should pass
      ``donate_argnums=(0, 1)`` themselves.
    - ``offload_opt_state=True`` — opt-in host offload of the optimizer
      state (``docs/memory.md``): the updated state is transferred into
      the backend's host memory space (``pinned_host``) inside the step,
      so the Adam moments — 2 model-sized f32 buffers — stop occupying
      HBM between steps.  Seed the loop by placing the initial state
      there too: ``opt_state = compat.host_device_put(opt.init(params))``.
      Placement preserves each leaf's sharding (a ZeRO-1 sharded state
      stays sharded on host).  On a backend without an addressable host
      space the transfer is the identity and the step is unchanged; the
      placement is auditable via ``analysis.recompile.audit_host_offload``
      and ``tools/check_contracts.py --memory``.
    - ``collect_metrics=True`` — the instrumented step
      (``utils/telemetry.py``): the signature becomes
      ``step(params, opt_state, metrics, *batch) ->
      (params, opt_state, metrics, loss)`` where ``metrics`` is a
      :class:`~.telemetry.TrainMetrics` carry seeded by
      :func:`~.telemetry.init_train_metrics` holding this step's loss and
      pre-clip global gradient norm plus running skipped/nonfinite
      counters.  Composes with ``skip_nonfinite`` (the metrics carry then
      *replaces* the ``StepStats`` argument — it is a superset).  Every
      metric derives from values the step already computes, so
      instrumentation adds no collectives to the compiled program
      (pinned by ``tests/test_telemetry.py``).
    - ``shard_opt_state=True`` — ZeRO-1 optimizer-state sharding
      (``shard_mesh`` required): every eligible opt-state leaf gets a
      data-axis ``with_sharding_constraint`` inside the step (both tiers
      — ``("dcn_data", "data")`` — on a hierarchical mesh), via
      :func:`shard_optimizer_state`.  Adam's two model-sized f32 moment
      buffers then cost ``1/data_world`` HBM per chip; gradients and
      parameters stay replicated, XLA inserts the gather around the
      update.  Seed the loop the same way::

          opt_state = shard_optimizer_state(opt.init(params), mesh)

      Composes with ``offload_opt_state`` (constrain FIRST, then park on
      host — a sharded state stays sharded in host memory) and with the
      elastic checkpoint manager (each process saves only its shard
      group of the now-sharded moments; restore re-scatters).  Audited
      by ``analysis/recompile.audit_donation`` / ``audit_host_offload``
      and pinned in ``tests/test_elastic.py``.
    - ``on_step_end`` — a HOST callback ``on_step_end(outputs)`` invoked
      after every step call with the step's full output tuple.  This is
      the hook the elastic runtime hangs off (``elastic/``): the async
      checkpointer snapshots state from it and ``PreemptionGuard`` checks
      its drain flag — neither belongs inside the compiled program.  The
      callback runs OUTSIDE the jitted step, after dispatch: the output
      arrays are handed over un-fetched, so a callback that only inspects
      Python state adds no device sync (one that reads values forces the
      step to finish, same as any host read).  Unset, this is a strict
      no-op: the returned step is the exact same callable, not a wrapper.
      When set, the wrapper exposes the undecorated step as
      ``step.__wrapped__`` — ``tests/test_elastic.py`` pins that its
      compiled program carries the identical collective sequence to the
      hookless step (the hook adds zero collectives by construction).
      Do NOT wrap the hooked step in an outer ``jax.jit`` (the hook
      would be traced away); the wrapper detects tracing and raises —
      jit ``step.__wrapped__`` or pass ``jit_donate=True`` instead.
    """
    if accum_steps < 1:
        raise ValueError(f"make_train_step: accum_steps must be >= 1, got {accum_steps}")
    if clip_grad_norm is not None and clip_grad_norm <= 0:
        raise ValueError(
            f"make_train_step: clip_grad_norm must be > 0, got {clip_grad_norm}"
        )
    if shard_opt_state and shard_mesh is None:
        raise ValueError(
            "make_train_step: shard_opt_state=True needs shard_mesh= "
            "(the mesh whose data axis the optimizer state shards over)"
        )
    grad_fn = jax.value_and_grad(loss_fn)

    def compute_update(params, opt_state, *batch):
        if offload_opt_state:
            # the state arrives host-resident; arithmetic needs every
            # operand in one memory space, so fetch it for the update
            from . import compat

            opt_state = compat.device_memory_put(opt_state)
        if accum_steps == 1:
            loss, grads = grad_fn(params, *batch)
        else:
            def split(x):
                n = x.shape[0]
                if n % accum_steps:
                    raise ValueError(
                        f"make_train_step: leading batch dim {n} not "
                        f"divisible by accum_steps={accum_steps}"
                    )
                return x.reshape(accum_steps, n // accum_steps, *x.shape[1:])

            micro = jax.tree.map(split, batch)
            zeros = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params
            )

            def body(carry, mb):
                acc, loss_sum = carry
                loss, grads = grad_fn(params, *mb)
                with jax.named_scope("train/accumulate"):
                    acc = jax.tree.map(
                        lambda a, g: a + g.astype(jnp.float32), acc, grads
                    )
                return (acc, loss_sum + loss), None

            (gsum, loss_sum), _ = lax.scan(
                body, (zeros, jnp.float32(0.0)), micro
            )
            inv = 1.0 / accum_steps
            with jax.named_scope("train/accumulate"):
                grads = jax.tree.map(
                    lambda g, p: (g * inv).astype(p.dtype), gsum, params
                )
            loss = loss_sum * inv

        # one global norm serves clipping, the non-finite guard, AND the
        # metrics carry: any NaN/inf in any leaf propagates into it, and
        # clipping by a finite factor keeps non-finite values non-finite,
        # so checking the pre-clip norm is equivalent to post-clip.
        # (train/* scopes are HLO metadata only: they put the step's own
        # ops on the profiler's layer table, utils/profiling.py STAGES)
        with jax.named_scope("train/clip"):
            gnorm = (
                optax.global_norm(grads)
                if (clip_grad_norm is not None or skip_nonfinite
                    or collect_metrics)
                else None
            )
            if clip_grad_norm is not None:
                clip = jnp.minimum(
                    1.0, clip_grad_norm / jnp.maximum(gnorm, 1e-12)
                )
                grads = jax.tree.map(
                    lambda g: (g * clip).astype(g.dtype), grads
                )

        with jax.named_scope("train/optimizer"):
            updates, new_opt_state = optimizer.update(
                grads, opt_state, params)
            new_params = optax.apply_updates(params, updates)
        return new_params, new_opt_state, loss, gnorm

    def place_opt(opt_state):
        # placement runs LAST in the step (after any skip-guard select):
        # ZeRO-1 data-axis constraint first (per-program, so the
        # partitioner keeps the moments sharded), then the host offload
        # — a sharded state stays sharded in host memory; both are
        # no-ops when their knob is off
        if shard_opt_state:
            from ..parallel.mesh import data_partition

            opt_state = shard_optimizer_state(
                opt_state, shard_mesh, axis=data_partition(shard_mesh)
            )
        if not offload_opt_state:
            return opt_state
        from . import compat

        return compat.host_device_put(opt_state)

    def finish(step):
        if jit_donate:
            from . import compat

            step = compat.jit(step, donate_argnums=(0, 1))
        if on_step_end is None:
            return step  # strict no-op: the very same callable
        import functools

        @functools.wraps(step)
        def stepped(*args, **kwargs):
            out = step(*args, **kwargs)
            # a host hook baked into a trace would fire ONCE at compile
            # time and never again — the drain check / async snapshot it
            # exists for would silently stop running.  Fail loudly
            # instead of being traced away.
            if any(isinstance(x, jax.core.Tracer)
                   for x in jax.tree_util.tree_leaves(out)):
                raise RuntimeError(
                    "make_train_step(on_step_end=...): the hooked step "
                    "was traced by an outer jax.jit, which would "
                    "silently drop the host hook. jit the inner step "
                    "instead (step.__wrapped__), or build with "
                    "jit_donate=True so make_train_step jits it for you."
                )
            on_step_end(out)
            return out

        stepped.__wrapped__ = step  # the lowerable inner step (HLO pin)
        return stepped

    if not skip_nonfinite and not collect_metrics:

        def step(params, opt_state, *batch):
            new_params, new_opt_state, loss, _ = compute_update(
                params, opt_state, *batch
            )
            return new_params, place_opt(new_opt_state), loss

        return finish(step)

    def apply_or_skip(ok, new_params, new_opt_state, params, opt_state):
        if not skip_nonfinite:
            return new_params, new_opt_state

        def keep_old(new, old):
            with jax.named_scope("train/clip"):
                return jax.tree.map(
                    lambda n, o: jnp.where(ok, n, o), new, old
                )

        # jnp.where with the old value on the skip branch is bit-identical
        # (no arithmetic touches the kept params) — the property the
        # fault-injection suite asserts
        return (
            keep_old(new_params, params), keep_old(new_opt_state, opt_state)
        )

    if not collect_metrics:

        def guarded_step(params, opt_state, stats: StepStats, *batch):
            new_params, new_opt_state, loss, gnorm = compute_update(
                params, opt_state, *batch
            )
            # one scalar covers every gradient leaf: any NaN/inf propagates
            # into the global norm (see compute_update)
            ok = jnp.isfinite(loss) & jnp.isfinite(gnorm)
            params, opt_state = apply_or_skip(
                ok, new_params, new_opt_state, params, opt_state
            )
            stats = StepStats(
                step_ok=ok,
                skipped=stats.skipped + jnp.where(ok, 0, 1).astype(jnp.int32),
            )
            return params, place_opt(opt_state), stats, loss

        return finish(guarded_step)

    from .telemetry import TrainMetrics

    def metric_step(params, opt_state, metrics: TrainMetrics, *batch):
        new_params, new_opt_state, loss, gnorm = compute_update(
            params, opt_state, *batch
        )
        finite = jnp.isfinite(loss) & jnp.isfinite(gnorm)
        # step_ok reports whether the update was APPLIED: without the
        # guard every step applies; nonfinite still counts the poison
        ok = finite if skip_nonfinite else jnp.asarray(True)
        params, opt_state = apply_or_skip(
            finite, new_params, new_opt_state, params, opt_state
        )
        one = jnp.asarray(1, jnp.int32)
        zero = jnp.asarray(0, jnp.int32)
        metrics = TrainMetrics(
            loss=loss.astype(jnp.float32),
            grad_norm=gnorm.astype(jnp.float32),
            step_ok=ok,
            skipped=metrics.skipped
            + (jnp.where(finite, zero, one) if skip_nonfinite else zero),
            nonfinite=metrics.nonfinite + jnp.where(finite, zero, one),
        )
        return params, place_opt(opt_state), metrics, loss

    return finish(metric_step)


def shard_optimizer_state(
    opt_state: Any, mesh: Mesh, axis: str | tuple = "data"
) -> Any:
    """ZeRO-1-style optimizer-state sharding over one or more mesh axes.

    Every float array in ``opt_state`` whose leading dimension divides by
    the axis size gets ``with_sharding_constraint(P(axis))`` on that
    dimension; everything else (step counters, odd shapes) stays
    replicated.  ``axis`` may be a tuple of mesh axis names — on a
    hierarchical mesh pass ``("dcn_data", "data")`` (or just
    :func:`~ring_attention_tpu.parallel.mesh.data_partition`) so the
    moments spread over the FULL data-parallel world, both tiers.  Apply
    once to the freshly-initialized state AND inside the jitted step to
    the updated state (constraints guide the partitioner per-program) —
    or build the step with ``make_train_step(shard_opt_state=True,
    shard_mesh=mesh)``, which does the in-step half for you::

        opt_state = shard_optimizer_state(opt.init(params), mesh)

        @jax.jit
        def step(params, opt_state, batch):
            ...
            opt_state = shard_optimizer_state(opt_state, mesh)
            return params, opt_state, loss

    Adam on a replicated model keeps 2 extra model-sized f32 buffers; over
    a ``data=8`` axis this drops per-chip moment memory 8x while gradients
    and parameters stay replicated (the reference has no equivalent — its
    DDP replicates optimizer state per rank).
    """
    axes = axis if isinstance(axis, (tuple, list)) else (axis,)
    size = 1
    for a in axes:
        size *= mesh.shape[a]
    entry = tuple(axes) if len(axes) > 1 else axes[0]

    def constrain(x):
        if (
            isinstance(x, jax.Array)
            and x.ndim >= 1
            and jnp.issubdtype(x.dtype, jnp.floating)
            and x.shape[0] % size == 0
            and x.shape[0] > 0
        ):
            spec = P(entry, *([None] * (x.ndim - 1)))
            return lax.with_sharding_constraint(
                x, NamedSharding(mesh, spec)
            )
        return x

    return jax.tree.map(constrain, opt_state)
