"""Thin seams over the JAX APIs this package leans on.

One spelling per API for the one installation there is (``pyproject.toml``
pins ``jax>=0.9``; the sandbox and the chip machine both run 0.9.0):
``jax.typeof``, ``jax.shard_map``, ``lax.pcast``, ``lax.axis_size``,
``pltpu.CompilerParams``, ``jax.profiler.trace``.  The wrappers remain so
call sites (and lint RA001) keep one import; inlining them away is ROADMAP
D5.  The host-memory helpers are the only ones that still probe: whether
a backend exposes a host memory space is a property of the machine, not
of the JAX version.
"""

from __future__ import annotations

from typing import Any

import jax
from jax import lax


def typeof(x: Any):
    """``jax.typeof`` — shape/dtype plus the shard_map varying-axes
    (``vma``) metadata the Pallas launchers unify across operands."""
    return jax.typeof(x)


def pcast(x: Any, axes, to: str = "varying"):
    """``lax.pcast``: mark ``x`` as varying over ``axes``."""
    return lax.pcast(x, axes, to=to)


def shard_map(f, *, mesh, in_specs, out_specs, check_vma: bool = True):
    """``jax.shard_map`` (``check_vma`` gates the per-output
    replication/varying checker)."""
    return jax.shard_map(
        f, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=check_vma,
    )


def jit(fn, *, donate_argnums=(), **kwargs):
    """``jax.jit`` with buffer donation.

    Donation is an aliasing hint — XLA reuses the donated input buffers
    for outputs instead of double-allocating (the train loop's
    ``(params, opt_state)`` are exactly the buffers whose copies would
    otherwise double peak optimizer-state memory)."""
    return jax.jit(fn, donate_argnums=donate_argnums, **kwargs)


def profiler_trace(logdir: str):
    """``jax.profiler.trace(logdir)`` — callers go through
    ``utils/profiling.trace`` (docs/observability.md §Observatory)."""
    return jax.profiler.trace(logdir)


def tpu_compiler_params(**kwargs):
    """``pltpu.CompilerParams`` for a ``pallas_call``."""
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.CompilerParams(**kwargs)


def host_memory_kind() -> str | None:
    """The host memory space in-graph placement can target
    ("pinned_host"), or None when the backend has none.

    jax 0.9.0's CPU backend LISTS ``pinned_host`` among its memory spaces,
    but XLA:CPU compiles an in-graph host placement to device memory (the
    compiled output's ``memory_kind`` is ``device``), so a host-seeded
    state would mismatch the step that consumes it.  Offload on the CPU
    is therefore the identity, as it always was: this returns None there.
    """
    if jax.default_backend() == "cpu":
        return None
    kinds = {
        m.kind
        for d in jax.local_devices()
        for m in d.addressable_memories()
    }
    return "pinned_host" if "pinned_host" in kinds else None


def host_device_put(tree):
    """Move every array leaf of ``tree`` into host memory, PRESERVING its
    sharding; the identity when the backend has no host memory space.

    Placement keeps each leaf's partitioning — a ZeRO-1 sharded optimizer
    state stays sharded on host, never silently re-replicated N-x:

    - concrete leaves (seeding the loop outside jit) move via their own
      ``sharding.with_memory_kind``;
    - traced leaves (inside the step) move via ``jax.memory.Space.Host``,
      which changes only the memory space and lets the partitioner keep
      the layout it chose.

    Used by ``make_train_step(offload_opt_state=True)`` for the Adam
    moments — the next HBM cliff after activations (docs/memory.md).
    """
    kind = host_memory_kind()
    if kind is None:
        return tree

    def place(x):
        if not isinstance(x, jax.Array):
            return x
        if isinstance(x, jax.core.Tracer):
            return jax.device_put(x, jax.memory.Space.Host)
        return jax.device_put(x, x.sharding.with_memory_kind(kind))

    return jax.tree.map(place, tree)


def device_memory_put(tree):
    """Inverse of :func:`host_device_put` inside a traced step: every
    array leaf back in device memory (the identity for leaves already
    there, and on a backend without a host memory space)."""
    if host_memory_kind() is None:
        return tree
    return jax.tree.map(
        lambda x: jax.device_put(x, jax.memory.Space.Device)
        if isinstance(x, jax.Array) else x,
        tree,
    )


def bound_axis_names():
    """Every mesh axis name bound at this point of the trace, in mesh
    binding order — or None when the axis environment is unreadable.

    The fused remote tier needs MESH-coordinate device ids (a coordinate
    per mesh axis, not just the ring axis: a LOGICAL id built from the
    ring coordinate alone addresses the wrong device on any multi-axis
    mesh).  ``get_axis_env().axis_sizes`` is an insertion-ordered dict of
    bound axes; it has no public home, and a None here just means "no
    coordinate table", which callers treat as "take the gathered-KV local
    tier instead"."""
    try:
        from jax._src.core import get_axis_env

        return tuple(get_axis_env().axis_sizes.keys())
    except (ImportError, AttributeError):
        return None


def axis_size(axis_name):
    """``lax.axis_size``: a static Python int inside ``shard_map``, so
    callers can keep using it for loop bounds and shape arithmetic."""
    return lax.axis_size(axis_name)
