"""Compile-cache placement and wall-clock timing helpers.

JAX dispatch is asynchronous: a timing loop must end in a
synchronisation the device cannot skip.  ``timed_chained`` synchronises
by fetching a scalar that depends on every iteration of one chained
executable, and subtracts the separately measured fetch round trip.
``chip_smoke.py`` compares ``jax.block_until_ready`` against a value
fetch on the chip it runs on; its output records what that chip does.
"""

from __future__ import annotations

import os
from typing import Callable, Sequence

from .tracing import perf_counter as _perf_counter

__all__ = ["enable_compile_cache", "fetch_rtt", "timed_chained"]

CACHE_DIR_ENV = "JAX_COMPILATION_CACHE_DIR"


def enable_compile_cache(default_dir: str | None = None) -> str:
    """Turn on jax's persistent executable cache; returns its directory.

    The cache is placed from OUTSIDE: where ``JAX_COMPILATION_CACHE_DIR``
    is set, jax reads it and no directory is set in code.  Where it is
    not, the cache lives at ``default_dir`` (default: ``.jax_cache_tpu/``
    in the checkout) — a fixed path, because the path is part of the
    cache key and a directory that moves never hits.  Every entry point
    (examples, ``chip_smoke.py``, bench workers, ``tools/``, the tests)
    calls this at start, so a call's processes share one cache."""
    import jax

    cache_dir = os.environ.get(CACHE_DIR_ENV)
    if not cache_dir:
        cache_dir = default_dir or os.path.join(
            os.path.dirname(os.path.dirname(os.path.dirname(
                os.path.abspath(__file__)))),
            ".jax_cache_tpu",
        )
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.05)
    # cache every executable over the time threshold regardless of size
    # (the hop-sequence/train programs are exactly the large ones)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return cache_dir


def fetch_rtt(samples: int = 3) -> float:
    """Seconds for one host<->device scalar fetch (min over ``samples``)."""
    import jax.numpy as jnp

    from . import compat

    f = compat.jit(lambda x: x + 1)
    _ = float(f(jnp.float32(0)))  # compile outside the timed region
    best = float("inf")
    for i in range(samples):
        t0 = _perf_counter()
        _ = float(f(jnp.float32(i)))
        best = min(best, _perf_counter() - t0)
    return best


def timed_chained(
    chained_fn: Callable[..., object],
    args: Sequence[object],
    iters: int,
    *,
    return_value: bool = False,
) -> tuple[float, float] | tuple[float, float, float]:
    """(compile_seconds, seconds_per_iteration[, value]) for a chained run.

    ``chained_fn`` must be a jitted callable that runs ``iters``
    data-dependent iterations on device and returns a scalar (convertible
    with ``float``); with ``return_value=True`` that scalar is returned
    too.  Raises ``RuntimeError`` if the measured time is not above the
    fetch round trip — a nonsense number is worse than no number.

    Kept for the benchmark PR (ROADMAP S0) to judge against a plain
    ``block_until_ready`` loop; see ``chip_smoke.py``'s sync line for
    what was measured on the chip.
    """
    t0 = _perf_counter()
    _ = float(chained_fn(*args))
    first_total = _perf_counter() - t0
    rtt = fetch_rtt()
    t0 = _perf_counter()
    value = float(chained_fn(*args))
    total = _perf_counter() - t0
    if total <= rtt:
        raise RuntimeError(
            f"measurement ({total * 1e3:.1f} ms) not above fetch RTT "
            f"({rtt * 1e3:.1f} ms); increase iters"
        )
    # the first call is compile + one full execution of the chain
    compile_s = max(first_total - total, 0.0)
    per_iter = (total - rtt) / iters
    if return_value:
        return compile_s, per_iter, value
    return compile_s, per_iter
