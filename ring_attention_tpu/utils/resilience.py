"""Resilience layer: retries, fault injection, and kernel degradation.

Ring attention's value is multi-hour runs over million-token contexts on
many chips — exactly the regime where a single NaN step, preempted host,
or wedged device kills hours of work.  Three pieces, used across
``utils/train.py`` (guarded step), ``utils/checkpoint.py``
(preemption-safe saves), ``ops``/``models`` (kernel fallback) and the
elastic runtime:

- :func:`with_retries` — timeout + exponential-backoff wrapper for
  callables that can hang (a rendezvous with a dead peer) or fail
  transiently (a coordinator that is still starting).
- :class:`FaultInjector` / :func:`inject` — the test harness's hook for
  forcing the failures the resilience machinery exists to survive
  (NaN grads, truncated checkpoints, Pallas compile errors, hung
  probes), so every degradation path is exercised on the CPU mesh.
- :class:`DegradationRecord` + :func:`pallas_available` /
  :func:`resolve_attention_impl` — graceful kernel degradation:
  ``impl="auto"`` callers get the Pallas path when it compiles and a
  one-shot-warned, queryable fallback to the XLA path when it doesn't.

Everything here is host-side Python (no jax transforms are applied to
this module's code), so it composes with jit-compiled callers by running
at trace/dispatch time.
"""

from __future__ import annotations

import os
import shutil
import threading
import time
import warnings
from contextlib import contextmanager
from dataclasses import dataclass, field
from typing import Any, Callable, Iterator, Sequence

from . import tracing


# ----------------------------------------------------------------------
# Fault injection
# ----------------------------------------------------------------------


class InjectedFault(RuntimeError):
    """Raised by :meth:`FaultInjector.check` at an armed injection point."""


class FaultInjector:
    """Process-global registry of armed faults.

    Production code calls :meth:`check`/:meth:`armed` at its injection
    points; tests arm faults with :func:`inject` (a context manager, so a
    failing assertion can never leave a fault armed for the next test).
    Armed faults may carry a payload (:meth:`value`) — e.g. the step index
    at which to poison gradients.
    """

    def __init__(self) -> None:
        self._faults: dict[str, Any] = {}
        self._lock = threading.Lock()

    def arm(self, name: str, value: Any = True) -> None:
        with self._lock:
            self._faults[name] = value

    def disarm(self, name: str) -> None:
        with self._lock:
            self._faults.pop(name, None)

    def armed(self, name: str) -> bool:
        with self._lock:
            return name in self._faults

    def value(self, name: str, default: Any = None) -> Any:
        with self._lock:
            return self._faults.get(name, default)

    def check(self, name: str) -> None:
        """Raise :class:`InjectedFault` when ``name`` is armed (no-op
        otherwise) — the one-line injection point for failure paths."""
        if self.armed(name):
            raise InjectedFault(f"injected fault: {name}")

    def clear(self) -> None:
        with self._lock:
            self._faults.clear()


_INJECTOR = FaultInjector()


def get_injector() -> FaultInjector:
    return _INJECTOR


@contextmanager
def inject(name: str, value: Any = True) -> Iterator[FaultInjector]:
    """Arm fault ``name`` for the duration of the block (always disarmed
    on exit, even when the block raises).

    Exit drains pending JAX runtime effects first: jitted computations
    dispatch asynchronously, so a ``pure_callback`` injection point
    (:func:`nan_tap`) inside a step launched in the block could otherwise
    execute AFTER the block disarmed the fault — the injection would
    silently miss.  ``jax.effects_barrier()`` guarantees every callback
    from inside the block observed the armed state.
    """
    _INJECTOR.arm(name, value)
    try:
        yield _INJECTOR
    finally:
        try:
            import jax

            jax.effects_barrier()
        except Exception:  # noqa: BLE001 — jax absent/old: nothing to drain
            pass
        _INJECTOR.disarm(name)


# ----------------------------------------------------------------------
# Retry / timeout / backoff
# ----------------------------------------------------------------------


class RetryTimeout(TimeoutError):
    """A single attempt exceeded its timeout budget."""


class RetryError(RuntimeError):
    """All attempts failed; ``last`` holds the final attempt's exception."""

    def __init__(self, message: str, last: BaseException | None = None):
        super().__init__(message)
        self.last = last


# Callbacks fired when a with_retries ladder exhausts all attempts —
# the flight-recorder hook (telemetry.FlightRecorder.install): a probe or
# save that died after its last retry dumps the run's recent trajectory
# alongside the RetryError, instead of surfacing as a bare exception.
# Kept as a module-level registry (like DegradationRecord's listeners) so
# this module stays stdlib-only and import-free of telemetry.
_failure_listeners: list[Callable[[str, str], None]] = []
_failure_lock = threading.Lock()


def add_failure_listener(callback: Callable[[str, str], None]) -> None:
    """Register ``callback(where, error)`` to run when a
    :func:`with_retries` call exhausts its attempts (idempotent per
    callback).  Callback failures are swallowed — diagnostics must never
    mask the retried operation's own error.  Pair with
    :func:`remove_failure_listener` for listeners whose lifetime is
    shorter than the process (``FlightRecorder.uninstall`` does)."""
    with _failure_lock:
        if callback not in _failure_listeners:
            _failure_listeners.append(callback)


def remove_failure_listener(callback: Callable[[str, str], None]) -> None:
    """Unregister a failure listener (no-op when absent)."""
    with _failure_lock:
        if callback in _failure_listeners:
            _failure_listeners.remove(callback)


def _notify_failure(where: str, error: BaseException | None) -> None:
    text = (
        f"{type(error).__name__}: {error}" if error is not None else "unknown"
    )
    with _failure_lock:
        listeners = tuple(_failure_listeners)
    for cb in listeners:
        try:
            cb(where, text)
        except Exception:  # noqa: BLE001 — see add_failure_listener
            pass


def _call_with_timeout(fn: Callable[[], Any], timeout: float) -> Any:
    """Run ``fn()`` with a hard wall-clock budget.

    The callable runs in a daemon thread; on timeout the thread is
    abandoned (Python offers no safe cross-thread kill) and
    :class:`RetryTimeout` is raised.  Callables that own external
    resources should therefore enforce their own inner timeout too
    (e.g. ``subprocess.run(timeout=...)`` kills the child) — this wrapper
    is the backstop for the observed wedge mode where even the probe's
    bookkeeping hangs.
    """
    result: list[Any] = []
    error: list[BaseException] = []

    def run() -> None:
        try:
            result.append(fn())
        except BaseException as e:  # noqa: BLE001 — re-raised on the caller
            error.append(e)

    t = threading.Thread(target=run, daemon=True)
    t.start()
    t.join(timeout)
    if t.is_alive():
        raise RetryTimeout(f"attempt still running after {timeout:.1f}s")
    if error:
        raise error[0]
    return result[0]


def with_retries(
    fn: Callable[[], Any],
    *,
    timeout: float | None = None,
    backoff: float = 1.0,
    max_attempts: int = 3,
    retry_on: tuple[type[BaseException], ...] = (Exception,),
    sleep: Callable[[float], None] = time.sleep,
    on_retry: Callable[[int, BaseException], None] | None = None,
) -> Any:
    """Call ``fn()`` with per-attempt ``timeout`` and exponential backoff.

    Attempt ``i`` (0-based) that fails with ``retry_on`` (or times out)
    is followed by ``sleep(backoff * 2**i)`` before the next attempt;
    after ``max_attempts`` failures a :class:`RetryError` carrying the
    last exception is raised.  ``sleep`` and ``on_retry`` are injectable
    for tests (and for callers that want to log each retry).

    ``timeout=None`` disables the wall-clock guard (pure retry/backoff);
    otherwise each attempt gets its own ``timeout`` seconds — see
    :func:`_call_with_timeout` for the abandonment caveat on hung
    callables.
    """
    if max_attempts < 1:
        raise ValueError(f"with_retries: max_attempts must be >= 1, got {max_attempts}")
    if backoff < 0:
        raise ValueError(f"with_retries: backoff must be >= 0, got {backoff}")
    last: BaseException | None = None
    for attempt in range(max_attempts):
        try:
            if timeout is None:
                return fn()
            return _call_with_timeout(fn, timeout)
        except (RetryTimeout, *retry_on) as e:  # noqa: B030
            last = e
            if on_retry is not None:
                on_retry(attempt, e)
            if attempt + 1 < max_attempts:
                sleep(backoff * (2**attempt))
    _notify_failure(getattr(fn, "__name__", None) or "callable", last)
    raise RetryError(
        f"with_retries: all {max_attempts} attempts failed "
        f"(last: {type(last).__name__}: {last})",
        last,
    )


# ----------------------------------------------------------------------
# Cross-process directory lock (watcher protocol, in-library form)
# ----------------------------------------------------------------------


def pid_alive(pid: int) -> bool:
    """Best-effort liveness check for a local pid (signal 0 probe).

    ``EPERM`` counts as alive (the process exists, we just can't signal
    it); any other failure counts as dead.  This is the takeover predicate
    of :class:`DirectoryLock`, shared so checkpoint managers apply the
    same rule.
    """
    if pid <= 0:
        return False
    try:
        os.kill(pid, 0)
    except PermissionError:
        return True
    except OSError:
        return False
    return True


class LockTimeout(TimeoutError):
    """A :class:`DirectoryLock` could not be acquired within its budget."""


class DirectoryLock:
    """Atomic cross-process lock on a directory, with stale takeover.

    Acquisition is ``os.mkdir`` of a lock
    directory (atomic-exclusive on every POSIX filesystem) followed by a
    pid stamp inside it, so a held lock always names its holder.  A
    SIGKILLed holder (no cleanup ran) must not block the directory
    forever: a contender may take over only when the pid file exists,
    the pid is **dead**, AND the lock is at least ``stale_age`` seconds
    old — and the takeover renames the stale lock aside first, so of N
    concurrent contenders exactly one wins the rename and the losers
    retry cleanly (a plain ``rmtree`` could delete the winner's freshly
    acquired lock).

    Used by ``utils/checkpoint.py`` and ``elastic/checkpoint.py`` so two
    managers on one directory serialize their save/prune/sweep sections
    instead of interleaving (one manager's retention pass deleting the
    step another just renamed into place).
    """

    def __init__(
        self,
        directory: str | os.PathLike,
        name: str = ".ckpt.lock",
        *,
        stale_age: float = 30.0,
        poll: float = 0.05,
    ) -> None:
        self.path = os.path.join(os.fspath(directory), name)
        self.stale_age = stale_age
        self.poll = poll
        self._held = False
        # within-process serialization: the filesystem lock is per
        # PROCESS (one pid stamp), so two threads of one process — the
        # async checkpoint writer and a concurrent restore's sweep —
        # must contend here first; without this, thread B would see
        # _held, "acquire" a lock thread A holds, and release it out
        # from under A's critical section
        self._tlock = threading.Lock()

    def _try_acquire(self) -> bool:
        try:
            os.mkdir(self.path)
        except FileExistsError:
            return False
        except FileNotFoundError:
            os.makedirs(os.path.dirname(self.path), exist_ok=True)
            return self._try_acquire()
        with open(os.path.join(self.path, "pid"), "w") as f:
            f.write(str(os.getpid()))
        self._held = True
        return True

    def _takeover_if_stale(self) -> None:
        try:
            with open(os.path.join(self.path, "pid")) as f:
                holder = int(f.read().strip())
        except (OSError, ValueError):
            # no/garbled pid stamp: a holder that died between mkdir and
            # the stamp write (SIGKILL, ENOSPC).  The age rule below is
            # the only takeover predicate left — a healthy acquirer
            # stamps within milliseconds, so an unstamped lock past
            # stale_age is debris, not a writer
            holder = None
        try:
            age = time.time() - os.stat(self.path).st_mtime  # ra: allow(RA014 mtime age against the filesystem wall clock, not an emitted timestamp)
        except OSError:
            return  # lock vanished between checks: next acquire retries
        if (holder is not None and pid_alive(holder)) or age < self.stale_age:
            return
        aside = f"{self.path}.stale-{os.getpid()}"
        try:
            os.rename(self.path, aside)  # one winner among N contenders
        except OSError:
            return  # another contender won the rename; retry acquire
        shutil.rmtree(aside, ignore_errors=True)

    def acquire(self, timeout: float | None = 60.0) -> bool:
        """Acquire, blocking up to ``timeout`` seconds (None = forever;
        0 = one nonblocking attempt).  Returns True when held; raises
        :class:`LockTimeout` when the budget runs out.  NOT re-entrant:
        a thread that already holds the lock must not re-acquire it."""
        tracer = tracing.get_tracer()
        if tracer is None or not tracer.enabled:
            return self._acquire(timeout)
        # the lock-wait span IS the straggler signal: a process stuck
        # behind a dead holder shows up on the cluster timeline as one
        # long lock/acquire span (errored with LockTimeout if it loses)
        with tracer.span("lock/acquire", path=self.path,
                         timeout=timeout) as sp:
            got = self._acquire(timeout)
            sp.set(held=got)
            return got

    def _acquire(self, timeout: float | None) -> bool:
        # ONE deadline covers both waits: the in-process tlock and the
        # filesystem loop share the budget (counting it twice would let
        # acquire(600) block for 20 minutes)
        deadline = None if timeout is None else time.monotonic() + timeout  # ra: allow(RA014 deadline arithmetic; the acquire() span records the wait)
        # within-process contention first: a sibling thread holding the
        # filesystem lock is contention, not ownership
        if timeout == 0:
            if not self._tlock.acquire(blocking=False):
                return False
        elif timeout is None:
            self._tlock.acquire()
        else:
            if not self._tlock.acquire(timeout=timeout):
                raise LockTimeout(
                    f"DirectoryLock: {self.path} held by another thread "
                    f"of this process after {timeout:.1f}s"
                )
        try:
            first = True
            while True:
                if self._try_acquire():
                    return True
                self._takeover_if_stale()
                if deadline is not None and time.monotonic() >= deadline:  # ra: allow(RA014 deadline arithmetic; the acquire() span records the wait)
                    # nonblocking mode still deserves one retry AFTER the
                    # takeover: a stale lock (dead holder) must not make
                    # a timeout=0 acquire fail when the dir is free now
                    if first and self._try_acquire():
                        return True
                    if timeout == 0:
                        self._tlock.release()
                        return False
                    raise LockTimeout(
                        f"DirectoryLock: {self.path} still held after "
                        f"{timeout:.1f}s (holder pid in {self.path}/pid)"
                    )
                first = False
                time.sleep(self.poll)
        except BaseException:
            if not self._held:
                self._tlock.release()
            raise

    def release(self) -> None:
        if not self._held:
            return
        self._held = False
        shutil.rmtree(self.path, ignore_errors=True)
        self._tlock.release()

    @contextmanager
    def locked(self, timeout: float | None = 60.0) -> Iterator[bool]:
        """``with lock.locked():`` — acquire/release around a block.
        With ``timeout=0`` the block still runs when the lock is busy,
        and the yielded bool says whether it is actually held (callers
        use this for optional housekeeping: skip the sweep, never block
        a restore on another process's save)."""
        got = self.acquire(timeout)
        try:
            yield got
        finally:
            if got:
                self.release()


# ----------------------------------------------------------------------
# Graceful kernel degradation
# ----------------------------------------------------------------------


@dataclass
class DegradationEvent:
    component: str
    reason: str
    time: float = field(default_factory=time.time)


class DegradationRecord:
    """Queryable record of components that fell back to a degraded path.

    The first failure of a component emits ONE ``UserWarning`` (multi-hour
    runs must not drown their logs in per-step warnings); every failure is
    appended to :meth:`events` so operators and tests can ask exactly what
    degraded and why.
    """

    def __init__(self) -> None:
        self._events: list[DegradationEvent] = []
        self._degraded: set[str] = set()
        self._listeners: list[Callable[[str, str], None]] = []
        self._lock = threading.Lock()

    def add_listener(self, callback: Callable[[str, str], None]) -> None:
        """Register ``callback(component, reason)`` to run on every
        recorded degradation (idempotent per callback).  This is how the
        telemetry layer turns silent ``impl="auto"`` fallbacks into metric
        rows without this module importing it."""
        with self._lock:
            if callback not in self._listeners:
                self._listeners.append(callback)

    def remove_listener(self, callback: Callable[[str, str], None]) -> None:
        """Unregister a listener (no-op when absent) — for listeners
        whose lifetime is shorter than the process, e.g. a
        ``FlightRecorder`` bound to one run's directory."""
        with self._lock:
            if callback in self._listeners:
                self._listeners.remove(callback)

    def record(self, component: str, reason: BaseException | str) -> None:
        text = f"{type(reason).__name__}: {reason}" if isinstance(
            reason, BaseException
        ) else str(reason)
        with self._lock:
            first = component not in self._degraded
            self._degraded.add(component)
            self._events.append(DegradationEvent(component, text))
            listeners = tuple(self._listeners)
        for cb in listeners:
            try:
                cb(component, text)
            except Exception:  # noqa: BLE001 — telemetry must never break
                pass           # the degradation path it observes
        if first:
            warnings.warn(
                f"resilience: {component} degraded, falling back "
                f"({text}); further occurrences are recorded silently — "
                f"see ring_attention_tpu.utils.resilience.degradation.events()",
                stacklevel=3,
            )

    def is_degraded(self, component: str) -> bool:
        with self._lock:
            return component in self._degraded

    def events(self) -> Sequence[DegradationEvent]:
        with self._lock:
            return tuple(self._events)

    def reset(self) -> None:
        with self._lock:
            self._events.clear()
            self._degraded.clear()


degradation = DegradationRecord()

# component name shared by the probe, the ops dispatcher, and the models
PALLAS_COMPONENT = "pallas_flash"
# fault name the injection harness arms to force the Pallas path to fail
PALLAS_FAULT = "pallas_fail"

_pallas_probe: bool | None = None
_pallas_probe_lock = threading.Lock()


class _PallasNotApplicable(Exception):
    """The backend has no real Pallas path (non-TPU): ``auto`` resolves to
    XLA *silently* — nothing degraded, the fast path never existed here.
    Interpret-mode Pallas would "work" on CPU but is pure-Python slow;
    choosing it over the XLA flash path would be a pessimization, not a
    fallback."""


def _probe_pallas() -> None:
    """Compile-and-run a minimal real (non-interpret) Pallas flash call.

    Raises whatever the Pallas path raises on this backend — lowering
    errors, Mosaic rejections, missing plugin — which is exactly the
    signal ``impl="auto"`` needs BEFORE a caller's outer jit bakes the
    kernel choice in.  Raises :class:`_PallasNotApplicable` on non-TPU
    backends (see its docstring); the injected :data:`PALLAS_FAULT` is
    checked first so CI can exercise the degradation path anywhere.
    """
    get_injector().check(PALLAS_FAULT)
    import jax

    if jax.devices()[0].platform != "tpu":
        raise _PallasNotApplicable(
            f"backend {jax.devices()[0].platform!r} has no Mosaic path"
        )
    import jax.numpy as jnp

    from ..ops.pallas_flash import pallas_flash_attention

    q = jnp.zeros((1, 1, 128, 64), jnp.float32)
    out = pallas_flash_attention(q, q, q, causal=True, interpret=False)
    jax.block_until_ready(out)


def pallas_available(*, refresh: bool = False) -> bool:
    """True when the real Pallas kernel path works on this backend.

    The probe runs once per process (cached).  A non-TPU backend returns
    False silently (not a degradation — see :class:`_PallasNotApplicable`);
    a TPU whose kernels fail records a :data:`PALLAS_COMPONENT`
    degradation with a one-shot warning.  Pass ``refresh=True`` to
    re-probe (tests; or after an operator fixes the environment
    mid-process).
    """
    global _pallas_probe
    with _pallas_probe_lock:
        if _pallas_probe is not None and not refresh:
            return _pallas_probe
        try:
            _probe_pallas()
            _pallas_probe = True
        except _PallasNotApplicable:
            _pallas_probe = False
        except Exception as e:  # noqa: BLE001 — any failure means degrade
            degradation.record(PALLAS_COMPONENT, e)
            _pallas_probe = False
        return _pallas_probe


def resolve_attention_impl(impl: str | None) -> str:
    """Resolve a requested attention impl to a concrete one.

    ``"xla"``/``None`` and ``"pallas"`` pass through (an explicit request
    must fail loudly, never silently degrade); ``"auto"`` returns
    ``"pallas"`` when the probe passes and the component has not been
    marked degraded, else ``"xla"``.  Resolution happens at trace time,
    so an outer ``jax.jit`` compiles exactly one path.
    """
    if impl in (None, "xla"):
        return "xla"
    if impl == "pallas":
        return "pallas"
    if impl == "auto":
        if degradation.is_degraded(PALLAS_COMPONENT):
            return "xla"
        return "pallas" if pallas_available() else "xla"
    raise ValueError(
        f"resolve_attention_impl: impl must be 'auto', 'pallas', 'xla' or "
        f"None, got {impl!r}"
    )


# fused-ring kernel (ops/pallas_ring.py): component name shared by the
# probe, parallel/ring.py's dispatcher, and the models
FUSED_COMPONENT = "fused_ring"
# fault name the injection harness arms to force the fused path to fail
FUSED_FAULT = "fused_fail"

_fused_probe: bool | None = None


def remote_copy_supported() -> bool:
    """Does this jax expose the in-kernel remote-DMA surface the fused
    ring's ICI tier needs (``pltpu.make_async_remote_copy`` + semaphore
    primitives)?  Cheap attribute check, no compilation."""
    from ..ops.pallas_ring import remote_supported

    return remote_supported()


def _probe_fused() -> None:
    """Compile-and-run a minimal real (non-interpret) fused-ring launch.

    Unlike the plain Pallas probe, a non-TPU backend here is a RECORDED
    degradation, not a silent miss: ``impl="auto"`` via
    :func:`resolve_ring_impl` promises the launch-free fused forward, and
    falling back to the scan-path ring (per-hop launches + ppermutes) is
    a real performance property change operators must be able to query.
    The injected :data:`FUSED_FAULT` is checked first so CI can exercise
    the degradation path anywhere.
    """
    get_injector().check(FUSED_FAULT)
    import jax

    if not remote_copy_supported():
        raise RuntimeError(
            "jax.experimental.pallas.tpu lacks the remote-DMA surface "
            "(make_async_remote_copy / semaphore primitives) — the fused "
            "ring cannot circulate KV in-kernel on this jax version"
        )
    if jax.devices()[0].platform != "tpu":
        raise RuntimeError(
            f"backend {jax.devices()[0].platform!r} runs the fused ring "
            "in interpret mode only — degrading to the scan-path ring"
        )
    import jax.numpy as jnp

    from ..ops.pallas_ring import fused_ring_local

    q = jnp.zeros((1, 1, 128, 64), jnp.float32)
    out, _ = fused_ring_local(
        q, q, q,
        origins=jnp.zeros((1,), jnp.int32),
        his=jnp.zeros((1,), jnp.int32),
        los=jnp.full((1,), -128, jnp.int32),
        works=jnp.ones((1,), jnp.int32),
        n_local=128, interpret=False,
    )
    jax.block_until_ready(out)


# ICI tier of the fused ring: its OWN component, because its failure
# mode is softer — fused_ring_local (gather + the same single launch)
# still honors the "fused" contract, so a remote-tier Mosaic rejection
# or VMEM overflow degrades one tier, not all the way to the scan ring.
FUSED_REMOTE_COMPONENT = "fused_ring_remote"
# fault name the injection harness arms to force the remote tier to fail
FUSED_REMOTE_FAULT = "fused_remote_fail"

_fused_remote_probe: bool | None = None


def _probe_fused_remote() -> None:
    """Compile-and-run a minimal ``fused_ring_remote`` launch — the tier
    the TPU model path actually prefers, which the local-tier probe never
    touches.  A one-device ring under ``shard_map`` exercises the whole
    remote surface (ANY-space HBM buffers, barrier + grant semaphores,
    MESH-coordinate device ids, the async-copy staging pipeline) without
    needing a second chip; a Mosaic rejection here must become a recorded
    degradation, not a hard failure on the first model step."""
    get_injector().check(FUSED_REMOTE_FAULT)
    import jax

    if not remote_copy_supported():
        raise RuntimeError(
            "jax.experimental.pallas.tpu lacks the remote-DMA surface "
            "(make_async_remote_copy / semaphore primitives) — the fused "
            "ring cannot circulate KV in-kernel on this jax version"
        )
    if jax.devices()[0].platform != "tpu":
        raise RuntimeError(
            f"backend {jax.devices()[0].platform!r} cannot execute "
            "in-kernel remote DMA — remote tier degrades to the "
            "gather-based fused_ring_local"
        )
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, PartitionSpec

    from . import compat
    from ..ops.pallas_ring import fused_ring_remote

    n = 128

    def core(q, k, v):
        # Hardcoded (2, 1) self-coordinates, NOT neighbor_mesh_coords:
        # this probe may run at trace time inside a model's shard_map,
        # where the ambient axis env still holds the OUTER mesh axes —
        # introspecting it here would leak outer tracers into this
        # self-contained one-axis launch.  On a one-device ring both
        # neighbors are rank 0 anyway.
        coords = jnp.zeros((2, 1), jnp.int32)
        return fused_ring_remote(
            q, k, v,
            his=jnp.zeros((1,), jnp.int32),
            los=jnp.full((1,), -n, jnp.int32),
            works=jnp.ones((1,), jnp.int32),
            nbr_coords=coords,
        )[0]

    mesh = Mesh(np.array(jax.devices()[:1]), ("fused_probe",))
    fn = compat.shard_map(
        core, mesh=mesh,
        in_specs=(PartitionSpec(),) * 3, out_specs=PartitionSpec(),
        check_vma=False,
    )
    q = jnp.zeros((1, 1, n, 64), jnp.float32)
    jax.block_until_ready(compat.jit(fn)(q, q, q))


def fused_remote_available(*, refresh: bool = False) -> bool:
    """True when the fused ring's in-kernel remote-DMA tier works here.

    Probed once per process (cached, same lock discipline as
    :func:`fused_ring_available`).  Every failure records a
    :data:`FUSED_REMOTE_COMPONENT` degradation with a one-shot warning;
    ``parallel/ring.py::_ring_fwd_fused`` consults this before choosing
    the remote tier and falls back to ``fused_ring_local`` — still the
    single-launch fused forward, just gather-fed."""
    global _fused_remote_probe
    with _pallas_probe_lock:
        if _fused_remote_probe is not None and not refresh:
            return _fused_remote_probe
        try:
            _probe_fused_remote()
            _fused_remote_probe = True
        except Exception as e:  # noqa: BLE001 — any failure means degrade
            degradation.record(FUSED_REMOTE_COMPONENT, e)
            _fused_remote_probe = False
        return _fused_remote_probe


def fused_ring_available(*, refresh: bool = False) -> bool:
    """True when the real fused-ring kernel path works on this backend.

    Probed once per process (cached, same lock discipline as
    :func:`pallas_available`).  EVERY failure — CPU/interpret backend,
    missing remote-copy support, Mosaic rejection, armed fault — records
    a :data:`FUSED_COMPONENT` degradation with a one-shot warning (see
    :func:`_probe_fused` for why non-TPU is not silent here)."""
    global _fused_probe
    with _pallas_probe_lock:
        if _fused_probe is not None and not refresh:
            return _fused_probe
        try:
            _probe_fused()
            _fused_probe = True
        except Exception as e:  # noqa: BLE001 — any failure means degrade
            degradation.record(FUSED_COMPONENT, e)
            _fused_probe = False
        return _fused_probe


def resolve_ring_impl(impl: str | None) -> str:
    """Resolve a requested RING impl (superset of the attention impls).

    ``"fused"`` returns ``"fused"`` when the probe passes.  When it does
    not, a TPU backend raises (an explicit request must fail loudly where
    the kernel is meant to run); any other backend records the
    degradation (in the probe) and re-resolves as ``"auto"`` through
    :func:`resolve_attention_impl` — the scan-path ring at the best
    per-hop compute tier available.  ``"auto"`` prefers the fused tier,
    then degrades the same way.  ``"xla"``/``"pallas"``/``None`` pass
    through unchanged (explicit scan-path requests stay scan-path).

    Note the asymmetry with :func:`ring_flash_attention`: calling it with
    a literal ``impl="fused"`` always RUNS the fused kernel (interpret
    mode on CPU — the parity-test tier); resolution here is the
    model-level seam where interpret-mode would be a silent pessimization
    rather than a test fixture.
    """
    if impl == "fused":
        if fused_ring_available():
            return "fused"
        import jax

        if jax.default_backend() == "tpu":
            # an explicit request on the backend the kernel was written
            # for fails loudly; only "auto" may pick another path there
            failed = [e for e in degradation.events()
                      if e.component == FUSED_COMPONENT]
            raise RuntimeError(
                "impl=\"fused\" was requested explicitly but the "
                "fused-ring kernel does not compile on this TPU: "
                f"{failed[-1].reason if failed else 'probe failed'}"
            )
        return resolve_attention_impl("auto")
    if impl == "auto":
        if (not degradation.is_degraded(FUSED_COMPONENT)
                and fused_ring_available()):
            return "fused"
        return resolve_attention_impl("auto")
    if impl in (None, "xla", "pallas"):
        return resolve_attention_impl(impl)
    raise ValueError(
        f"resolve_ring_impl: impl must be 'auto', 'fused', 'pallas', "
        f"'xla' or None, got {impl!r}"
    )


def reset(*, probe: bool = True) -> None:
    """Test-harness hook: clear armed faults, degradation state, and
    (optionally) the cached Pallas/fused-ring probe results."""
    global _pallas_probe, _fused_probe, _fused_remote_probe
    _INJECTOR.clear()
    degradation.reset()
    if probe:
        with _pallas_probe_lock:
            _pallas_probe = None
            _fused_probe = None
            _fused_remote_probe = None


# ----------------------------------------------------------------------
# NaN-grad injection tap (jit-compatible)
# ----------------------------------------------------------------------


def nan_tap(x, name: str = "nan_loss"):
    """Multiply ``x`` by NaN when fault ``name`` is armed — under jit.

    The armed/disarmed decision is fetched at RUN time through
    ``jax.pure_callback`` (a trace-time Python check would be baked into
    the compiled step and could never fire "at step k"), so a test can run
    a compiled train step normally for k steps, arm the fault for exactly
    one step, and assert the guarded step skipped it.  Production code
    pays one scalar host callback only if it opts in by wrapping its loss
    with :func:`faulty_loss`.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    def factor() -> np.ndarray:
        return np.float32(np.nan if _INJECTOR.armed(name) else 1.0)

    f = jax.pure_callback(
        factor, jax.ShapeDtypeStruct((), jnp.float32), vmap_method="broadcast_all"
    )
    return x * f.astype(x.dtype)


def faulty_loss(loss_fn: Callable[..., Any], name: str = "nan_loss"):
    """Wrap ``loss_fn`` with a :func:`nan_tap` on its scalar output, so the
    fault-injection harness can poison the loss (and therefore every
    gradient) of an arbitrary training step."""

    def wrapped(*args, **kwargs):
        return nan_tap(loss_fn(*args, **kwargs), name)

    return wrapped
