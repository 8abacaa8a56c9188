"""Process-level chaos harness: kill, delay, and corrupt on purpose.

PR 1's :class:`~ring_attention_tpu.utils.resilience.FaultInjector` raises
exceptions at armed injection points — the right shape for NaN grads and
truncated files, but a *preemption* is not an exception: the process is
gone mid-syscall, ``finally`` blocks never run, and whatever was on disk
at that instant is what the next process finds.  This module extends the
injector to that level, in four pieces, all CPU-testable with virtual
devices (``tests/test_elastic.py``):

- :func:`chaos_point` — a named hard-death point: when its fault is armed
  (optionally for a specific step), the process dies via ``os._exit``
  with :data:`CHAOS_EXIT_CODE` — no cleanup, no atexit, no flushing, the
  closest a single call can get to SIGKILL/preemption.  The elastic
  checkpointer plants these at every commit-protocol window (mid-shard,
  pre-manifest-commit, post-commit, mid-resume).
- :func:`arm_from_env` — process-level arming: a parent test/driver sets
  ``RING_ATTN_CHAOS="elastic_kill_mid_shard,kill_at_step=3"`` and the
  child worker arms those faults at startup, so multi-process chaos runs
  need no RPC into the victim.
- :func:`delay_tap` / :func:`hang` — injected delay: a ``pure_callback``
  sleep gate spliced into a jitted step simulates a hung collective /
  wedged device from inside the compiled program, so ``with_retries``
  timeout ladders are exercisable without hardware.
- :func:`corrupt_file` — truncation/garbage corruption of a shard file,
  for the corrupted-checkpoint fallback matrix.

The multi-process runner (:class:`ChaosWorker`) spawns a training worker
as a real OS process on virtual CPU devices
(``--xla_force_host_platform_device_count``), with chaos faults in its
environment — kill it anywhere, restart it at any device count, and the
parent asserts what the elastic runtime promised: a valid checkpoint and
a loss trajectory that continues where the dead process left off.
"""

from __future__ import annotations

import os
import socket
import subprocess
import sys
import time
from typing import Any, Iterable, Mapping, Sequence

from ..utils import tracing
from ..utils.resilience import get_injector

# exit code a chaos_point death uses: distinguishable from a crash (1),
# a signal death (negative returncode), and success (0)
CHAOS_EXIT_CODE = 113

# environment variable arm_from_env reads: comma-separated
# ``name`` / ``name=value`` fault specs
CHAOS_ENV = "RING_ATTN_CHAOS"

# cluster spec a spawned worker reads at startup to join a
# jax.distributed cluster: "<process_id>:<num_processes>:<port>"
CLUSTER_ENV = "RING_ATTN_CLUSTER"

# the elastic checkpointer's planted kill points (elastic/checkpoint.py)
KILL_MID_SHARD = "elastic_kill_mid_shard"
KILL_PRE_COMMIT = "elastic_kill_pre_commit"
KILL_POST_COMMIT = "elastic_kill_post_commit"
KILL_MID_RESUME = "elastic_kill_mid_resume"
KILL_AT_STEP = "kill_at_step"


def chaos_point(name: str, *, step: int | None = None) -> None:
    """Die here — hard — when fault ``name`` is armed.

    ``os._exit`` skips every Python-level cleanup (atexit, finally,
    buffered writes), which is the point: the on-disk state the next
    process observes is whatever the commit protocol had made durable at
    this exact line.  When the armed value is an integer and ``step`` is
    given, death fires only when they match (``kill_at_step=3`` kills
    step 3, not every step).
    """
    inj = get_injector()
    if not inj.armed(name):
        return
    value = inj.value(name)
    if step is not None and value is not True:
        try:
            if int(value) != step:
                return
        except (TypeError, ValueError):
            pass
    # the kill instant + flushed open spans are O_APPEND span-file
    # writes: durable the moment they return, so the merged cluster
    # timeline names the victim and the fault even though os._exit
    # skips every normal shutdown path
    try:
        tracer = tracing.get_tracer()
        tracer.instant(
            "chaos/kill", fault=name, exit_code=CHAOS_EXIT_CODE,
            **({"step": step} if step is not None else {}),
        )
        tracer.flush_open("chaos_kill")
    except Exception:  # noqa: BLE001 — diagnostics must not save the victim
        pass
    # one line of evidence for the parent's log, then nothing runs after
    sys.stderr.write(f"chaos: dying at {name}"
                     + (f" (step {step})" if step is not None else "") + "\n")
    sys.stderr.flush()
    os._exit(CHAOS_EXIT_CODE)


def arm_from_env(environ: Mapping[str, str] | None = None) -> list[str]:
    """Arm every fault named in :data:`CHAOS_ENV` (``name`` or
    ``name=value``, comma-separated) on the process-global injector.
    Returns the armed names — call once at worker startup.  Values
    parse as int when they look like one (step indices, delays)."""
    spec = (environ if environ is not None else os.environ).get(CHAOS_ENV, "")
    armed: list[str] = []
    inj = get_injector()
    for item in spec.split(","):
        item = item.strip()
        if not item:
            continue
        name, _, raw = item.partition("=")
        value: Any = True
        if raw:
            try:
                value = int(raw)
            except ValueError:
                try:
                    value = float(raw)
                except ValueError:
                    value = raw
        inj.arm(name, value)
        armed.append(name)
    if armed:
        # the fault window opens HERE: armed -> chaos/kill is the span
        # of exposure the incident reconstruction annotates
        tracing.get_tracer().instant("chaos/armed", faults=",".join(armed))
    return armed


def cluster_from_env(
    environ: Mapping[str, str] | None = None,
) -> tuple[int, int, str] | None:
    """Parse :data:`CLUSTER_ENV` (``"<pid>:<nproc>:<port>"``) into
    ``(process_id, num_processes, port)``, or None when the worker runs
    standalone.  The spawned-worker half of :meth:`ChaosWorker.run_cluster`:
    call at startup and feed ``initialize_multihost``."""
    spec = (environ if environ is not None else os.environ).get(
        CLUSTER_ENV, ""
    )
    if not spec:
        return None
    try:
        pid, nproc, port = spec.split(":")
        return int(pid), int(nproc), port
    except ValueError as e:
        raise ValueError(
            f"{CLUSTER_ENV}={spec!r}: want '<process_id>:<num_processes>"
            f":<port>'"
        ) from e


def hang(name: str = "hang_collective") -> float:
    """Host-side injected delay: sleep for the armed value (seconds) and
    return how long was slept (0.0 when disarmed)."""
    inj = get_injector()
    if not inj.armed(name):
        return 0.0
    delay = float(inj.value(name, 0.0) or 0.0)
    if delay > 0:
        time.sleep(delay)
    return delay


def delay_tap(x, name: str = "hang_collective"):
    """Multiply ``x`` by 1.0 through a ``pure_callback`` that sleeps for
    the armed delay — under jit, at RUN time.

    Splice this onto a collective's operand (or any tensor on the step's
    critical path) and the compiled step stalls for the armed duration:
    the wedged-collective simulation.  Like
    :func:`~...utils.resilience.nan_tap`, the armed/disarmed decision is
    fetched from the host each run, so the SAME compiled step can be
    healthy for k steps and hang at exactly step k.

    Multi-process caveat: in a ``jax.distributed`` SPMD program the
    callback of a replicated value executes only on the process holding
    its first shard — process 0.  Arm the wedge THERE; every peer then
    wedges inside its own (real) cross-process collective waiting for
    process 0's contribution, which is the symmetric cluster-wide stall
    the watchdog pin wants (``tests/test_multihost.py``).  A wedge
    armed on a non-zero process silently no-ops in-graph.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    def factor() -> np.ndarray:
        hang(name)
        return np.float32(1.0)

    f = jax.pure_callback(
        factor, jax.ShapeDtypeStruct((), jnp.float32),
        vmap_method="broadcast_all",
    )
    return x * f.astype(x.dtype)


def corrupt_file(path: str, mode: str = "truncate") -> None:
    """Corrupt one file the way real failures do: ``"truncate"`` cuts it
    mid-byte (power loss / preemption mid-write), ``"garbage"`` flips a
    block in the middle (bit rot / torn sector)."""
    size = os.path.getsize(path)
    if mode == "truncate":
        with open(path, "r+b") as f:
            f.truncate(max(size // 2, 1))
    elif mode == "garbage":
        with open(path, "r+b") as f:
            f.seek(size // 2)
            f.write(b"\xde\xad\xbe\xef" * 8)
    else:
        raise ValueError(
            f"corrupt_file: mode must be 'truncate' or 'garbage', got {mode!r}"
        )


def free_port() -> int:
    """A free localhost TCP port for a spawned cluster's coordinator."""
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


class ChaosWorker:
    """Spawn-and-kill driver for a training worker on virtual CPU devices.

    One instance = one worker *command* (script + args); each
    :meth:`run` is a fresh OS process with its own device count and
    chaos faults — the parent-side half of the kill-anywhere matrix::

        w = ChaosWorker([sys.executable, "tests/elastic_worker.py",
                         "--ckpt-dir", d])
        r = w.run(devices=4, chaos={"kill_at_step": 3})   # dies at step 3
        assert r.returncode == CHAOS_EXIT_CODE
        r = w.run(devices=2)                              # re-mesh resume
        assert r.returncode == 0

    :meth:`run_cluster` is the pod-scale form: N worker processes join
    ONE ``jax.distributed`` cluster (coordinator on a free localhost
    port, spec delivered via :data:`CLUSTER_ENV`) and the chaos faults
    arm in exactly ONE victim — kill one worker of a live cluster at any
    commit window, then :meth:`run` restarts at the surviving process
    count and the elastic checkpoint re-meshes (``tests/test_multihost.py``
    drives the full matrix).
    """

    def __init__(
        self,
        argv: Sequence[str],
        *,
        cwd: str | None = None,
        timeout: float = 300.0,
    ) -> None:
        self.argv = list(argv)
        self.cwd = cwd
        self.timeout = timeout

    def run(
        self,
        *,
        devices: int,
        chaos: Mapping[str, Any] | Iterable[str] | None = None,
        extra_env: Mapping[str, str] | None = None,
        extra_args: Sequence[str] = (),
    ) -> subprocess.CompletedProcess:
        env = dict(os.environ)
        env.pop("XLA_FLAGS", None)  # the worker owns its device count
        env["JAX_PLATFORMS"] = "cpu"
        env["RING_ATTN_CHAOS_DEVICES"] = str(devices)
        if chaos:
            items = (chaos.items() if isinstance(chaos, Mapping)
                     else ((c, True) for c in chaos))
            env[CHAOS_ENV] = ",".join(
                name if value is True else f"{name}={value}"
                for name, value in items
            )
        else:
            env.pop(CHAOS_ENV, None)
        if extra_env:
            env.update(extra_env)
        return subprocess.run(
            self.argv + list(extra_args),
            capture_output=True, text=True, env=env, cwd=self.cwd,
            timeout=self.timeout,
        )

    def run_cluster(
        self,
        *,
        processes: int,
        devices_per_process: int,
        chaos: Mapping[str, Any] | Iterable[str] | None = None,
        chaos_process: int = 0,
        extra_env: Mapping[str, str] | None = None,
        extra_args: Sequence[str] = (),
        port: int | None = None,
    ) -> list[subprocess.CompletedProcess]:
        """One live multi-process cluster run; chaos arms in ONE victim.

        Spawns ``processes`` copies of the worker command, each owning
        ``devices_per_process`` virtual CPU devices, joined through a
        ``jax.distributed`` coordinator on a localhost port.  The chaos
        faults land only in ``chaos_process``'s environment — the other
        workers run clean and must convert the victim's death into a
        bounded error (checkpoint barrier timeout), never a hang.

        Returns one :class:`subprocess.CompletedProcess` per worker, in
        process order.  Outputs are reaped PER WORKER even when some hang
        past the timeout (those report ``returncode=None``-style kill
        codes with whatever partial output they produced) — misattributed
        logs are how multi-process failures become undebuggable.
        """
        port = port or free_port()
        env_base = dict(os.environ)
        env_base.pop("XLA_FLAGS", None)
        env_base["JAX_PLATFORMS"] = "cpu"
        env_base["RING_ATTN_CHAOS_DEVICES"] = str(devices_per_process)
        if extra_env:
            env_base.update(extra_env)
        procs = []
        for pid in range(processes):
            env = dict(env_base)
            env[CLUSTER_ENV] = f"{pid}:{processes}:{port}"
            if chaos and pid == chaos_process:
                items = (chaos.items() if isinstance(chaos, Mapping)
                         else ((c, True) for c in chaos))
                env[CHAOS_ENV] = ",".join(
                    name if value is True else f"{name}={value}"
                    for name, value in items
                )
            else:
                env.pop(CHAOS_ENV, None)
            procs.append(subprocess.Popen(
                self.argv + list(extra_args),
                stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
                text=True, env=env, cwd=self.cwd,
            ))
        results: list[subprocess.CompletedProcess | None] = [None] * processes
        deadline = time.monotonic() + self.timeout  # ra: allow(RA014 deadline arithmetic over worker reaping, not an emitted timestamp)
        try:
            for pid, p in enumerate(procs):
                budget = max(deadline - time.monotonic(), 0.01)  # ra: allow(RA014 deadline arithmetic over worker reaping, not an emitted timestamp)
                try:
                    out, _ = p.communicate(timeout=budget)
                except subprocess.TimeoutExpired:
                    continue  # reaped (with partial output) below
                results[pid] = subprocess.CompletedProcess(
                    p.args, p.returncode, out, ""
                )
        finally:
            for pid, p in enumerate(procs):
                if results[pid] is not None:
                    continue
                p.kill()
                try:
                    out, _ = p.communicate(timeout=10)
                except Exception:  # noqa: BLE001 — corpse refuses to talk
                    out = ""
                results[pid] = subprocess.CompletedProcess(
                    p.args, p.returncode if p.returncode is not None
                    else -9, out, ""
                )
        return list(results)
