"""Heartbeat watchdog: a wedged collective becomes a deadline abort.

The failure mode of a pod that loses a host mid-step is not a crash but
a *wedge*: one process blocks forever inside a collective whose peer will
never arrive, `finally` blocks never run, and the job burns its
reservation doing nothing.  Python cannot interrupt a thread stuck in a
C extension, so the only honest conversion is: a watchdog THREAD watches
a heartbeat the training loop touches at every step boundary, and when
the heartbeat goes stale past the deadline it (1) dumps a
FlightRecorder ``watchdog_abort`` incident — the wedge arrives with the
run's recent trajectory and the stalled step number attached — and then
(2) hard-exits the process with :data:`WATCHDOG_EXIT_CODE`, so the
supervisor restarts it and the elastic checkpoint resumes the run.  The
simulation knob is :func:`~.chaos.delay_tap` (an armed in-graph sleep
wedges the SAME compiled step the healthy rounds ran), and
``tests/test_elastic.py`` / ``tests/test_multihost.py`` pin both the
incident dump and the exit code.
"""

from __future__ import annotations

import os
import sys
import threading
from typing import Callable

from ..utils import tracing

# distinguishable from success (0), a crash (1), a signal death
# (negative), and a chaos_point death (113)
WATCHDOG_EXIT_CODE = 114


class Watchdog:
    """Deadline abort for wedged steps.

    ::

        dog = Watchdog(deadline_s=300, recorder=recorder).start()
        for step in range(start, steps):
            state = train_step(state)       # may wedge forever
            dog.beat(step)                  # step boundary reached
        dog.stop()

    ``beat()`` is a single monotonic-clock store — cheap enough for
    every boundary.  The watchdog only arms AFTER the first beat (the
    first step legitimately pays minutes of XLA compilation; pass
    ``arm_immediately=True`` to cover the compile window too, with a
    correspondingly generous deadline).  ``abort`` is injectable for
    in-process tests; the default dumps the incident and calls
    ``os._exit(WATCHDOG_EXIT_CODE)`` — no cleanup, because the wedged
    main thread would never run it anyway.
    """

    def __init__(
        self,
        deadline_s: float,
        *,
        recorder=None,
        abort: Callable[[str], None] | None = None,
        poll_s: float | None = None,
        arm_immediately: bool = False,
    ) -> None:
        if deadline_s <= 0:
            raise ValueError(
                f"Watchdog: deadline_s must be > 0, got {deadline_s}"
            )
        self.deadline_s = float(deadline_s)
        self.recorder = recorder
        self._abort = abort
        self.poll_s = poll_s if poll_s is not None else min(
            max(deadline_s / 10.0, 0.05), 5.0
        )
        self._last = tracing.monotonic() if arm_immediately else None
        self._step: int | None = None
        self._stop = threading.Event()
        self._thread: threading.Thread | None = None
        self.fired = False

    # -- the loop-side API --------------------------------------------

    def beat(self, step: int | None = None) -> None:
        """The training loop reached a step boundary: reset the clock.
        With a tracer configured, each beat is an instant row — the
        cluster timeline's per-process liveness track (the gap before a
        wedge is visible straggler evidence)."""
        self._step = step
        self._last = tracing.monotonic()
        tracing.get_tracer().instant(
            "watchdog/beat", **({"step": step} if step is not None else {})
        )

    def start(self) -> "Watchdog":
        if self._thread is not None:
            return self
        self._thread = threading.Thread(
            target=self._watch, name="elastic-watchdog", daemon=True
        )
        self._thread.start()
        return self

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=10)
            self._thread = None

    def __enter__(self) -> "Watchdog":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.stop()

    # -- the watcher thread -------------------------------------------

    def _watch(self) -> None:
        while not self._stop.wait(self.poll_s):
            if self._last is None:
                continue  # not armed until the first beat
            stale = tracing.monotonic() - self._last
            if stale < self.deadline_s:
                continue
            self.fired = True
            after = (
                f"step {self._step}" if self._step is not None
                else "the last beat"
            )
            message = (
                f"watchdog: no heartbeat for {stale:.1f}s (deadline "
                f"{self.deadline_s:.0f}s) after {after} — a collective "
                f"is wedged (dead peer / hung device); aborting so the "
                f"supervisor can restart from the last checkpoint"
            )
            # the abort instant + flushed open spans are O_APPEND writes
            # — durable before os._exit, so the merged cluster timeline
            # names this process and what it was stuck inside even
            # though no normal shutdown will ever run here
            try:
                tracer = tracing.get_tracer()
                tracer.instant(
                    "watchdog/abort", stale_s=round(stale, 1),
                    deadline_s=self.deadline_s,
                    exit_code=WATCHDOG_EXIT_CODE,
                    **({"step": self._step}
                       if self._step is not None else {}),
                )
                tracer.flush_open("watchdog_abort")
            except Exception:  # noqa: BLE001 — diagnostics must not
                pass           # block the abort itself
            if self.recorder is not None:
                try:
                    self.recorder.dump(
                        "watchdog_abort",
                        stale_s=round(stale, 1),
                        deadline_s=self.deadline_s,
                        **({"step": self._step}
                           if self._step is not None else {}),
                    )
                except Exception:  # noqa: BLE001 — diagnostics must not
                    pass           # block the abort itself
            if self._abort is not None:
                self._abort(message)
                return
            sys.stderr.write(message + "\n")
            sys.stderr.flush()
            os._exit(WATCHDOG_EXIT_CODE)
            return
