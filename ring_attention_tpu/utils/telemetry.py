"""Telemetry spine: in-graph metrics, host-side logging, MFU/hop accounting.

The reference has no timers or profiler hooks at all (SURVEY §5): a
regression arrives with no per-phase breakdown to say whether the ring
hop, the Ulysses all-to-all, or the kernel itself moved.  FlashAttention
(arXiv 2205.14135) made IO-awareness the design axis; this module is the
measurement side of that, plus TASP-style (arXiv 2509.26541) topology-aware
communication accounting, in four pieces:

- **In-graph collection** — :class:`TrainMetrics` (the extended stats
  pytree ``make_train_step(collect_metrics=True)`` carries: loss,
  grad-norm, nonfinite/skipped-step counts) and :class:`Telemetry`, a
  trace-time scalar registry: instrumented code calls
  ``telemetry.observe(name, scalar)``, which is a strict no-op unless a
  ``collecting()`` context is active at the same trace level — so the
  annotations cost nothing (and change no HLO) when nobody is listening.
- **Host-side logging** — :class:`MetricsLogger`, a rolling JSONL writer
  (one line per step window, schema-versioned, atomic append) with
  optional CSV / TensorBoard export and a reader that survives a writer
  killed mid-line.  ``tools/trace_report.py`` renders its output.
- **MFU / comms accounting** — analytic flash-FLOP formulas
  (:func:`flash_attention_flops`, :func:`transformer_step_flops`),
  :func:`achieved_mfu` against the chip's bf16 peak, and
  :func:`ring_comms_accounting`: hop-count, bytes-moved-per-hop, and the
  per-hop compute/transfer overlap fraction for a (ring x ulysses)
  factoring — PR 3's "ulysses x fewer hops" claim as a number logged
  every step instead of an HLO pin we trust.
- **Diagnostic summaries** — :func:`attention_logit_summaries`: exact
  max-logit and softmax-entropy of an attention call via an online
  blockwise sweep (O(bucket) memory).  This is an *extra* O(n^2 d) pass:
  run it on a probe batch every N steps, never inside the hot step.

Like ``resilience.py``, this module is stdlib-only at module level (jax is
imported inside functions).
"""

from __future__ import annotations

import collections
import contextlib
import csv
import json
import os
import threading
import time
from typing import Any, Iterator, NamedTuple

from . import resilience, tracing
from .tracing import monotonic_wall as _monotonic_wall

# JSONL row schema version.  Bump when a field is renamed or its meaning
# changes; adding fields is backward compatible and needs no bump.
# v1: schema, step, time, plus free-form metric scalars (see
# docs/observability.md for the glossary emitted by examples/train.py).
SCHEMA_VERSION = 1

# bf16 dense peak TFLOPs per chip, keyed by the EXACT ``device_kind``
# string jax reports — the denominator of every MFU number this framework
# reports.  A device that is not listed is an error, never a default: add
# a row when a new chip is seen, with the string it reports.  Source:
# Google Cloud "TPU v5e" documentation; the key is what one v5e chip
# reported on 2026-07-29 and again in chip_smoke.py.
PEAK_TFLOPS = {
    "TPU v5 lite": 197.0,
}

# per-direction ICI link bandwidth (GB/s), same keying — used only for
# the analytic per-hop overlap fraction (a planning number, not a
# measurement; the measured truth is a profiler capture)
ICI_GBPS = {
    "TPU v5 lite": 186.0,
}

# the chip ``ring_comms_accounting``'s overlap MODEL describes when the
# caller passes no peaks: a fixed reference, not the device the process
# happens to run on — the model's output never depends on where it runs
MODEL_DEVICE_KIND = "TPU v5 lite"

# attention matmul counts: 2 matmuls forward
# (q@k^T, p@v); backward recomputes scores and adds 4 grad matmuls
# (dv, dp, dq, dk) => fwd+bwd is 7
FWD_MATMULS = 2
FWDBWD_MATMULS = 7

# per-launch dispatch cost the scan-path ring pays at every hop boundary
# (host dispatch + Mosaic program setup, ~5us — the order XLA's launch
# path costs on current TPU runtimes).  The fused ring's whole point is
# that this term, and the launch boundary it models, do not exist: every
# hop after the first starts inside the already-running kernel.
DISPATCH_OVERHEAD_S = 5e-6


# ----------------------------------------------------------------------
# In-graph scalar collection
# ----------------------------------------------------------------------


class TrainMetrics(NamedTuple):
    """Extended per-step stats pytree carried through
    ``make_train_step(collect_metrics=True)``.

    Scalars live on device (the step stays one fused executable; nothing
    here adds a collective — pinned by
    ``tests/test_telemetry.py::test_metrics_add_no_collectives``):

    - ``loss`` — this step's loss (f32; NOT masked on a skipped step, so
      logs show the offending value).
    - ``grad_norm`` — this step's global gradient L2 norm, pre-clip.
    - ``step_ok`` — whether this step's update was applied (always True
      when ``skip_nonfinite=False``, even for a non-finite step).
    - ``skipped`` — running count of skipped updates (stays 0 unguarded).
    - ``nonfinite`` — running count of steps whose loss or grad norm was
      non-finite, applied or not: under ``skip_nonfinite=False`` this is
      the "the run is corrupting itself" alarm the guard would have
      stopped.
    """

    loss: Any  # f32 scalar
    grad_norm: Any  # f32 scalar
    step_ok: Any  # bool scalar
    skipped: Any  # int32 scalar, running
    nonfinite: Any  # int32 scalar, running


def init_train_metrics(skipped: int = 0, nonfinite: int = 0) -> TrainMetrics:
    """Seed carry for the instrumented step; ``skipped``/``nonfinite`` let a
    resumed run continue its counters from a checkpointed ``StepStats``."""
    import jax.numpy as jnp

    return TrainMetrics(
        loss=jnp.float32(0.0),
        grad_norm=jnp.float32(0.0),
        step_ok=jnp.asarray(True),
        skipped=jnp.asarray(skipped, jnp.int32),
        nonfinite=jnp.asarray(nonfinite, jnp.int32),
    )


class Telemetry:
    """Trace-time registry of named in-graph scalars + host-side events.

    ``observe(name, value)`` is sprinkled through instrumented code and is
    a strict no-op (not even a dict lookup on the value) unless a
    ``collecting()`` context is active — so instrumentation points cost
    nothing when nobody is listening, and the compiled program is
    bit-identical with telemetry off.

    ``collecting()`` must be entered at the SAME trace level as the
    observations it collects — typically *inside* the jitted function::

        tel = Telemetry()

        @jax.jit
        def fwd(x):
            with tel.collecting() as col:
                out = model(x)
            return out, col.values()   # observed scalars become outputs

    Observations made at a deeper transform level (inside ``shard_map``,
    ``lax.scan`` bodies, or a ``custom_vjp`` trace) CANNOT escape to an
    outer collector — jax would report a leaked tracer.  Instrumentation
    points inside those regions must aggregate locally first (or be
    logged through the analytic accounting below instead).

    ``event(kind, **fields)`` records host-side events (degraded kernels,
    probe failures) that :class:`MetricsLogger` drains into the JSONL
    stream as ``{"event": kind, ...}`` rows.
    """

    def __init__(self) -> None:
        self._stores: list[dict[str, Any]] = []
        self._events: list[dict[str, Any]] = []
        self._lock = threading.Lock()

    # -- in-graph scalars -------------------------------------------------

    class _Collector:
        def __init__(self, store: dict[str, Any]):
            self._store = store

        def values(self) -> dict[str, Any]:
            return dict(self._store)

    @contextlib.contextmanager
    def collecting(self) -> Iterator["Telemetry._Collector"]:
        store: dict[str, Any] = {}
        self._stores.append(store)
        try:
            yield Telemetry._Collector(store)
        finally:
            self._stores.pop()

    def active(self) -> bool:
        return bool(self._stores)

    def observe(self, name: str, value: Any) -> None:
        """Record scalar ``value`` under ``name`` in the innermost active
        collector; silently dropped when none is active.  ``value`` may be
        a thunk (callable taking no args) so the metric's compute is only
        traced when someone is listening."""
        if not self._stores:
            return
        if callable(value):
            value = value()
        self._stores[-1][name] = value

    # -- host-side events -------------------------------------------------

    def event(self, kind: str, **fields: Any) -> None:
        # monotonic+wall pair (shared helper with tracing.py): wall alone
        # cannot order events across processes — an NTP step or host skew
        # reorders a merged timeline; the mono stamp pins local order and
        # the merger's clock-offset correction handles the rest
        mono, wall = _monotonic_wall()
        with self._lock:
            self._events.append(
                {"event": kind, "time": wall, "mono": round(mono, 6),
                 **fields}
            )

    def events(self) -> tuple[dict[str, Any], ...]:
        with self._lock:
            return tuple(self._events)

    def drain_events(self) -> list[dict[str, Any]]:
        with self._lock:
            out, self._events = self._events, []
            return out

    def reset(self) -> None:
        with self._lock:
            self._events.clear()


#: process-global default registry (instrumented library code observes
#: here; tests and power users may build private instances)
telemetry = Telemetry()


def _on_degradation(component: str, reason: str) -> None:
    """Listener wired onto ``resilience.degradation``: every kernel
    fallback lands as a telemetry event, so a run that silently lost its
    fast kernels shows up in the metrics stream — not just as a one-shot
    warning scrolled out of the log."""
    telemetry.event("degraded", component=component, reason=reason)


resilience.degradation.add_listener(_on_degradation)


# ----------------------------------------------------------------------
# Host-side metrics logging (JSONL / CSV / TensorBoard)
# ----------------------------------------------------------------------


class MetricsLogger:
    """Rolling JSONL metrics writer: one line per step window.

    Every row carries ``schema`` (:data:`SCHEMA_VERSION`), ``step``, and
    ``time``; remaining fields are the caller's scalars.  Writes go
    through a single ``os.write`` on an ``O_APPEND`` fd, so concurrent
    writers interleave whole lines and a killed writer leaves at most one
    torn FINAL line — which :func:`read_metrics` skips — never a corrupt
    middle.  Host-side events registered on ``telemetry`` (kernel
    degradation, probe failures) are drained into the stream as their own
    rows, and any drained ``degraded`` event also marks the NEXT metric
    row with ``degraded=1`` so a plain metrics consumer sees it too.

    ``csv_path`` mirrors metric rows (not event rows) to a CSV whose
    header is fixed by the first row.  ``tensorboard_dir`` mirrors scalar
    fields via ``jax.profiler``'s summary writer when TensorBoard is
    importable — missing TB never fails training.
    """

    def __init__(
        self,
        directory: str,
        *,
        filename: str = "metrics.jsonl",
        csv_path: str | None = None,
        tensorboard_dir: str | None = None,
        registry: Telemetry | None = None,
    ) -> None:
        os.makedirs(directory, exist_ok=True)
        self.path = os.path.join(directory, filename)
        self._fd = os.open(
            self.path, os.O_WRONLY | os.O_CREAT | os.O_APPEND, 0o644
        )
        self._registry = registry if registry is not None else telemetry
        self._csv_path = csv_path
        self._csv_fields: list[str] | None = None
        self._tb = None
        if tensorboard_dir is not None:
            try:  # pragma: no cover - TB optional in CI
                from torch.utils.tensorboard import SummaryWriter  # type: ignore

                self._tb = SummaryWriter(tensorboard_dir)
            except Exception:
                try:
                    from tensorboardX import SummaryWriter  # type: ignore

                    self._tb = SummaryWriter(tensorboard_dir)
                except Exception:
                    self._tb = None

    def _append(self, row: dict[str, Any]) -> None:
        data = (json.dumps(row, sort_keys=True) + "\n").encode()
        os.write(self._fd, data)  # O_APPEND: one atomic whole-line append

    def log(self, step: int, **metrics: Any) -> dict[str, Any]:
        """Write one metric row (plus any pending event rows); scalars are
        coerced to host floats/ints (a device array forces a sync — call
        this at your logging cadence, not every step)."""
        pending = self._registry.drain_events()
        degraded = 0
        for ev in pending:
            self._append({"schema": SCHEMA_VERSION, **ev})
            if ev.get("event") == "degraded":
                degraded += 1
        mono, wall = _monotonic_wall()
        row: dict[str, Any] = {
            "schema": SCHEMA_VERSION,
            "step": int(step),
            "time": round(wall, 3),
            "mono": round(mono, 6),
        }
        if degraded:
            row["degraded"] = degraded
        for key, val in metrics.items():
            row[key] = _to_scalar(val)
        self._append(row)
        if self._csv_path is not None:
            self._write_csv(row)
        if self._tb is not None:  # pragma: no cover - TB optional
            for key, val in row.items():
                if isinstance(val, (int, float)) and key not in (
                    "schema", "step", "time", "mono",
                ):
                    self._tb.add_scalar(key, val, int(step))
        return row

    def _write_csv(self, row: dict[str, Any]) -> None:
        first = self._csv_fields is None
        if first:
            self._csv_fields = sorted(row)
        with open(self._csv_path, "a", newline="") as f:
            writer = csv.DictWriter(
                f, fieldnames=self._csv_fields, extrasaction="ignore"
            )
            if first:
                writer.writeheader()
            writer.writerow(row)

    def close(self) -> None:
        try:
            os.close(self._fd)
        except OSError:
            pass
        if self._tb is not None:  # pragma: no cover
            self._tb.close()

    def __enter__(self) -> "MetricsLogger":
        return self

    def __exit__(self, *exc: Any) -> None:
        self.close()


def _to_scalar(val: Any) -> Any:
    """Host scalar from python/numpy/jax values; strings/lists pass through."""
    if isinstance(val, (str, bool, int, float)) or val is None:
        return val
    if isinstance(val, (list, tuple, dict)):
        return val
    try:
        f = float(val)
    except (TypeError, ValueError):
        return str(val)
    return int(f) if f.is_integer() and abs(f) < 2**53 else f


def read_metrics(path: str) -> list[dict[str, Any]]:
    """Parse a metrics JSONL file (or a directory holding
    ``metrics.jsonl``), skipping torn/garbage lines — the reader half of
    the killed-writer contract."""
    if os.path.isdir(path):
        path = os.path.join(path, "metrics.jsonl")
    rows: list[dict[str, Any]] = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            try:
                row = json.loads(line)
            except json.JSONDecodeError:
                continue  # torn final line from a killed writer
            if isinstance(row, dict):
                rows.append(row)
    return rows


# ----------------------------------------------------------------------
# Numerics flight recorder
# ----------------------------------------------------------------------

# Flight-dump schema.  v1: {"schema", "trigger": {"kind", "time", ...},
# "context", "rows": [last-N metric rows, oldest first], "events"}.
# v2: + "spans" (last-N open/closed span rows from the active
# utils/tracing.py tracer — the incident's local timeline) and "mono"
# monotonic stamps alongside every "time" wall stamp.
FLIGHT_SCHEMA_VERSION = 2


class FlightRecorder:
    """Rolling ring buffer of the last N metric rows + host events, dumped
    as one JSON file when something goes wrong.

    A NaN at step 40k is useless as a bare counter; what the operator
    needs is the preceding trajectory — was ``grad_norm`` trending up for
    2k steps (diverging run) or flat until one step (bad batch / hardware
    fault)?  The recorder keeps that trajectory in memory at O(window)
    cost and writes it only on a trigger:

    - **nonfinite-skip** — :meth:`observe_step` watches the
      :class:`TrainMetrics` carry and dumps when a step is skipped or the
      nonfinite counter advances;
    - **degradation / retry exhaustion** — :meth:`install` registers
      listeners on ``resilience.degradation`` and the ``with_retries``
      failure hook;
    - **crash (incl. RetraceError)** — wrap the loop in :meth:`guard`;
      any escaping exception dumps before re-raising.

    Dumps are atomic (write-then-rename, like ``utils/checkpoint.py``) so
    a crash mid-dump can never leave a torn file, and each trigger gets
    its own numbered file — a cascade (NaN then crash) keeps both.
    ``context`` (static run config: mesh shape, hop config, remat policy)
    rides along in every dump.  Format: docs/observability.md
    §Observatory.
    """

    def __init__(
        self,
        directory: str,
        *,
        window: int = 64,
        registry: Telemetry | None = None,
        context: dict[str, Any] | None = None,
        max_dumps_per_trigger: int = 5,
        span_window: int = 32,
    ) -> None:
        if window < 1:
            raise ValueError(
                f"FlightRecorder: window must be >= 1, got {window}"
            )
        os.makedirs(directory, exist_ok=True)
        self.directory = directory
        self.span_window = span_window
        self._rows: collections.deque = collections.deque(maxlen=window)
        self._events: collections.deque = collections.deque(maxlen=window)
        self._registry = registry if registry is not None else telemetry
        self._context = dict(context or {})
        self._lock = threading.Lock()
        self._last_nonfinite: int | None = None  # set by first observe_step
        self._last_skipped: int | None = None
        self._n_dumps = 0
        # per-trigger-kind cap: a run that goes permanently non-finite
        # must not write one dump per step for the rest of the run — the
        # first few carry the diagnostic value, the rest are disk burn
        self._max_per_trigger = max_dumps_per_trigger
        self._per_trigger: dict[str, int] = {}
        self.suppressed: dict[str, int] = {}
        self.dumps: list[str] = []

    # -- feeding the buffer ------------------------------------------------

    def record(self, step: int, **metrics: Any) -> None:
        """Append one metric row (host-coerced scalars) to the window."""
        mono, wall = _monotonic_wall()
        row = {"step": int(step), "time": round(wall, 3),
               "mono": round(mono, 6)}
        for key, val in metrics.items():
            row[key] = _to_scalar(val)
        with self._lock:
            self._rows.append(row)

    def note_event(self, kind: str, **fields: Any) -> None:
        """Append a host-side event (checkpoint saved, lr change) to the
        window without going through the global registry."""
        mono, wall = _monotonic_wall()
        with self._lock:
            self._events.append(
                {"event": kind, "time": round(wall, 3),
                 "mono": round(mono, 6), **fields}
            )

    def observe_step(self, step: int, metrics: "TrainMetrics") -> str | None:
        """Record this step's :class:`TrainMetrics` row and dump when it
        shows trouble: the step was skipped, or the nonfinite counter
        advanced (unguarded runs — the update was applied anyway).

        Reading the metrics forces a device sync; call at your logging
        cadence, or per step in loops that already block each step.
        Returns the dump path when a dump was triggered, else None.
        """
        row = {
            "loss": _to_scalar(metrics.loss),
            "grad_norm": _to_scalar(metrics.grad_norm),
            "step_ok": bool(metrics.step_ok),
            "skipped": int(metrics.skipped),
            "nonfinite": int(metrics.nonfinite),
        }
        self.record(step, **row)
        # watermarks seed from the FIRST observed row: a resumed run
        # whose checkpoint carried nonzero skipped/nonfinite counters
        # must not false-alarm on its first healthy step (step_ok still
        # catches a genuinely-bad first step)
        if self._last_skipped is None:
            self._last_skipped = row["skipped"]
            self._last_nonfinite = row["nonfinite"]
        trigger = None
        if row["skipped"] > self._last_skipped or not row["step_ok"]:
            trigger = "nonfinite_skip"
        elif row["nonfinite"] > self._last_nonfinite:
            trigger = "nonfinite_applied"
        self._last_skipped = row["skipped"]
        self._last_nonfinite = row["nonfinite"]
        if trigger is None:
            return None
        return self.dump(trigger, step=step, loss=row["loss"],
                         grad_norm=row["grad_norm"])

    # -- triggers ----------------------------------------------------------

    def dump(self, trigger: str, **detail: Any) -> str | None:
        """Write the window to ``flight_NNN_<trigger>.json`` atomically and
        return the path; ``None`` when nothing was written — either the
        write failed (never raises: a full disk must not mask the
        original fault; the failure lands as an event row in the next
        dump) or this trigger kind already hit ``max_dumps_per_trigger``
        (``suppressed`` counts what was withheld)."""
        mono, wall = _monotonic_wall()
        tracer = tracing.get_tracer()
        spans = tracer.last_spans(self.span_window) if tracer else []
        with self._lock:
            count = self._per_trigger.get(trigger, 0)
            if self._max_per_trigger and count >= self._max_per_trigger:
                if trigger not in self.suppressed:
                    self._events.append({
                        "event": "flight_dumps_capped", "trigger": trigger,
                        "limit": self._max_per_trigger,
                        "time": round(wall, 3),
                        "mono": round(mono, 6),
                    })
                self.suppressed[trigger] = self.suppressed.get(trigger, 0) + 1
                return None
            self._per_trigger[trigger] = count + 1
            self._n_dumps += 1
            payload = {
                "schema": FLIGHT_SCHEMA_VERSION,
                "trigger": {
                    "kind": trigger,
                    "time": round(wall, 3),
                    "mono": round(mono, 6),
                    **{k: _to_scalar(v) for k, v in detail.items()},
                },
                "context": dict(self._context),
                "rows": list(self._rows),
                "events": list(self._events)
                + list(self._registry.events()),
                # the incident's local timeline: the last-N closed spans
                # plus everything still open on the active tracer — what
                # the process was DOING when the trigger fired, not just
                # what its counters said
                "spans": spans,
            }
            safe = "".join(
                c if c.isalnum() or c in "-_" else "_" for c in trigger
            )[:40]
            path = os.path.join(
                self.directory, f"flight_{self._n_dumps:03d}_{safe}.json"
            )
        tmp = f"{path}.{os.getpid()}.tmp"
        try:
            with open(tmp, "w") as f:
                json.dump(payload, f, sort_keys=True)
            os.replace(tmp, path)
        except OSError as e:
            # return None, not the path: a caller printing "dump at X"
            # for a file that was never written sends the operator
            # chasing a ghost.  The cap slot is refunded — N failed
            # writes (disk briefly full) must not silence the trigger
            # kind for the rest of the run.
            with self._lock:
                self._per_trigger[trigger] -= 1
            self.note_event("flight_dump_failed", path=path, error=str(e))
            return None
        with self._lock:
            self.dumps.append(path)
        return path

    @contextlib.contextmanager
    def guard(self, label: str = "train_loop") -> Iterator["FlightRecorder"]:
        """Dump on any escaping exception (``RetraceError``, OOM, a bug),
        then re-raise — the crash arrives with its trajectory attached."""
        try:
            yield self
        except BaseException as e:
            self.dump(
                "crash", label=label,
                error=f"{type(e).__name__}: {e}"[:500],
            )
            raise

    def install(self) -> "FlightRecorder":
        """Wire the automatic host-side triggers: every kernel degradation
        and every exhausted ``with_retries`` ladder dumps the window.
        Idempotent; returns self for chaining.  The registries are
        process-global — call :meth:`uninstall` when the recorder's run
        ends before the process does (tests, config sweeps), or dead
        recorders keep dumping into stale directories forever."""
        resilience.degradation.add_listener(self._on_degraded)
        resilience.add_failure_listener(self._on_retry_exhausted)
        return self

    def uninstall(self) -> "FlightRecorder":
        """Detach the :meth:`install` listeners (no-op if never
        installed)."""
        resilience.degradation.remove_listener(self._on_degraded)
        resilience.remove_failure_listener(self._on_retry_exhausted)
        return self

    def _on_degraded(self, component: str, reason: str) -> None:
        self.dump("degraded", component=component, reason=reason)

    def _on_retry_exhausted(self, where: str, error: str) -> None:
        self.dump("retry_exhausted", where=where, error=error)


def read_flight_dump(path: str) -> dict[str, Any]:
    """Parse one flight dump, with a loud error naming an unknown schema
    (forward-compat: readers must not silently misread a v3 dump).
    v1 dumps (no "spans"/"mono") stay readable — the additions were
    backward compatible; the reader normalizes them with an empty
    "spans" list."""
    with open(path) as f:
        payload = json.load(f)
    schema = payload.get("schema")
    if schema not in (1, FLIGHT_SCHEMA_VERSION):
        raise ValueError(
            f"read_flight_dump: {path} has schema {schema!r}; this reader "
            f"understands <= {FLIGHT_SCHEMA_VERSION}"
        )
    payload.setdefault("spans", [])
    return payload


# ----------------------------------------------------------------------
# MFU / FLOP / comms accounting
# ----------------------------------------------------------------------


def _device_rate(table: dict[str, float], what: str, device: Any) -> float:
    if device is None:
        import jax

        device = jax.devices()[0]
    kind = getattr(device, "device_kind", str(device))
    if kind not in table:
        raise ValueError(
            f"no {what} listed for device_kind {kind!r} (listed: "
            f"{sorted(table)}); add the chip to utils/telemetry.py with "
            f"its published figure — an assumed peak is not a utilization"
        )
    return table[kind]


def device_peak_tflops(device: Any = None) -> float:
    """bf16 peak TFLOPs of ``device`` (default: ``jax.devices()[0]``);
    raises ``ValueError`` for a ``device_kind`` :data:`PEAK_TFLOPS` does
    not list."""
    return _device_rate(PEAK_TFLOPS, "bf16 peak", device)


def device_ici_gbps(device: Any = None) -> float:
    """Per-direction ICI GB/s of ``device``; raises for an unlisted kind."""
    return _device_rate(ICI_GBPS, "ICI bandwidth", device)


def flash_attention_flops(
    seq_q: int,
    seq_k: int | None = None,
    *,
    heads: int,
    dim_head: int,
    causal: bool = False,
    backward: bool = False,
    batch: int = 1,
) -> float:
    """Analytic FLOPs of one flash-attention call.

    Two matmuls forward (``q@k^T`` and ``p@v``, each
    ``2 * seq_q * seq_k * dim_head`` MACs-as-FLOPs per head); backward
    recomputes scores and adds the 4 gradient matmuls (dv, dp, dq, dk) —
    7 matmuls total, ``FWDBWD_MATMULS``.  ``causal`` halves the
    work (only the lower triangle is computed).  Softmax/normalization
    vector work is excluded by convention — MFU counts MXU work.
    """
    if seq_k is None:
        seq_k = seq_q
    matmuls = FWDBWD_MATMULS if backward else FWD_MATMULS
    flops = matmuls * 2.0 * seq_q * seq_k * heads * dim_head * batch
    return flops * 0.5 if causal else flops


def transformer_step_flops(
    n_params: int,
    tokens: int,
    *,
    depth: int,
    heads: int,
    dim_head: int,
    seq_len: int,
    causal: bool = True,
    batch: int = 1,
) -> float:
    """Analytic FLOPs of one train step (fwd+bwd) of a dense transformer.

    The standard ``6 * params * tokens`` matmul estimate (2 fwd + 4 bwd
    FLOPs per param per token) plus the attention score/grad matmuls the
    param count does not see (:func:`flash_attention_flops` per layer).
    Good to ~10% for MFU trend lines; the measured truth is
    ``compiled.cost_analysis()`` where the backend provides it.
    """
    dense = 6.0 * float(n_params) * float(tokens)
    attn = depth * flash_attention_flops(
        seq_len, heads=heads, dim_head=dim_head, causal=causal,
        backward=True, batch=batch,
    )
    return dense + attn


def achieved_mfu(flops: float, seconds: float, peak_tflops: float) -> float:
    """Model FLOPs utilization: achieved / peak, in [0, ~1]."""
    if seconds <= 0 or peak_tflops <= 0:
        return 0.0
    return (flops / seconds / 1e12) / peak_tflops


def compiled_cost(compiled: Any) -> dict[str, float]:
    """Best-effort ``cost_analysis()`` of a compiled executable:
    ``{"xla_flops": ..., "bytes_accessed": ...}`` (empty when the backend
    offers no analysis — never raises)."""
    try:
        ca = compiled.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0] if ca else {}
        out: dict[str, float] = {}
        if ca.get("flops"):
            out["xla_flops"] = float(ca["flops"])
        if ca.get("bytes accessed"):
            out["bytes_accessed"] = float(ca["bytes accessed"])
        return out
    except Exception:  # noqa: BLE001 — diagnostics must never fail a run
        return {}


def compiled_memory(compiled: Any) -> dict[str, int]:
    """Best-effort ``memory_analysis()`` of a compiled executable: the
    compiler's own peak-memory accounting (``temp_bytes`` is the scratch
    high-water mark — the number the blockwise-FFN/remat knobs exist to
    shrink), as ``{"temp_bytes", "argument_bytes", "output_bytes",
    "alias_bytes"(+host_* when a host memory space is in play)}``.  Empty
    when the backend offers no analysis — never raises.  Works on the CPU
    backend too, which is what lets the tests prove the chunked-FFN memory
    claim as a relation between two compiled programs (docs/memory.md)."""
    try:
        ma = compiled.memory_analysis()
        if ma is None:
            return {}
        out: dict[str, int] = {}
        for attr, key in (
            ("temp_size_in_bytes", "temp_bytes"),
            ("argument_size_in_bytes", "argument_bytes"),
            ("output_size_in_bytes", "output_bytes"),
            ("alias_size_in_bytes", "alias_bytes"),
        ):
            v = getattr(ma, attr, None)
            if v is not None:
                out[key] = int(v)
        for attr, key in (
            ("host_temp_size_in_bytes", "host_temp_bytes"),
            ("host_argument_size_in_bytes", "host_argument_bytes"),
            ("host_output_size_in_bytes", "host_output_bytes"),
        ):
            v = getattr(ma, attr, None)
            if v:  # host figures are 0 unless offload is active
                out[key] = int(v)
        return out
    except Exception:  # noqa: BLE001 — diagnostics must never fail a run
        return {}


def train_memory_estimate(
    *,
    seq_len: int,
    dim: int,
    depth: int,
    heads: int,
    vocab: int,
    n_params: int,
    batch: int = 1,
    ff_mult: int = 4,
    dtype_bytes: int = 2,
    ff_chunk_size: int | None = None,
    loss_chunk_size: int | None = None,
    remat_policy: str | None = None,
    offload_opt_state: bool = False,
    shard_opt_data: int = 1,
    seq_shards: int = 1,
    compute_dtype: str | None = None,
) -> dict[str, Any]:
    """Analytic per-chip peak-HBM model of one rematted train step.

    The measured truth is ``compiled_memory()`` of the actual executable;
    this formula exists so a config can be sanity-checked against a
    chip's HBM before spending chip time on it.  Terms (per chip,
    sequence split ``seq_shards``-ways):

    - params: weights (model dtype) + Adam moments (2x f32) + f32 grads,
      moments dropped from HBM when ``offload_opt_state``, divided
      ``shard_opt_data``-ways when ZeRO-1 sharding spreads them over the
      data axes (``make_train_step(shard_opt_state=True)``; pass the
      full data-parallel world — both tiers of a hierarchical mesh);
    - saved per layer: the two rematted block inputs ``2*(b, n, dim)``,
      plus the policy's keeps (``save_attn``: ``(b, n, dim)`` out +
      f32 ``(b, h, n)`` lse; ``offload_attn`` keeps those on host);
    - transient peak: the largest single recompute working set —
      the FFN intermediate ``(b, n_or_chunk, mult*dim)`` (THE term
      ``ff_chunk_size`` shrinks), the CE logits ``(b, n_or_chunk, vocab)``
      f32 (``loss_chunk_size``), and the flash workspace (bucket-local,
      negligible at these scales).
    """
    n = seq_len // max(seq_shards, 1)
    b = batch
    act = dtype_bytes

    params_bytes = n_params * act + n_params * 4  # weights + f32 grads
    opt_bytes = (
        0 if offload_opt_state
        else 2 * n_params * 4 // max(int(shard_opt_data), 1)
    )
    saved = 2 * b * n * dim * act  # the two block inputs per layer
    policy = remat_policy or "nothing_saveable"
    if policy in ("save_attn", "save_attn_and_ffn_inputs"):
        saved += b * n * dim * act + b * heads * n * 4  # flash_out + lse
    if policy in ("save_ffn_inputs", "save_attn_and_ffn_inputs"):
        saved += b * n * dim * act  # ffn_in
    saved *= depth

    ff_n = min(ff_chunk_size, n) if ff_chunk_size else n
    ce_n = min(loss_chunk_size, n) if loss_chunk_size else n
    transient = max(
        b * ff_n * ff_mult * dim * act,  # FFN intermediate (+grad twin)
        b * ce_n * vocab * 4,  # CE logits in f32
    ) * 2  # forward value + its cotangent live together in backward

    total = params_bytes + opt_bytes + saved + transient
    # the attention matmul feed (per layer, transient): one q/k/v copy at
    # the compute operand width — int8 quarters/halves these (PR 13,
    # docs/precision.md) while the f32 online-softmax accumulator state
    # is INVARIANT (the contract the precision auditor proves); reported
    # as dedicated keys, not folded into the peak (the FFN/CE transients
    # above dominate it at every modeled shape)
    operand_bytes = 1 if compute_dtype == "int8" else act
    attn_operand_bytes = 3 * b * n * dim * operand_bytes
    attn_accumulator_bytes = b * n * (dim + 2 * heads) * 4
    return {
        "peak_hbm_bytes": int(total),
        "peak_hbm_gb": round(total / 2**30, 3),
        "params_bytes": int(params_bytes + opt_bytes),
        "saved_activation_bytes": int(saved),
        "transient_bytes": int(transient),
        "compute_dtype": compute_dtype,
        "attn_operand_bytes": int(attn_operand_bytes),
        "attn_accumulator_bytes": int(attn_accumulator_bytes),
    }


def ring_comms_accounting(
    *,
    ring_size: int,
    seq_len: int,
    kv_heads: int,
    dim_head: int,
    ulysses_size: int = 1,
    heads: int | None = None,
    dtype_bytes: int = 2,
    batch: int = 1,
    depth: int = 1,
    passes: int | None = None,
    causal: bool = True,
    peak_tflops: float | None = None,
    ici_gbps: float | None = None,
    counter_rotate: bool = False,
    hop_compression: str | None = None,
    compute_dtype: str | None = None,
    impl: str = "scan",
) -> dict[str, Any]:
    """Topology-aware per-step communication accounting for a
    (ring x ulysses) sequence-parallel factoring (TASP, arXiv 2509.26541).

    All numbers are analytic — derived from shapes and the mesh factoring,
    so they cost nothing to log every step:

    - ``ring_hops`` — inter-device transfers in one attention call's
      latency chain: ``passes - 1`` (the last hop's rotation is elided).
      The pure-ring equivalent at the same world is
      ``ring_size * ulysses_size - 1`` (``pure_ring_hops``) — PR 3's
      "ulysses x fewer hops" claim as a logged number.
    - ``hop_bytes`` — K+V bytes ppermuted per hop per device (the ring
      circulates kv-head-sized blocks of the post-all-to-all chunk).
      With ``hop_compression="int8"`` the payload is int8 values + four
      bitcast f32 scale bytes per ``(head, token)`` row, so this shrinks
      ``dtype_bytes * dim_head / (dim_head + 4)``-fold (~3.8x from f32 at
      d=64; the contract ``analysis/contracts.py`` pins the same formula
      against traced payloads).
    - ``ring_bytes_per_step`` — per device, forward only; backward
      circulates (k, v) plus f32 (dk, dv) accumulators (~3x with default
      ``dkv_dtype``), reported as ``ring_bytes_per_step_bwd``.
    - ``a2a_bytes_per_step`` — Ulysses leg: q in + out back per device
      (kv rides :func:`~..parallel.ulysses.kv_head_reshard`'s all-gather,
      counted as ``a2a_kv_bytes``).
    - ``hop_overlap_fraction`` — analytic per-hop compute time at peak
      over max(compute, transfer at ICI bandwidth): 1.0 means the hop's
      flash compute fully hides the transfer (the overlap the reference
      lacks); < 1.0 means the ring is transfer-bound at these shapes.

    ``counter_rotate=True`` accounts the TokenRing schedule
    (``parallel/ring.py::_counter_fwd``): the forward alternates Q-pack
    rotations (f32 ``[q | acc | m | l]``, reported as ``q_pack_bytes``)
    one ring direction with KV rotations the other, plus one out/lse
    catch-up; the backward circulates only the q-side pack with KV and
    dKV resident.  Extra keys:

    - ``fwd_collectives`` / ``bwd_collectives`` — ppermutes per attention
      call (baseline ``passes - 1`` / ``2 * (passes - 1) + 1``; counter
      ``passes`` / ``passes`` — one extra forward, repaid in backward).
    - ``fwd_link_direction_bytes`` — the busier ICI direction's forward
      rotation traffic per device: the counter schedule splits the
      payloads across both full-duplex directions, the baseline loads one.

    ``compute_dtype="int8"`` (PR 13, the quantized QK^T/PV kernel path,
    ``docs/precision.md``) accounts the matmul FEED rather than the wire:
    ``matmul_operand_bytes`` — the q/k/v operand bytes one hop's kernels
    read, at 1 byte/element instead of ``dtype_bytes`` — and the per-hop
    compute time in the overlap model runs at the int8 MXU rate (~2x the
    bf16 peak on v5e/v5p), so ``hop_overlap_fraction`` reflects that a
    quantized hop has HALF the compute available to hide the same
    transfer.  ``accumulator_bytes`` — the f32 ``(acc, m, l)`` state —
    is emitted under every compute_dtype and is invariant by
    construction: the contract the precision auditor proves.

    ``impl`` selects the analytic execution model the numbers describe:

    - ``"scan"`` (default) — one kernel launch per hop
      (``parallel/ring.py``'s scanned/unrolled schedule): ``passes``
      launches per forward, a :data:`DISPATCH_OVERHEAD_S` dispatch term
      per launch, and the per-hop transfer exposed to the launch boundary
      — the overlap denominator is
      ``max(compute, transfer + dispatch)``, because a transfer finishing
      inside the next launch's dispatch window hides nothing.
    - ``"fused"`` — the single-launch fused ring
      (``ops/pallas_ring.py``): the analytic hop count (``ring_hops``,
      the data that must move) is IDENTICAL, but ``kernel_launches``
      drops to 1, ``dispatch_overhead_s`` to 0.0, ``fwd_collectives`` to
      0 (hops are in-kernel remote DMAs, not ppermutes — the contract
      row pins this), and the overlap denominator loses the dispatch
      term: ``max(compute, transfer)``, the model ``overlap_report``
      holds a fused capture against.  ``counter_rotate`` has no fused
      form and raises.
    """
    if heads is None:
        heads = kv_heads
    if impl not in ("scan", "fused"):
        raise ValueError(
            f"ring_comms_accounting: impl={impl!r}; want \"scan\" (one "
            'launch per hop) or "fused" (single-launch fused ring)'
        )
    if impl == "fused" and counter_rotate:
        raise ValueError(
            "ring_comms_accounting: counter_rotate has no fused form — "
            "the alternating Q/KV schedule cannot ride one kernel launch "
            '(parallel/ring.py raises on the same combination)'
        )
    if hop_compression not in (None, "int8"):
        raise ValueError(
            f"ring_comms_accounting: hop_compression={hop_compression!r}; "
            'want None or "int8" (parallel/ring.py accepts the same values)'
        )
    if compute_dtype not in (None, "int8"):
        raise ValueError(
            f"ring_comms_accounting: compute_dtype={compute_dtype!r}; "
            'want None or "int8" (parallel/ring.py accepts the same values)'
        )
    world = ring_size * ulysses_size
    if seq_len % world:
        raise ValueError(
            f"ring_comms_accounting: seq_len {seq_len} must divide over "
            f"the {world}-device sequence-parallel world"
        )
    if passes is None:
        passes = ring_size
    passes = min(passes, ring_size)
    # resident shard and post-all-to-all ring chunk lengths
    n_chunk = seq_len // ring_size  # what the ring circulates / attends
    hops = max(passes - 1, 0)
    pure_ring_hops = max(world - 1, 0)
    # the ring moves the device's kv-head block of the chunk each hop;
    # int8 compression ships 1-byte values + 4 bitcast f32 scale bytes
    # per (head, token) row in the same single payload
    kv_heads_local = max(kv_heads // max(ulysses_size, 1), 1)
    if hop_compression == "int8":
        hop_bytes = 2 * batch * kv_heads_local * n_chunk * (dim_head + 4)
    else:
        hop_bytes = (
            2 * batch * kv_heads_local * n_chunk * dim_head * dtype_bytes
        )
    heads_local = max(heads // max(ulysses_size, 1), 1)
    if counter_rotate:
        # forward: ceil((P-1)/2) Q-pack rotations one direction,
        # floor((P-1)/2) KV rotations the other, + one out/lse catch-up
        # (f32 [out | lse], rides the KV direction as a composed permute)
        q_pack_bytes = 4 * batch * heads_local * n_chunk * (2 * dim_head + 2)
        q_rots = (passes - 1 + 1) // 2 if passes > 1 else 0
        kv_rots = (passes - 1) // 2
        catchup = (
            4 * batch * heads_local * n_chunk * (dim_head + 1)
            if (passes // 2) % max(ring_size, 1)
            else 0
        )
        fwd_collectives = hops + (1 if catchup else 0)
        ring_bytes = q_rots * q_pack_bytes + kv_rots * hop_bytes + catchup
        fwd_dir_bytes = max(
            q_rots * q_pack_bytes, kv_rots * hop_bytes + catchup
        )
        # backward: ONE f32 [q | do | dq | lse | delta] pack circulates;
        # (k, v) and the f32 (dk, dv) accumulators stay resident
        bwd_pack = 4 * batch * heads_local * n_chunk * (3 * dim_head + 2)
        bwd_collectives = passes
        ring_bytes_bwd = hops * bwd_pack + (
            4 * batch * heads_local * n_chunk * dim_head  # dq catch-up
        )
        worst_hop_bytes = max(hop_bytes, q_pack_bytes)
    else:
        q_pack_bytes = 0
        fwd_collectives = hops
        bwd_collectives = max(2 * passes - 1, 0)
        ring_bytes = hops * hop_bytes
        fwd_dir_bytes = ring_bytes  # everything rides one link direction
        # backward recirculates exact-dtype (k, v) + f32 (dk, dv): the
        # compressed forward payload never enters the backward ring
        kv_exact = 2 * batch * kv_heads_local * n_chunk * dim_head
        ring_bytes_bwd = hops * (kv_exact * dtype_bytes + kv_exact * 4)
        worst_hop_bytes = hop_bytes
    n_local = seq_len // world
    a2a_bytes = (
        2 * batch * heads * n_local * dim_head * dtype_bytes
        if ulysses_size > 1 else 0
    )
    a2a_kv_bytes = (
        2 * batch * kv_heads * n_local * dim_head * dtype_bytes
        * max(ulysses_size - 1, 0)
        if ulysses_size > 1 else 0
    )
    # analytic overlap: one full hop's flash compute vs its transfer
    hop_flops = flash_attention_flops(
        n_chunk, n_chunk, heads=heads_local, dim_head=dim_head,
        causal=False, batch=batch,
    )
    if causal:
        hop_flops *= 0.5  # averaged over hops, half the band is masked
    if peak_tflops is None:
        peak_tflops = PEAK_TFLOPS[MODEL_DEVICE_KIND]
    if ici_gbps is None:
        ici_gbps = ICI_GBPS[MODEL_DEVICE_KIND]
    # int8 matmuls run at ~2x the bf16 MXU rate (v5e/v5p), so a quantized
    # hop finishes its compute in half the time — less of it available to
    # hide the same ICI transfer
    matmul_peak = peak_tflops * (2.0 if compute_dtype == "int8" else 1.0)
    compute_s = hop_flops / (matmul_peak * 1e12)
    # the counter schedule's worst rotation is whichever circulating
    # payload is larger (Q-pack vs KV handle); baseline it's the KV hop
    transfer_s = worst_hop_bytes / (ici_gbps * 1e9)
    # launch model: the scan path pays a dispatch boundary per hop that
    # the transfer cannot hide behind; the fused ring has no boundary
    if impl == "fused":
        kernel_launches = 1
        dispatch_overhead_s = 0.0
        exposed_s = transfer_s
    else:
        kernel_launches = passes
        dispatch_overhead_s = DISPATCH_OVERHEAD_S * passes
        exposed_s = transfer_s + DISPATCH_OVERHEAD_S
    overlap = compute_s / max(compute_s, exposed_s, 1e-30)
    # the matmul feed (per hop per device): q read once + the held k/v
    # span, at the compute operand width; the f32 (acc, m, l) state is
    # the invariant the precision auditor pins — never quantized
    operand_bytes = 1 if compute_dtype == "int8" else dtype_bytes
    matmul_operand_bytes = (
        batch * heads_local * n_chunk * dim_head
        + 2 * batch * kv_heads_local * n_chunk * dim_head
    ) * operand_bytes
    accumulator_bytes = 4 * batch * heads_local * n_chunk * (dim_head + 2)
    if impl == "fused":
        # hops are in-kernel remote DMAs: the forward issues ZERO
        # ppermutes (analysis/contracts.py::check_fused_ring_contract);
        # the backward retains the scan-path schedule (exact residuals)
        fwd_collectives = 0
    return {
        "impl": impl,
        "kernel_launches": kernel_launches,
        "dispatch_overhead_s": dispatch_overhead_s,
        "ring_size": ring_size,
        "ulysses_size": ulysses_size,
        "counter_rotate": counter_rotate,
        "hop_compression": hop_compression,
        "compute_dtype": compute_dtype,
        "matmul_operand_bytes": matmul_operand_bytes,
        "accumulator_bytes": accumulator_bytes,
        "ring_hops": hops,
        "pure_ring_hops": pure_ring_hops,
        "ring_hops_per_step": hops * depth * 2,  # fwd + bwd rings
        "hop_bytes": hop_bytes,
        "q_pack_bytes": q_pack_bytes,
        "fwd_collectives": fwd_collectives,
        "bwd_collectives": bwd_collectives,
        "fwd_link_direction_bytes": fwd_dir_bytes * depth,
        "ring_bytes_per_step": ring_bytes * depth,
        "ring_bytes_per_step_bwd": ring_bytes_bwd * depth,
        "a2a_bytes_per_step": a2a_bytes * depth * 2,
        "a2a_kv_bytes": a2a_kv_bytes * depth,
        "hop_overlap_fraction": round(overlap, 4),
    }


# ----------------------------------------------------------------------
# Diagnostic attention summaries (exact, blockwise, opt-in)
# ----------------------------------------------------------------------


def attention_logit_summaries(
    q: Any,
    k: Any,
    *,
    scale: float | None = None,
    causal: bool = False,
    bucket_size: int = 512,
    softclamp_value: float | None = None,
) -> dict[str, Any]:
    """Exact max-logit and mean softmax-entropy of ``softmax(q @ k^T)``.

    Max attention logits drifting up is the canonical early-warning for
    attention-entropy collapse (and the thing ``softclamp_value`` exists
    to bound); row entropy collapsing toward 0 means degenerate one-hot
    attention.  Computed in an online blockwise sweep — memory is one
    ``(nq, bucket)`` tile, never ``(nq, nk)`` — tracking per-row
    ``(m, l, t)`` where ``t = sum exp(s - m) * s`` gives the exact
    entropy ``H = lse - t / l`` without a second pass.

    This is an EXTRA O(n^2 d) pass over scores: run it on a probe batch
    every N steps (or feed the result to ``telemetry.observe``), never
    inside the hot train step.  jit-compatible; differentiation is
    blocked (``stop_gradient``) — these are diagnostics, not losses.

    Returns ``{"max_logit", "softmax_entropy", "softmax_entropy_min"}``
    (f32 scalars: global max, mean row entropy in nats, min row entropy).
    """
    import jax
    import jax.numpy as jnp
    from jax import lax

    from ..ops.attention import MASK_VALUE, softclamp

    q = lax.stop_gradient(q)
    k = lax.stop_gradient(k)
    b, h, nq, d = q.shape
    hk = k.shape[1]
    g = h // hk
    nk = k.shape[2]
    if scale is None:
        scale = d**-0.5
    bk = min(bucket_size, nk)
    while nk % bk:
        bk -= 1
    qg = q.reshape(b, hk, g, nq, d).astype(jnp.float32)
    ks = jnp.moveaxis(
        k.reshape(b, hk, nk // bk, bk, d), 2, 0
    ).astype(jnp.float32)

    rows = jnp.arange(nq)

    def body(carry, xs):
        m, l, t = carry
        k_j, j = xs
        s = jnp.einsum("bhgid,bhjd->bhgij", qg, k_j) * scale
        if softclamp_value is not None:
            s = softclamp(s, softclamp_value)
        visible = None
        if causal:
            cols = j * bk + jnp.arange(bk)
            visible = (
                cols[None, None, None, None, :]
                <= (nk - nq + rows)[None, None, None, :, None]
            )
            s = jnp.where(visible, s, MASK_VALUE)
        m_new = jnp.maximum(m, s.max(-1))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[..., None])
        if visible is not None:
            # exact zeros (a fully-masked tile would otherwise leave
            # p = exp(0) = 1 rows) and s zeroed in the entropy product so
            # MASK_VALUE never multiplies into t (it would overflow f32)
            p = jnp.where(visible, p, 0.0)
            s = jnp.where(visible, s, 0.0)
        l = l * alpha + p.sum(-1)
        t = t * alpha + (p * s).sum(-1)
        return (m_new, l, t), None

    m0 = jnp.full((b, hk, g, nq), MASK_VALUE, jnp.float32)
    l0 = jnp.zeros((b, hk, g, nq), jnp.float32)
    (m, l, t), _ = lax.scan(
        body, (m0, l0, l0), (ks, jnp.arange(nk // bk))
    )
    l = jnp.maximum(l, 1e-30)
    lse = m + jnp.log(l)
    entropy = lse - t / l  # H = lse - E_p[s], exact
    return {
        "max_logit": m.max(),
        "softmax_entropy": entropy.mean(),
        "softmax_entropy_min": entropy.min(),
    }
