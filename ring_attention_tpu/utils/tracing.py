"""Span-based distributed tracing: the cluster's flight-data substrate.

PR 15 made the runtime multi-process (DCN mesh, elastic checkpoints,
watchdog, chaos) but observability stayed per-process: JSONL metrics and
an xplane profiler can say *this* process was slow, never *which rank
stalled the barrier* or *what the whole cluster was doing in the five
seconds before the exit-114*.  This module adds the missing layer:

* **Spans** — named intervals with a ``trace_id`` / ``span_id`` / parent
  hierarchy (thread-local nesting) and **dual timestamps**: a monotonic
  reading for durations (immune to NTP steps) and a wall reading for
  cross-process alignment.  Both are read back-to-back by
  :func:`monotonic_wall`, the one timestamp helper the rest of the
  package routes through (lint rule RA014 enforces the seam).
* **Per-process span files** — ``spans_pNNNNN.jsonl``, O_APPEND exactly
  like ``MetricsLogger``: one :func:`os.write` per line is atomic, so a
  process killed mid-write (chaos, preemption, OOM) leaves at most one
  torn final line, which :func:`read_spans` skips.  A span row is
  emitted when the span *closes*; :meth:`Tracer.instant` rows and
  :meth:`Tracer.flush_open` (called on abort paths before ``os._exit``)
  are durable the moment the write returns.
* **The merger** — :func:`merge_trace_dir` joins every process's file
  into one cluster timeline.  Wall clocks skew across hosts, so each
  process stamps a ``rendezvous`` row as it *exits* a shared coordinator
  barrier (all processes leave a barrier at nearly the same true
  instant); :func:`clock_offsets` averages the per-tag deltas against a
  reference process and the merger adds the offset to every wall time.
* **Renderers** — :func:`render_timeline` (text table),
  :func:`to_chrome_trace` (Chrome trace-event / Perfetto JSON; load in
  ``chrome://tracing`` or https://ui.perfetto.dev), and
  :func:`reconstruct_incident` (a chaos kill or watchdog abort becomes
  an annotated "what was everyone doing" dump — victim, fault window,
  stragglers — from the span files alone).
* **:class:`LatencyHistogram`** — fixed log-spaced buckets (64 buckets,
  1 µs lower edge, x sqrt(2) per bucket) so per-token decode latencies
  recorded on different processes **merge associatively** by elementwise
  add; percentiles are deterministic integers (a bucket upper edge in
  ns), which is what lets ``tests/test_tracing.py`` pin the geometry and
  a fixed sample's read-off as exact values.

Stdlib-only at module level (like telemetry/resilience): tools load this
file standalone by path, and nothing here may import jax.
"""

from __future__ import annotations

import bisect
import contextlib
import json
import math
import os
import threading
import time
import uuid
from typing import Any, Iterator

#: Stamped on every span row.  Bump when a field is renamed or changes
#: meaning; readers skip rows from schemas they don't understand.
TRACE_SCHEMA_VERSION = 1

#: Per-process span file name: ``spans_p00000.jsonl`` etc.
SPAN_FILE_PREFIX = "spans_p"
SPAN_FILE_SUFFIX = ".jsonl"

#: Environment hooks: workers (tests/elastic_worker.py, chaos fleets)
#: opt into tracing by env so the parent needs no per-worker plumbing.
TRACE_DIR_ENV = "RING_ATTN_TRACE_DIR"

#: Instant-row names the incident reconstructor anchors on.
INCIDENT_ANCHORS = ("chaos/kill", "watchdog/abort")


# ----------------------------------------------------------------------
# The timestamp seam (lint RA014 routes host clock reads through here)
# ----------------------------------------------------------------------


def monotonic_wall() -> tuple[float, float]:
    """One ``(monotonic, wall)`` pair read back-to-back — the dual
    timestamp every span and telemetry row carries.  Monotonic orders
    and measures within a process (NTP-step immune); wall aligns across
    processes after :func:`clock_offsets` correction."""
    return time.monotonic(), time.time()


def wall() -> float:
    """Wall-clock seconds (``time.time``) — for mtime comparisons and
    human-facing stamps, never for durations."""
    return time.time()


def monotonic() -> float:
    """Monotonic seconds — for deadlines and durations."""
    return time.monotonic()


def perf_counter() -> float:
    """Highest-resolution monotonic counter — for benchmark timing."""
    return time.perf_counter()


# ----------------------------------------------------------------------
# Tracer
# ----------------------------------------------------------------------


class SpanHandle:
    """The live span a ``with tracer.span(...)`` block holds: carries the
    ids and start stamps; ``set(**attrs)`` attaches attributes that ride
    the row emitted at close."""

    __slots__ = ("span_id", "parent_id", "name", "mono", "wall", "attrs")

    def __init__(self, span_id: int, parent_id: int | None, name: str,
                 mono: float, wall_s: float, attrs: dict[str, Any]):
        self.span_id = span_id
        self.parent_id = parent_id
        self.name = name
        self.mono = mono
        self.wall = wall_s
        self.attrs = attrs

    def set(self, **attrs: Any) -> "SpanHandle":
        self.attrs.update(attrs)
        return self


class _NullHandle:
    """The no-op handle the null tracer yields: accepts attributes and
    drops them."""

    __slots__ = ()
    span_id = None
    parent_id = None
    name = ""

    def set(self, **attrs: Any) -> "_NullHandle":
        return self


_NULL_HANDLE = _NullHandle()


class NullTracer:
    """The unconfigured default: every call is a cheap no-op so library
    instrumentation never needs an ``if tracing:`` guard."""

    enabled = False
    process = 0
    trace_id = ""

    @contextlib.contextmanager
    def span(self, name: str, **attrs: Any) -> Iterator[_NullHandle]:
        yield _NULL_HANDLE

    def instant(self, name: str, **attrs: Any) -> None:
        pass

    def rendezvous(self, tag: str) -> None:
        pass

    def flush_open(self, reason: str = "") -> None:
        pass

    def last_spans(self, n: int = 32) -> list[dict[str, Any]]:
        return []

    def close(self) -> None:
        pass


NULL = NullTracer()


class Tracer:
    """Per-process span writer.

    One O_APPEND fd per process (``spans_pNNNNN.jsonl``); every emitted
    row is a single atomic :func:`os.write` so concurrent threads
    interleave whole lines and a kill tears at most the final line.
    Span rows are emitted at close (start stamps + duration); open spans
    live in memory until then — :meth:`flush_open` persists them with
    ``kind="open"`` on abort paths, and :meth:`last_spans` hands the
    recent window (open + closed) to ``FlightRecorder.dump``.

    A write failure (full disk) drops the row and counts it in
    ``dropped`` — tracing must never take down the run it observes.
    """

    enabled = True

    def __init__(self, directory: str | os.PathLike, *, process: int = 0,
                 trace_id: str | None = None, keep: int = 256) -> None:
        self.directory = os.fspath(directory)
        os.makedirs(self.directory, exist_ok=True)
        self.process = int(process)
        self.trace_id = trace_id or uuid.uuid4().hex[:16]
        self.path = os.path.join(
            self.directory,
            f"{SPAN_FILE_PREFIX}{self.process:05d}{SPAN_FILE_SUFFIX}",
        )
        self._fd = os.open(
            self.path, os.O_WRONLY | os.O_CREAT | os.O_APPEND, 0o644
        )
        self._lock = threading.Lock()
        self._local = threading.local()
        self._n = 0
        self._open: dict[int, SpanHandle] = {}
        self._recent: list[dict[str, Any]] = []
        self._keep = max(int(keep), 1)
        self.dropped = 0
        mono, wall_s = monotonic_wall()
        self._emit({
            "kind": "process", "name": "process", "span": self._next_id(),
            "parent": None, "mono": mono, "wall": wall_s,
            "attrs": {"pid": os.getpid()},
        })

    # -- plumbing ------------------------------------------------------

    def _next_id(self) -> int:
        with self._lock:
            self._n += 1
            return self._n

    def _stack(self) -> list[SpanHandle]:
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
        return stack

    def _emit(self, row: dict[str, Any]) -> None:
        full = {
            "schema": TRACE_SCHEMA_VERSION,
            "trace": self.trace_id,
            "proc": self.process,
            **row,
        }
        data = (json.dumps(full, sort_keys=True) + "\n").encode()
        with self._lock:
            try:
                os.write(self._fd, data)  # O_APPEND: one atomic line
            except OSError:
                self.dropped += 1
                return
            self._recent.append(full)
            if len(self._recent) > self._keep:
                del self._recent[: len(self._recent) - self._keep]

    # -- the span API --------------------------------------------------

    @contextlib.contextmanager
    def span(self, name: str, **attrs: Any) -> Iterator[SpanHandle]:
        """Open a span; emitted as one row when the block exits.  An
        exception escaping the block stamps ``error=<type name>`` before
        re-raising (a barrier timeout becomes a visible straggler span,
        not a vanished one)."""
        stack = self._stack()
        parent = stack[-1].span_id if stack else None
        mono, wall_s = monotonic_wall()
        handle = SpanHandle(
            self._next_id(), parent, name, mono, wall_s, dict(attrs)
        )
        stack.append(handle)
        with self._lock:
            self._open[handle.span_id] = handle
        try:
            yield handle
        except BaseException as e:
            handle.attrs.setdefault("error", type(e).__name__)
            raise
        finally:
            stack.pop()
            with self._lock:
                self._open.pop(handle.span_id, None)
            dur = time.monotonic() - handle.mono
            self._emit({
                "kind": "span", "name": handle.name,
                "span": handle.span_id, "parent": handle.parent_id,
                "mono": handle.mono, "wall": handle.wall,
                "dur": round(dur, 6), "attrs": handle.attrs,
            })

    def instant(self, name: str, **attrs: Any) -> None:
        """Emit a zero-duration event row immediately (durable before
        any subsequent ``os._exit`` — the chaos kill points rely on
        this)."""
        stack = self._stack()
        parent = stack[-1].span_id if stack else None
        mono, wall_s = monotonic_wall()
        self._emit({
            "kind": "instant", "name": name, "span": self._next_id(),
            "parent": parent, "mono": mono, "wall": wall_s,
            "attrs": dict(attrs),
        })

    def rendezvous(self, tag: str) -> None:
        """Stamp a clock-alignment row: call as this process *exits* a
        shared coordinator barrier — every process leaves the same
        barrier at nearly the same true instant, which is what
        :func:`clock_offsets` needs to cancel wall-clock skew."""
        mono, wall_s = monotonic_wall()
        self._emit({
            "kind": "rendezvous", "name": "rendezvous",
            "span": self._next_id(), "parent": None,
            "mono": mono, "wall": wall_s, "attrs": {"tag": tag},
        })

    def flush_open(self, reason: str = "") -> None:
        """Persist every currently-open span with ``kind="open"`` and
        its duration so far.  Abort paths (watchdog, preemption) call
        this right before ``os._exit`` so the timeline shows what was
        in flight when the process died."""
        with self._lock:
            pending = sorted(self._open.values(), key=lambda h: h.span_id)
        now = time.monotonic()
        for handle in pending:
            self._emit({
                "kind": "open", "name": handle.name,
                "span": handle.span_id, "parent": handle.parent_id,
                "mono": handle.mono, "wall": handle.wall,
                "dur": round(now - handle.mono, 6),
                "attrs": {**handle.attrs,
                          **({"flush": reason} if reason else {})},
            })

    def last_spans(self, n: int = 32) -> list[dict[str, Any]]:
        """The most recent ``n`` emitted rows plus every still-open span
        (as ``kind="open"`` dicts) — the local timeline context a
        ``FlightRecorder`` incident dump carries."""
        now = time.monotonic()
        with self._lock:
            recent = list(self._recent[-n:])
            open_rows = [
                {
                    "schema": TRACE_SCHEMA_VERSION, "trace": self.trace_id,
                    "proc": self.process, "kind": "open",
                    "name": h.name, "span": h.span_id,
                    "parent": h.parent_id, "mono": h.mono, "wall": h.wall,
                    "dur": round(now - h.mono, 6), "attrs": dict(h.attrs),
                }
                for h in sorted(self._open.values(), key=lambda h: h.span_id)
            ]
        return (recent + open_rows)[-max(n, len(open_rows)):]

    def close(self) -> None:
        with self._lock:
            if self._fd is not None:
                try:
                    os.close(self._fd)
                except OSError:
                    pass
                self._fd = None  # type: ignore[assignment]


# ----------------------------------------------------------------------
# Process-global tracer registry
# ----------------------------------------------------------------------

_REGISTRY_LOCK = threading.Lock()
_TRACER: Tracer | None = None


def configure(directory: str | os.PathLike, *, process: int = 0,
              trace_id: str | None = None, keep: int = 256) -> Tracer:
    """Install the process-global tracer (replacing any previous one)."""
    global _TRACER
    with _REGISTRY_LOCK:
        if _TRACER is not None:
            _TRACER.close()
        _TRACER = Tracer(
            directory, process=process, trace_id=trace_id, keep=keep
        )
        return _TRACER


def configure_from_env(process: int | None = None) -> Tracer | None:
    """Install a tracer when ``RING_ATTN_TRACE_DIR`` is set (the worker
    opt-in: chaos fleets pass it via ``extra_env``); returns None and
    changes nothing otherwise."""
    directory = os.environ.get(TRACE_DIR_ENV)
    if not directory:
        return None
    if process is None:
        process = int(os.environ.get("RING_ATTN_TRACE_PROC", "0"))
    return configure(directory, process=process)


def get_tracer() -> Tracer | NullTracer:
    """The installed tracer, or the no-op :data:`NULL` when none is."""
    return _TRACER if _TRACER is not None else NULL


def shutdown() -> None:
    """Close and uninstall the process-global tracer (tests)."""
    global _TRACER
    with _REGISTRY_LOCK:
        if _TRACER is not None:
            _TRACER.close()
            _TRACER = None


# ----------------------------------------------------------------------
# Reading + merging
# ----------------------------------------------------------------------


def read_spans(path: str | os.PathLike) -> list[dict[str, Any]]:
    """Parse one span file, skipping blank/torn/unknown-schema lines —
    a process killed mid-write tears at most the final line, and that
    must never take the whole timeline down with it."""
    rows: list[dict[str, Any]] = []
    with open(path, "r", encoding="utf-8", errors="replace") as fh:
        for line in fh:
            line = line.strip()
            if not line:
                continue
            try:
                row = json.loads(line)
            except ValueError:
                continue  # torn tail from a killed writer
            if (not isinstance(row, dict)
                    or row.get("schema") != TRACE_SCHEMA_VERSION):
                continue
            rows.append(row)
    return rows


def load_trace_dir(directory: str | os.PathLike) -> dict[int, list[dict]]:
    """Every process's rows, keyed by process index (from the file
    name: ``spans_p00001.jsonl`` -> 1)."""
    by_proc: dict[int, list[dict]] = {}
    for name in sorted(os.listdir(directory)):
        if not (name.startswith(SPAN_FILE_PREFIX)
                and name.endswith(SPAN_FILE_SUFFIX)):
            continue
        stem = name[len(SPAN_FILE_PREFIX):-len(SPAN_FILE_SUFFIX)]
        try:
            proc = int(stem)
        except ValueError:
            continue
        rows = read_spans(os.path.join(directory, name))
        if rows:
            by_proc[proc] = rows
    return by_proc


def clock_offsets(by_proc: dict[int, list[dict]],
                  reference: int | None = None) -> dict[int, float]:
    """Seconds to ADD to each process's wall stamps to align them with
    the reference process (lowest index by default).

    Uses the shared-rendezvous model: every process emits a
    ``rendezvous`` row with the same tag as it exits the same
    coordinator barrier, so for each shared tag the reference's wall
    minus this process's wall estimates the skew; tags are averaged.
    Processes with no shared rendezvous get offset 0.0 (wall clocks on
    one host are already close)."""
    if not by_proc:
        return {}
    ref = min(by_proc) if reference is None else reference
    marks: dict[int, dict[str, float]] = {}
    for proc, rows in by_proc.items():
        marks[proc] = {}
        for row in rows:
            if row.get("kind") == "rendezvous":
                tag = (row.get("attrs") or {}).get("tag")
                if isinstance(tag, str):
                    marks[proc][tag] = float(row["wall"])
    offsets = {proc: 0.0 for proc in by_proc}
    ref_marks = marks.get(ref, {})
    for proc in by_proc:
        if proc == ref:
            continue
        shared = sorted(set(ref_marks) & set(marks[proc]))
        if shared:
            deltas = [ref_marks[t] - marks[proc][t] for t in shared]
            offsets[proc] = sum(deltas) / len(deltas)
    return offsets


def merge_spans(by_proc: dict[int, list[dict]],
                reference: int | None = None) -> dict[str, Any]:
    """The cluster timeline: every row stamped with its corrected start
    time ``t`` (reference-process wall clock) and ``t_end`` for spans,
    sorted by ``t``.  Returns ``{"spans", "offsets", "processes",
    "t0"}`` where ``t0`` is the earliest corrected time (the timeline
    zero every renderer subtracts)."""
    offsets = clock_offsets(by_proc, reference)
    merged: list[dict[str, Any]] = []
    for proc, rows in by_proc.items():
        off = offsets.get(proc, 0.0)
        for row in rows:
            out = dict(row)
            out["proc"] = proc
            out["t"] = float(row["wall"]) + off
            dur = row.get("dur")
            if isinstance(dur, (int, float)):
                out["t_end"] = out["t"] + float(dur)
            merged.append(out)
    merged.sort(key=lambda r: (r["t"], r["proc"], r.get("span", 0)))
    return {
        "spans": merged,
        "offsets": offsets,
        "processes": sorted(by_proc),
        "t0": merged[0]["t"] if merged else 0.0,
    }


def merge_trace_dir(directory: str | os.PathLike,
                    reference: int | None = None) -> dict[str, Any]:
    """:func:`load_trace_dir` + :func:`merge_spans` in one call."""
    return merge_spans(load_trace_dir(directory), reference)


# ----------------------------------------------------------------------
# Renderers
# ----------------------------------------------------------------------


def _fmt_attrs(attrs: dict[str, Any] | None, limit: int = 60) -> str:
    if not attrs:
        return ""
    text = " ".join(f"{k}={attrs[k]}" for k in sorted(attrs))
    return text if len(text) <= limit else text[: limit - 1] + "…"


def render_timeline(merged: dict[str, Any], *,
                    limit: int | None = None) -> str:
    """The cluster timeline as a text table (one row per span/event,
    times relative to the merged ``t0``)."""
    spans = merged["spans"]
    if limit is not None and limit > 0:
        spans = spans[-limit:]
    t0 = merged.get("t0", 0.0)
    lines = [
        f"cluster timeline: {len(spans)} rows, "
        f"processes {merged.get('processes', [])}, "
        f"offsets {{{', '.join(f'{p}: {o:+.4f}s' for p, o in sorted(merged.get('offsets', {}).items()))}}}",
        f"{'t(s)':>10}  {'proc':>4}  {'kind':<10} {'dur(ms)':>9}  name / attrs",
    ]
    for row in spans:
        dur = row.get("dur")
        dur_txt = f"{dur * 1e3:9.2f}" if isinstance(dur, (int, float)) else " " * 9
        attrs = _fmt_attrs(row.get("attrs"))
        name = row.get("name", "?")
        lines.append(
            f"{row['t'] - t0:10.4f}  p{row['proc']:<3}  "
            f"{row.get('kind', '?'):<10} {dur_txt}  {name}"
            + (f"  [{attrs}]" if attrs else "")
        )
    return "\n".join(lines)


def to_chrome_trace(merged: dict[str, Any]) -> dict[str, Any]:
    """Chrome trace-event JSON (the Perfetto/chrome://tracing format):
    each process is a pid lane, spans are complete ("X") events, instants
    are "i" events, all in microseconds relative to the merged t0."""
    t0 = merged.get("t0", 0.0)
    events: list[dict[str, Any]] = []
    for proc in merged.get("processes", []):
        events.append({
            "ph": "M", "name": "process_name", "pid": proc, "tid": 0,
            "args": {"name": f"process {proc}"},
        })
    for row in merged["spans"]:
        ts = int(round((row["t"] - t0) * 1e6))
        base = {
            "name": row.get("name", "?"), "cat": row.get("kind", "span"),
            "pid": row["proc"], "tid": 0, "ts": ts,
            "args": dict(row.get("attrs") or {}),
        }
        dur = row.get("dur")
        if isinstance(dur, (int, float)):
            events.append({**base, "ph": "X",
                           "dur": int(round(float(dur) * 1e6))})
        else:
            events.append({**base, "ph": "i", "s": "p"})
    return {"traceEvents": events, "displayTimeUnit": "ms"}


def reconstruct_incident(merged: dict[str, Any], *,
                         window_s: float = 5.0) -> str | None:
    """The annotated "what was the cluster doing" dump for the last
    incident in the timeline, from span files alone.

    Anchors on the final ``chaos/kill`` / ``watchdog/abort`` instant (a
    flushed-open or errored span marks the victim too, but the kill
    instants are authoritative).  The reconstruction names the victim
    process, the fault window (``chaos/armed`` -> kill), every
    barrier/lock wait that overlapped the incident (the stragglers —
    a ``BarrierTimeout``-errored wait is the survivor watching the
    victim die), and the full timeline slice of the ``window_s``
    seconds before the anchor.  Returns None when no anchor exists.
    """
    spans = merged["spans"]
    t0 = merged.get("t0", 0.0)
    anchors = [r for r in spans
               if r.get("kind") == "instant"
               and r.get("name") in INCIDENT_ANCHORS]
    if not anchors:
        return None
    anchor = anchors[-1]
    victim = anchor["proc"]
    at = anchor["t"]
    attrs = anchor.get("attrs") or {}
    lines = [
        f"INCIDENT: {anchor['name']} on process {victim} "
        f"at t=+{at - t0:.4f}s"
        + (f"  [{_fmt_attrs(attrs)}]" if attrs else ""),
    ]
    armed = [r for r in spans
             if r["proc"] == victim and r.get("name") == "chaos/armed"
             and r["t"] <= at]
    if armed and anchor["name"] == "chaos/kill":
        arm = armed[-1]
        lines.append(
            f"fault window: armed at t=+{arm['t'] - t0:.4f}s "
            f"[{_fmt_attrs(arm.get('attrs'))}] -> kill at "
            f"t=+{at - t0:.4f}s ({at - arm['t']:.4f}s armed)"
        )
    waits = [
        r for r in spans
        if r.get("kind") in ("span", "open")
        and (r.get("name", "").startswith(("barrier/", "lock/"))
             or "barrier" in r.get("name", ""))
        and r.get("t_end", r["t"]) >= at - window_s
    ]
    for r in waits:
        err = (r.get("attrs") or {}).get("error")
        mark = f" -> {err}" if err else ""
        who = "STRAGGLER WATCH" if (err or r.get("kind") == "open") else "wait"
        lines.append(
            f"{who}: process {r['proc']} {r['name']} "
            f"waited {float(r.get('dur') or 0.0) * 1e3:.1f} ms "
            f"(t=+{r['t'] - t0:.4f}s){mark}"
            + (f"  [{_fmt_attrs(r.get('attrs'))}]" if r.get("attrs") else "")
        )
    tail = [r for r in spans if at - window_s <= r["t"] <= at + window_s]
    lines.append(f"timeline (±{window_s:.1f}s around the incident):")
    lines.append(render_timeline({
        "spans": tail, "offsets": merged.get("offsets", {}),
        "processes": merged.get("processes", []), "t0": t0,
    }))
    return "\n".join(lines)


# ----------------------------------------------------------------------
# Mergeable fixed-bucket latency histogram
# ----------------------------------------------------------------------

#: 64 log-spaced buckets: lower edges 1000 ns * sqrt(2)^i — 1 µs up to
#: ~3040 s, ~41 buckets per factor-of-1e6.  The edges are FIXED integers
#: (never derived from data) so histograms recorded on any process in
#: any order merge by elementwise add — associative and commutative —
#: and percentiles are deterministic ints the perf gate can pin exactly.
HIST_BUCKETS = 64
_BASE_NS = 1000
BUCKET_BOUNDS_NS: tuple[int, ...] = tuple(
    int(_BASE_NS * 2 ** (i / 2)) for i in range(HIST_BUCKETS)
)
#: The overflow bucket's reported value (and the last bucket's upper
#: edge): one more sqrt(2) step past the final lower edge.
OVERFLOW_EDGE_NS = int(_BASE_NS * 2 ** (HIST_BUCKETS / 2))
HIST_SCALE = f"ns-pow2half-{HIST_BUCKETS}"


class LatencyHistogram:
    """Fixed-bucket latency histogram with associative merge.

    ``record`` costs one bisect; ``merge`` is elementwise integer add;
    ``percentile_ns(q)`` returns the upper edge (ns, int) of the bucket
    holding the ceil(q% * n)-th sample — a deterministic function of the
    counts, which is what makes p50/p95/p99 pinnable as exact gate
    signals and identical regardless of which process recorded what.
    """

    __slots__ = ("counts", "n", "sum_ns")

    def __init__(self) -> None:
        self.counts = [0] * (HIST_BUCKETS + 1)  # [...buckets..., overflow]
        self.n = 0
        self.sum_ns = 0

    def record(self, seconds: float) -> None:
        self.record_ns(int(seconds * 1e9))

    def record_ns(self, ns: int) -> None:
        ns = max(int(ns), 0)
        b = bisect.bisect_right(BUCKET_BOUNDS_NS, ns) - 1
        self.counts[max(b, 0)] += 1  # sub-µs readings land in bucket 0
        self.n += 1
        self.sum_ns += ns

    def merge(self, other: "LatencyHistogram") -> "LatencyHistogram":
        """Elementwise add ``other`` into self (associative; returns
        self for chaining)."""
        if len(other.counts) != len(self.counts):
            raise ValueError(
                f"LatencyHistogram.merge: bucket count mismatch "
                f"({len(other.counts)} != {len(self.counts)})"
            )
        for i, c in enumerate(other.counts):
            self.counts[i] += c
        self.n += other.n
        self.sum_ns += other.sum_ns
        return self

    def percentile_ns(self, q: float) -> int:
        """Upper bucket edge (ns) covering the ceil(q% * n)-th smallest
        sample; 0 when empty.  Overflow samples report
        :data:`OVERFLOW_EDGE_NS`."""
        if self.n == 0:
            return 0
        rank = max(1, math.ceil(q / 100.0 * self.n))
        cum = 0
        for i, c in enumerate(self.counts):
            cum += c
            if cum >= rank:
                if i + 1 < HIST_BUCKETS:
                    return BUCKET_BOUNDS_NS[i + 1]
                return OVERFLOW_EDGE_NS
        return OVERFLOW_EDGE_NS  # unreachable: cum == n covers rank

    def percentile_ms(self, q: float) -> float:
        return self.percentile_ns(q) / 1e6

    def mean_ms(self) -> float:
        return (self.sum_ns / self.n) / 1e6 if self.n else 0.0

    def to_dict(self) -> dict[str, Any]:
        """JSON form with sparse counts (most of 65 buckets are empty)."""
        return {
            "scale": HIST_SCALE,
            "n": self.n,
            "sum_ns": self.sum_ns,
            "counts": {str(i): c for i, c in enumerate(self.counts) if c},
        }

    @classmethod
    def from_dict(cls, data: dict[str, Any]) -> "LatencyHistogram":
        scale = data.get("scale")
        if scale != HIST_SCALE:
            raise ValueError(
                f"LatencyHistogram.from_dict: scale {scale!r} != "
                f"{HIST_SCALE!r} — merging across bucket layouts would "
                f"silently mis-bin"
            )
        hist = cls()
        for key, c in (data.get("counts") or {}).items():
            hist.counts[int(key)] = int(c)
        hist.n = int(data.get("n", sum(hist.counts)))
        hist.sum_ns = int(data.get("sum_ns", 0))
        return hist


__all__ = [
    "TRACE_SCHEMA_VERSION",
    "SPAN_FILE_PREFIX",
    "TRACE_DIR_ENV",
    "INCIDENT_ANCHORS",
    "monotonic_wall",
    "wall",
    "monotonic",
    "perf_counter",
    "SpanHandle",
    "NullTracer",
    "NULL",
    "Tracer",
    "configure",
    "configure_from_env",
    "get_tracer",
    "shutdown",
    "read_spans",
    "load_trace_dir",
    "clock_offsets",
    "merge_spans",
    "merge_trace_dir",
    "render_timeline",
    "to_chrome_trace",
    "reconstruct_incident",
    "HIST_BUCKETS",
    "BUCKET_BOUNDS_NS",
    "OVERFLOW_EDGE_NS",
    "HIST_SCALE",
    "LatencyHistogram",
]
