#!/usr/bin/env python
"""Render a profiler capture and/or a telemetry run into tables.

``--xprof DIR`` (``benchmarks/run.py --trace 1`` leaves a capture under
``benchmarks/.trace/<cell>``, ``utils.profiling.trace`` anywhere) prints,
per program or per ``--window``, every device millisecond by layer and
pass with ``other`` and ``idle`` rows, the capture's clock-offset bounds and
the idle row split by what the host was doing where they allow it, the
host's own dispatch and fetch time, the same window by stage with each
stage's largest instructions (``utils.profiling.layer_breakdown``); then
the whole capture's per-stage table,
the per-hop compute-vs-transfer timeline and the MEASURED overlap, beside
the analytic ``hop_overlap_fraction`` of the metrics rows when both exist
(disagreement beyond ``--overlap-tolerance`` is a FINDING line).

A metrics directory (or JSONL file) written by ``MetricsLogger``
(``docs/observability.md``) prints the run summary, the per-metric table
(last / mean / p50 / p95) and the comms accounting; ``--diff OLD NEW``
compares two runs.  Usage::

  python tools/trace_report.py --xprof benchmarks/.trace/sc2-3b.train-64k
  python tools/trace_report.py --xprof DIR --window bench/token,bench/fetch --per bench/token
  python tools/trace_report.py /tmp/m [--xprof /tmp/profile]
  python tools/trace_report.py --diff /tmp/m_before /tmp/m_after
"""

from __future__ import annotations

import argparse
import os
import sys
from collections import defaultdict

# metric columns the table summarizes, in display order (other numeric
# fields are appended alphabetically)
PREFERRED = [
    "loss",
    "grad_norm",
    "tokens_per_sec",
    "steps_per_sec",
    "step_ms_p50",
    "step_ms_p95",
    "mfu",
]

# comms-accounting + compiled-memory fields echoed as a static block
# (they do not vary per step — one line each beats 5 columns of constants)
ACCOUNTING = [
    "ring_size",
    "ulysses_size",
    "ring_hops",
    "pure_ring_hops",
    "ring_hops_per_step",
    "hop_bytes",
    "ring_bytes_per_step",
    "ring_bytes_per_step_bwd",
    "a2a_bytes_per_step",
    "hop_overlap_fraction",
    # compiled peak-memory accounting of the train step (telemetry
    # .compiled_memory — temp_bytes is the scratch high-water mark the
    # ff_chunk_size / loss_chunk_size / remat-policy knobs shrink)
    "temp_bytes",
    "argument_bytes",
    "output_bytes",
    "alias_bytes",
    "host_temp_bytes",
    "host_argument_bytes",
    "host_output_bytes",
]


def _utils(name: str):
    """``ring_attention_tpu.utils.<name>``, imported on first use (the
    package pulls in jax; ``--help`` does not need it)."""
    import importlib

    try:  # prefer the installed package (pip install -e .)
        import ring_attention_tpu  # noqa: F401
    except ModuleNotFoundError:  # running from a source checkout, any cwd
        sys.path.insert(
            0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        )
    return importlib.import_module(f"ring_attention_tpu.utils.{name}")


def _read_rows(path: str) -> list[dict]:
    """The library's own reader (``telemetry.read_metrics`` — the one the
    killed-writer tests pin)."""
    return _utils("telemetry").read_metrics(path)


def _profiling():
    return _utils("profiling")


def _percentile(values: list[float], q: float) -> float:
    """The library's own percentile (``profiling.percentile`` — the one
    the timer and the timeline use), so the three tables can never
    disagree on interpolation."""
    return _profiling().percentile(values, q)


def _fmt(x: float) -> str:
    if x == 0:
        return "0"
    if abs(x) >= 1e5 or abs(x) < 1e-3:
        return f"{x:.3e}"
    return f"{x:,.4f}".rstrip("0").rstrip(".")


def _numeric_columns(rows: list[dict]) -> dict[str, list[float]]:
    numeric: dict[str, list[float]] = defaultdict(list)
    for r in rows:
        if "event" in r:
            continue
        for key, val in r.items():
            if key in ("schema", "step", "time") or isinstance(val, bool):
                continue
            if isinstance(val, (int, float)):
                numeric[key].append(float(val))
    return numeric


def metrics_report(rows: list[dict], out: list[str]) -> None:
    metric_rows = [r for r in rows if "event" not in r]
    events = [r for r in rows if "event" in r]
    steps = [r.get("step") for r in metric_rows if "step" in r]
    schemas = sorted({r.get("schema") for r in rows if "schema" in r})
    out.append(
        f"rows: {len(metric_rows)} metric + {len(events)} event | "
        f"steps {min(steps) if steps else '-'}..{max(steps) if steps else '-'}"
        f" | schema {','.join(str(s) for s in schemas) or '-'}"
    )
    for ev in events:
        kind = ev.get("event")
        detail = ev.get("component") or ev.get("reason") or ""
        out.append(f"  event: {kind} {detail}".rstrip())
    degraded = sum(int(r.get("degraded", 0)) for r in rows)
    if degraded:
        out.append(f"  DEGRADED run: {degraded} kernel-fallback event(s) — "
                   f"see ring_attention_tpu.utils.resilience.degradation")
    if not metric_rows:
        return

    numeric = _numeric_columns(rows)
    acct = [k for k in ACCOUNTING if k in numeric]
    if acct:
        out.append("")
        out.append("comms accounting (analytic, per device)")
        for key in acct:
            out.append(f"  {key:24s} {_fmt(numeric[key][-1])}")

    cols = [k for k in PREFERRED if k in numeric]
    cols += sorted(k for k in numeric if k not in cols and k not in acct)
    out.append("")
    out.append(f"  {'metric':20s} {'last':>12s} {'mean':>12s} "
               f"{'p50':>12s} {'p95':>12s}")
    for key in cols:
        vals = numeric[key]
        out.append(
            f"  {key:20s} {_fmt(vals[-1]):>12s} "
            f"{_fmt(sum(vals) / len(vals)):>12s} "
            f"{_fmt(_percentile(vals, 0.5)):>12s} "
            f"{_fmt(_percentile(vals, 0.95)):>12s}"
        )


def diff_report(old_path: str, new_path: str, out: list[str]) -> None:
    """Side-by-side per-metric comparison of two runs: p50 over each run
    plus delta and percent — the human-facing half of the perf gate."""
    old = _numeric_columns(_read_rows(old_path))
    new = _numeric_columns(_read_rows(new_path))
    out.append(f"diff: OLD={old_path}  NEW={new_path}")
    keys = [k for k in PREFERRED if k in old or k in new]
    keys += sorted((set(old) | set(new)) - set(keys))
    out.append("")
    out.append(f"  {'metric':24s} {'old p50':>12s} {'new p50':>12s} "
               f"{'delta':>12s} {'pct':>8s}")
    for key in keys:
        a = _percentile(old[key], 0.5) if key in old else None
        b = _percentile(new[key], 0.5) if key in new else None
        if a is None or b is None:
            side = "only OLD" if b is None else "only NEW"
            old_s = _fmt(a) if a is not None else "-"
            new_s = _fmt(b) if b is not None else "-"
            out.append(f"  {key:24s} {old_s:>12s} {new_s:>12s} "
                       f"{side:>12s} {'-':>8s}")
            continue
        delta = b - a
        pct = f"{delta / a * 100:+.1f}%" if a else "-"
        out.append(
            f"  {key:24s} {_fmt(a):>12s} {_fmt(b):>12s} "
            f"{_fmt(delta):>12s} {pct:>8s}"
        )


def _pairs(out: list[str], label: str, items) -> None:
    out.append(f"  {label}: " + ", ".join(
        f"{n} {ms:.4f}" for n, ms in sorted(items, key=lambda x: -x[1])))


def layer_report(capture, out: list[str], windows: list, per, chip) -> None:
    """The layer-and-pass table of each window; with none named, of each
    program that took at least 1% of the device's time, per execution."""
    if not windows:
        took: dict[str, int] = defaultdict(int)
        for _, name, _, dur in capture.programs:
            took[name] += dur
        windows = [[n] for n, t in took.items()
                   if t >= 0.01 * sum(took.values())] or [None]
    for window in windows:
        got = _profiling().layer_breakdown(
            capture, window, chip=chip,
            per=per or (window[0] if window else None))
        what = ",".join(window) if window else "all device ops"
        if "note" in got:
            out.append(f"[xprof] {what}: {got['note']}")
            continue
        out += ["", f"layer and pass: {what} x{got['units']}, "
                    f"{got['window_ms']:.3f} ms each, chip {got['chip']} "
                    f"(busy ms by chip: {got['busy_ms_by_chip']})",
                f"  {'layer':24s} {'pass':10s} {'ms':>11s} {'share':>7s} "
                f"{'ops':>8s} {'transfer ms':>12s}"]
        out += [f"  {r['layer']:24s} {r['pass']:10s} {r['ms']:11.4f} "
                f"{100 * r['share']:6.2f}% {r['ops']:8d} "
                f"{r['transfer_ms']:12.4f}" for r in got["rows"]]
        _pairs(out, "other holds", got["other_ops"])
        lower, upper = (
            "none" if b is None else f"{b:+.4f}" for b in got["offset_bounds_ms"])
        idle = (f"  idle {got['rows'][-1]['ms']:.4f} ms; host clock = device "
                f"clock + [{lower}, {upper}] ms")
        if got["idle_activity"] is None:
            # the device's clock and the host's are not known to agree to
            # 0.1 ms: cutting the idle time by host events would print the
            # offset, not the host
            out.append(idle + ": no split by host activity")
        else:
            out.append(idle)
            _pairs(out, "idle by host activity", got["idle_activity"].items())
            out += [f"    {r['ms']:11.4f}  {r['activity']:9s} {r['event']}"
                    for r in got["idle_host"][:12]]
        _pairs(out, "host activity on the host's clock",
               got["host_activity"].items())
        out += ["", f"  stage and pass: {what}",
                f"  {'layer':24s} {'stage':34s} {'pass':10s} {'ms':>11s}  "
                f"largest instructions"]
        out += [f"  {r['layer']:24s} {r['stage']:34s} {r['pass']:10s} "
                f"{r['ms']:11.4f}  " + ", ".join(
                    f"{n} {ms:.3f}" for n, ms in r["top"])
                for r in got["stages"]]
        out.append("  (an instruction is one fusion and has one path, its "
                   "root's: neighbouring stages' sum is firm, their split "
                   "is the compiler's)")


def xprof_report(trace_dir: str, out: list[str], *,
                 analytic: float | None = None,
                 tolerance: float = 0.25,
                 ring_size: int | None = None,
                 windows: list | None = None, per=None, chip=None) -> None:
    """Layer-and-pass tables, per-stage/per-hop device time and measured
    overlap from an xplane capture (``utils/profiling.py``).  ``ring_size``
    folds multi-step captures into per-step hop samples.  Best-effort: an
    unreadable capture is a note, never an error."""
    prof = _profiling()
    capture = prof.read_capture(trace_dir)
    if capture.note:
        out.append(f"[xprof] {capture.note}")
        return
    layer_report(capture, out, windows or [], per, chip)
    report = prof.overlap_report(capture.ops, analytic=analytic,
                                 tolerance=tolerance, ring_size=ring_size)
    timeline = report["timeline"]
    total = timeline["total_busy_ms"] or 1.0
    out.append("")
    out.append(f"per-stage device time ({trace_dir})")
    out.append(f"  {'stage':26s} {'kind':>8s} {'busy ms':>10s} "
               f"{'share':>7s} {'p50 ms':>9s} {'p95 ms':>9s}")
    for row in timeline["stages"]:
        out.append(
            f"  {row['stage']:26s} {row['kind']:>8s} "
            f"{row['busy_ms']:10.3f} {100 * row['busy_ms'] / total:6.1f}% "
            f"{row['p50_ms']:9.3f} {row['p95_ms']:9.3f}"
        )
    if timeline["hops"]:
        out.append("")
        out.append("per-hop timeline (ring schedule)")
        out.append(f"  {'hop':>4s} {'compute ms':>11s} {'transfer ms':>12s} "
                   f"{'samples':>8s}")
        for row in timeline["hops"]:
            out.append(
                f"  {row['hop']:4d} {row['compute_ms']:11.3f} "
                f"{row['transfer_ms']:12.3f} {row['samples']:8d}"
            )
    out.append("")
    out.append(
        f"measured overlap: {report['overlap_fraction']:.3f} "
        f"(transfer {report['transfer_ms']:.3f} ms, compute "
        f"{report['compute_ms']:.3f} ms, overlapped "
        f"{report['overlapped_ms']:.3f} ms)"
    )
    if "analytic_overlap_fraction" in report:
        out.append(
            f"analytic overlap: {report['analytic_overlap_fraction']:.3f} "
            f"(ring_comms_accounting hop_overlap_fraction)"
        )
        if not report["agrees"]:
            out.append(f"FINDING: {report['finding']}")


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(
        description="Render telemetry JSONL (+ optional xprof capture) "
                    "into per-metric / per-stage / per-hop tables"
    )
    ap.add_argument("metrics", nargs="?", default=None,
                    help="metrics directory (holding metrics.jsonl) or a "
                         "JSONL file written by MetricsLogger")
    ap.add_argument("--xprof", default=None,
                    help="profiler capture dir or .xplane.pb[.gz] "
                         "(benchmarks/run.py --trace 1, utils.profiling."
                         "trace): layer-and-pass, per-stage and per-hop "
                         "device-time tables plus the measured "
                         "compute/transfer overlap fraction")
    ap.add_argument("--window", action="append", default=[],
                    help="host event or program name(s), comma-separated, "
                         "whose extent is one layer table (repeatable); "
                         "default: one table per program")
    ap.add_argument("--per", default=None,
                    help="name whose events in the window are the units "
                         "(steps, tokens); default: the window's first name")
    ap.add_argument("--chip", type=int, default=None,
                    help="chip of the layer table (default: the busiest)")
    ap.add_argument("--last", type=int, default=None,
                    help="summarize only the last N metric rows")
    ap.add_argument("--diff", nargs=2, metavar=("OLD", "NEW"), default=None,
                    help="compare two metrics runs: per-metric p50 "
                         "side-by-side with delta and percent columns")
    ap.add_argument("--overlap-tolerance", type=float, default=0.25,
                    help="measured-vs-analytic overlap disagreement beyond "
                         "this is reported as a FINDING (default 0.25)")
    args = ap.parse_args(argv)

    out: list[str] = []
    if args.diff:
        diff_report(args.diff[0], args.diff[1], out)
        print("\n".join(out))
        return 0
    if args.metrics is None and args.xprof is None:
        ap.error("metrics path or --xprof DIR required (or --diff OLD NEW)")

    rows = _read_rows(args.metrics) if args.metrics else []
    if args.last is not None:
        events = [r for r in rows if "event" in r]
        metric = [r for r in rows if "event" not in r][-args.last:]
        rows = events + metric
    out.append(f"trace report: {args.metrics or args.xprof}")
    if args.metrics:
        metrics_report(rows, out)
    if args.xprof:
        # analytic overlap + ring size from the run's own accounting
        # rows, when present
        numeric = _numeric_columns(rows)
        analytic = (
            numeric["hop_overlap_fraction"][-1]
            if numeric.get("hop_overlap_fraction") else None
        )
        ring_size = (
            int(numeric["ring_size"][-1])
            if numeric.get("ring_size") else None
        )
        xprof_report(args.xprof, out, analytic=analytic,
                     tolerance=args.overlap_tolerance,
                     ring_size=ring_size, per=args.per, chip=args.chip,
                     windows=[w.split(",") for w in args.window])
    print("\n".join(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
