#!/usr/bin/env python
"""Perf-observatory regression gate CLI (docs/observability.md §Observatory).

Ingests the repo's benchmark history (``BENCH_r*.json`` + ``docs/hwlogs/
results.jsonl``) plus the committed CPU-signal baseline
(``docs/perf_baseline.json``), collects the current build's CPU
signals — collective fingerprint, analytic hop/byte reference table,
compiled cost/memory of the reference train step — and fails (exit 1)
with one line per regressed series.  Wedge-honest: rounds whose TPU
probe never ran contribute notes, not hardware points, and wedge
frequency is itself reported.

Usage::

  python tools/perf_gate.py --check              # the gate (default)
  python tools/perf_gate.py --check --json       # machine-readable report
  python tools/perf_gate.py --history-only       # no compiles: ingest+trend
  python tools/perf_gate.py --update-baseline    # re-record docs/perf_baseline.json
  python tools/perf_gate.py --check --strategies ring --skip-compiled
                                                 # cheap subset (CI smoke)

Runs on CPU anywhere: the fingerprint needs 8 simulated devices, which
this script forces before the first jax import (like bench.py's
fingerprint worker).
"""

from __future__ import annotations

import argparse
import json
import os
import sys

# must precede the first jax import (the fingerprint compiles per-strategy
# entries over an 8-device simulated mesh)
os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "")
    + " --xla_force_host_platform_device_count=8"
)
os.environ.setdefault("JAX_PLATFORMS", "cpu")

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(
        description="benchmark-history + CPU-signal perf regression gate"
    )
    ap.add_argument("--check", action="store_true",
                    help="run the gate (the default action)")
    ap.add_argument("--json", action="store_true",
                    help="emit the full report as one JSON object")
    ap.add_argument("--history-only", action="store_true",
                    help="ingest + trend-check the history without "
                         "collecting live signals (no compiles, no jax "
                         "device work)")
    ap.add_argument("--update-baseline", action="store_true",
                    help="record the current CPU signals as "
                         "docs/perf_baseline.json (conscious act: exact-"
                         "count families tolerate nothing until re-recorded)")
    ap.add_argument("--repo", default=REPO,
                    help="repo root holding BENCH_r*.json (default: this "
                         "checkout)")
    ap.add_argument("--baseline", default=None,
                    help="baseline path (default: REPO/docs/perf_baseline.json)")
    ap.add_argument("--strategies", nargs="*", default=None,
                    help="fingerprint strategy subset (default: the full "
                         "bench set; pass none to skip the fingerprint)")
    ap.add_argument("--skip-compiled", action="store_true",
                    help="skip the reference-step compile (fingerprint + "
                         "arithmetic comms table still collected)")
    ap.add_argument("--note", default="",
                    help="free-form note stored in the baseline on "
                         "--update-baseline")
    args = ap.parse_args(argv)

    baseline_path = args.baseline or os.path.join(
        args.repo, "docs", "perf_baseline.json"
    )
    if args.update_baseline and (
        args.skip_compiled or args.strategies is not None
    ):
        # a baseline recorded from a subset run would silently DROP the
        # missing families: check_baseline treats absent baseline
        # families as notes, so future full --check runs would green
        # with the fingerprint/compiled gates effectively deleted
        ap.error("--update-baseline requires the full signal set: drop "
                 "--skip-compiled/--strategies (the cheap subset is for "
                 "--check only)")

    from ring_attention_tpu.analysis import perfgate

    if args.history_only:
        report = perfgate.run_gate(None, root=args.repo,
                                   baseline_path=baseline_path)
        return _emit(report, args)

    # JAX_COMPILATION_CACHE_DIR places the cache (point it at
    # tests/.jax_cache to reuse the test suite's compiles)
    from ring_attention_tpu.utils import enable_compile_cache

    enable_compile_cache()

    strategies = args.strategies
    if strategies is None:
        current = perfgate.collect_current(compiled=not args.skip_compiled)
    else:
        current = perfgate.collect_current(
            strategies=tuple(strategies) or None,
            compiled=not args.skip_compiled,
        )

    if args.update_baseline:
        payload = perfgate.write_baseline(
            current, baseline_path, note=args.note
        )
        print(f"baseline recorded: {baseline_path} "
              f"(jax {payload.get('jax')}, "
              f"{len(payload['signals'])} signal families)")
        return 0

    report = perfgate.run_gate(current, root=args.repo,
                               baseline_path=baseline_path)
    return _emit(report, args)


def _emit(report, args) -> int:
    if args.json:
        print(json.dumps(report.to_json(), sort_keys=True))
    else:
        for f in report.findings:
            print(str(f))
        for note in report.notes:
            print(f"  note: {note}")
        verdict = "FAIL" if report.findings else "ok"
        print(f"perf-gate: {verdict} — {len(report.findings)} finding(s), "
              f"{len(report.checked)} series checked, "
              f"{len(report.notes)} note(s)")
    return 1 if report.findings else 0


if __name__ == "__main__":
    sys.exit(main())
