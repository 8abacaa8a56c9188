"""Benchmark-history regression gate: the perf trajectory as a CHECK.

ROADMAP item 5's measurement half: the repo accumulates perf evidence in
three places — ``BENCH_r*.json`` round files, ``docs/hwlogs/
results.jsonl`` hardware rows, and the committed ``docs/
perf_baseline.json`` CPU-signal baseline — and until now nothing read
them back.  This module ingests all three into one schema'd history and
gates on it, in the IO-accounting spirit of FlashAttention (arXiv
2205.14135): measure the hardware-facing quantities (collective counts,
bytes per hop, compiled peak scratch, tokens/sec) and fail loudly when
one regresses, instead of trusting the narrative.

Policy: the gate's PRIMARY signals are the CPU-computable ones that
land on every round — the
``collective_fingerprint`` (compiled HLO collective counts per
strategy), the analytic hop/byte accounting, ``compiled_cost`` FLOPs /
bytes, ``compiled_memory`` peak temp bytes, and the retrace-sentinel
compile count.  Hardware tokens/sec is checked only between rounds where
the probe actually ran; a round with no measurement is RECORDED as a
note (and wedge frequency is itself a tracked series via the
``probe_failure`` rows bench.py appends) — never silently passed, never
a false failure.

Like ``utils/telemetry.py``, this module is stdlib-only at module level:
``bench.py``'s parent process loads it by file path for
:data:`GATE_SCHEMA_VERSION` before the subprocess-isolated device probe;
everything jax-flavored imports inside functions.  CLI:
``tools/perf_gate.py``; gate semantics: docs/observability.md
§Observatory.
"""

from __future__ import annotations

import glob
import json
import os
import re
from dataclasses import dataclass, field
from typing import Any

# Version stamped on every gate artifact (bench phase payloads, the
# committed baseline, gate reports).  Bump when a field is renamed or its
# meaning changes; adding fields needs no bump.
GATE_SCHEMA_VERSION = 1

# Relative tolerance per compiled-signal family (exact-count families —
# fingerprints, hop/byte accounting, compile count — tolerate nothing).
DEFAULT_TOLERANCES = {
    "temp_bytes": 0.25,      # scheduler jitter in scratch accounting
    "output_bytes": 0.25,
    "xla_flops": 0.10,       # counted FLOPs barely move for one program
    "bytes_accessed": 0.35,  # fusion decisions move this the most
    "hardware": 0.15,        # round-over-round tokens/sec / TFLOPs
}

# Hardware series pulled from each bench round's payload:
# name -> (payload key, direction) where direction +1 means higher is
# better (throughput) and -1 means lower is better (latency).
HARDWARE_SERIES = {
    "fwd_tflops": ("value", +1),
    "fwdbwd_tflops": ("fwdbwd_tflops", +1),
    "tokens_per_sec": ("tokens_per_sec", +1),
    "train1m_tokens_per_sec": ("train1m_tokens_per_sec", +1),
    "hybrid262k_tflops": ("hybrid262k", +1),
    "counter262k_tflops": ("counter262k", +1),
    "fwd262k_q8_tflops": ("fwd262k_q8", +1),
    "fused262k_tflops": ("fused262k", +1),
    "packed262k_tokens_per_sec": ("packed262k", +1),
    "decode_ms_per_token": ("decode_ms_per_token", -1),
    # per-call decode latency distribution (bench phase 6's eager loop
    # through utils/tracing.LatencyHistogram): the tail regresses before
    # the chained mean does — a slow outlier every 20 tokens moves p95
    # 1:1 but the amortized ms/token by only 5%
    "decode_ms_p50": ("decode_ms_p50", -1),
    "decode_ms_p95": ("decode_ms_p95", -1),
}

# The analytic comms reference table: fixed north-star-shaped configs
# whose ``ring_comms_accounting`` outputs are pure arithmetic (no jax, no
# device) — pinned against the baseline so a formula regression (a hop
# miscounted, a payload byte-size change nobody meant) fails the gate
# with the same one-line diagnostic as a real comms regression.
COMMS_REFERENCE: dict[str, dict[str, Any]] = {
    "ring8_262k": dict(
        ring_size=8, seq_len=262144, kv_heads=8, dim_head=64,
        dtype_bytes=2,
    ),
    "hybrid2x4_262k": dict(
        ring_size=4, ulysses_size=2, seq_len=262144, kv_heads=8, heads=8,
        dim_head=64, dtype_bytes=2,
    ),
    "counter8_262k": dict(
        ring_size=8, seq_len=262144, kv_heads=8, dim_head=64,
        dtype_bytes=2, counter_rotate=True,
    ),
    "counter8_262k_int8": dict(
        ring_size=8, seq_len=262144, kv_heads=8, dim_head=64,
        dtype_bytes=2, counter_rotate=True, hop_compression="int8",
    ),
    # PR 13: the int8 COMPUTE path at the north-star shape — identical
    # wire accounting to counter8_262k_int8 (the quantized matmuls change
    # the kernel FEED, never the collectives) plus the operand-bytes /
    # f32-accumulator-bytes keys the q8 bench phase reports
    "ring8_262k_q8": dict(
        ring_size=8, seq_len=262144, kv_heads=8, dim_head=64,
        dtype_bytes=2, counter_rotate=True, hop_compression="int8",
        compute_dtype="int8",
    ),
    # PR 18: the fused single-launch ring at the north-star shape — the
    # analytic hop/byte accounting matches ring8_262k exactly (the data
    # that must move is impl-independent); what the row pins is the
    # launch model: kernel_launches=1 and fwd_collectives=0 (hops are
    # in-kernel remote DMAs, not ppermutes)
    "fused8_262k": dict(
        ring_size=8, seq_len=262144, kv_heads=8, dim_head=64,
        dtype_bytes=2, impl="fused",
    ),
}

# ring_comms_accounting keys kept per reference config (all exact ints).
COMMS_KEYS = (
    "ring_hops", "pure_ring_hops", "hop_bytes", "q_pack_bytes",
    "fwd_collectives", "bwd_collectives", "ring_bytes_per_step",
    "ring_bytes_per_step_bwd", "a2a_bytes_per_step",
    # PR 13: the matmul feed (operand width tracks compute_dtype) and the
    # f32 (acc, m, l) state (invariant under every compute_dtype — the
    # precision auditor's contract as a pinned number)
    "matmul_operand_bytes", "accumulator_bytes",
    # PR 18: the launch model — passes launches for the scan path, 1 for
    # the fused ring (the launch-free-hops claim as a pinned int)
    "kernel_launches",
)


@dataclass(frozen=True)
class GateFinding:
    """One regressed series: the gate's one-line diagnostic unit."""

    series: str
    baseline: Any
    current: Any
    message: str

    def __str__(self) -> str:
        return f"perf-gate: {self.series}: {self.message}"


@dataclass
class GateReport:
    """Findings (regressions — any means the gate fails), notes (the
    wedge-honest record: what could not be compared and why), and the
    list of series actually checked (an empty ``checked`` with a green
    verdict would be vacuous — callers can assert coverage)."""

    findings: list[GateFinding] = field(default_factory=list)
    notes: list[str] = field(default_factory=list)
    checked: list[str] = field(default_factory=list)

    @property
    def ok(self) -> bool:
        return not self.findings

    def to_json(self) -> dict[str, Any]:
        return {
            "gate_schema": GATE_SCHEMA_VERSION,
            "ok": self.ok,
            "checked": list(self.checked),
            "findings": [
                {
                    "series": f.series,
                    "baseline": f.baseline,
                    "current": f.current,
                    "message": f.message,
                }
                for f in self.findings
            ],
            "notes": list(self.notes),
        }


@dataclass
class BenchRound:
    """One ``BENCH_rNN.json`` round, normalized."""

    number: int
    path: str
    payload: dict[str, Any]

    @property
    def probe_ok(self) -> bool:
        """Did a hardware measurement actually run this round?  A wedged
        probe leaves ``error`` + ``value == 0`` — wedge-honesty means
        such a round contributes NO hardware points (its standing
        ``last_measured`` echo is an echo, not a measurement)."""
        return "error" not in self.payload and bool(self.payload.get("value"))

    @property
    def fingerprint(self) -> dict[str, Any] | None:
        fp = self.payload.get("collective_fingerprint")
        if isinstance(fp, dict) and "error" not in fp:
            # bench stamps its schema version on every phase payload;
            # that's provenance, not a collective count — a version bump
            # between rounds must not read as fingerprint drift
            return {k: v for k, v in fp.items() if k != "gate_schema"}
        return None

    @property
    def coverage(self) -> dict[str, Any] | None:
        """The tile-coverage fingerprint (bench phase 0b): per-row
        compact-grid tile counts from ``analysis/coverage.py``."""
        fp = self.payload.get("coverage_fingerprint")
        if isinstance(fp, dict) and "error" not in fp:
            return {k: v for k, v in fp.items() if k != "gate_schema"}
        return None

    @property
    def protocol(self) -> dict[str, Any] | None:
        """The fused-ring DMA-protocol fingerprint (bench phase 0f):
        schedverify's derived primitive counts, PROTOCOL row count,
        per-ring model event counts, and total violations
        (``analysis/schedverify.py::protocol_fingerprint``)."""
        fp = self.payload.get("protocol_fingerprint")
        if isinstance(fp, dict) and "error" not in fp:
            return {k: v for k, v in fp.items() if k != "gate_schema"}
        return None

    @property
    def multihost(self) -> dict[str, Any] | None:
        """The multihost-dryrun DCN fingerprint (bench phase 0e):
        per-row forward collective counts over the hierarchical
        ``(dcn_data, ...)`` mesh + the dcn-isolation verdict
        (``analysis/contracts.py::dcn_collective_fingerprint``)."""
        fp = self.payload.get("multihost_dryrun")
        if isinstance(fp, dict) and "error" not in fp:
            return {k: v for k, v in fp.items() if k != "gate_schema"}
        return None


@dataclass
class History:
    """The ingested perf history: bench rounds (oldest first), the
    standing hardware-log rows, and the wedge series."""

    rounds: list[BenchRound] = field(default_factory=list)
    hwlog: dict[str, dict[str, Any]] = field(default_factory=dict)
    probe_failures: list[dict[str, Any]] = field(default_factory=list)

    @property
    def wedged_rounds(self) -> list[BenchRound]:
        return [r for r in self.rounds if not r.probe_ok]


def _parse_round_payload(rec: Any) -> dict[str, Any] | None:
    """The bench JSON out of one round file: the driver wraps it as
    ``{"parsed": {...}}`` (preferred) with the raw line under ``tail``;
    a bare payload dict (a hand-rolled round) passes through."""
    if not isinstance(rec, dict):
        return None
    if isinstance(rec.get("parsed"), dict):
        return rec["parsed"]
    tail = rec.get("tail")
    if isinstance(tail, str):
        for line in reversed(tail.strip().splitlines()):
            try:
                obj = json.loads(line)
            except json.JSONDecodeError:
                continue
            if isinstance(obj, dict):
                return obj
    if "metric" in rec or "value" in rec:
        return rec
    return None


def load_history(root: str | None = None) -> History:
    """Ingest ``BENCH_r*.json`` + ``docs/hwlogs/results.jsonl`` under
    ``root`` (default: the repo this file lives in).

    Malformed files are skipped (a corrupt archive row must not brick the
    gate); ``probe_failure`` rows join as their own series so wedge
    frequency is trackable (``grep`` was the previous interface).
    """
    if root is None:
        root = os.path.dirname(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        )
    hist = History()
    for path in sorted(glob.glob(os.path.join(root, "BENCH_r*.json"))):
        m = re.search(r"BENCH_r(\d+)\.json$", path)
        if not m:
            continue
        try:
            with open(path) as f:
                rec = json.load(f)
        except (OSError, ValueError):
            continue
        payload = _parse_round_payload(rec)
        if payload is not None:
            hist.rounds.append(BenchRound(int(m.group(1)), path, payload))
    hist.rounds.sort(key=lambda r: r.number)
    log_path = os.path.join(root, "docs", "hwlogs", "results.jsonl")
    try:
        with open(log_path) as f:
            lines = f.readlines()
    except OSError:
        lines = []
    for line in lines:
        try:
            rec = json.loads(line)
        except json.JSONDecodeError:
            continue
        if not isinstance(rec, dict):
            continue
        step = rec.get("step")
        if step == "probe_failure":
            hist.probe_failures.append(rec)
        elif step and isinstance(rec.get("result"), dict):
            hist.hwlog[step] = rec  # newest row per step wins
    return hist


# ----------------------------------------------------------------------
# Current-build CPU signals
# ----------------------------------------------------------------------


def comms_reference_signals() -> dict[str, dict[str, int]]:
    """The analytic hop/byte table at the pinned reference configs —
    pure arithmetic (fixed v5e rate constants), runnable with no jax
    and no devices."""
    from ring_attention_tpu.utils.telemetry import ring_comms_accounting

    out: dict[str, dict[str, int]] = {}
    for name, cfg in COMMS_REFERENCE.items():
        acct = ring_comms_accounting(
            peak_tflops=197.0, ici_gbps=186.0, **cfg
        )
        out[name] = {k: int(acct[k]) for k in COMMS_KEYS}
    return out


def latency_reference_signals() -> dict[str, Any]:
    """The latency-histogram codec's fixed point — pure arithmetic, no
    jax, no devices, no clock.

    A deterministic LCG sample pushed through
    ``utils/tracing.LatencyHistogram`` pins the bucket geometry (count,
    scale tag, edge checksum) and the integer percentile read-off as
    EXACT values.  Any change to the bucket edges or the rank rule
    silently re-scales every decode-latency number the hardware gate
    compares across rounds — this family makes that a one-line gate
    failure instead, and the baseline must be consciously re-recorded
    together with the hardware history it invalidates."""
    from ring_attention_tpu.utils.tracing import (
        BUCKET_BOUNDS_NS,
        HIST_BUCKETS,
        HIST_SCALE,
        LatencyHistogram,
    )

    hist = LatencyHistogram()
    x = 1
    for _ in range(1000):
        x = (x * 48271) % 2147483647  # minstd LCG: portable, seedless
        hist.record_ns(1_000 + x % 50_000_000)  # 1 us .. 50 ms spread
    return {
        "hist_scale": HIST_SCALE,
        "hist_buckets": int(HIST_BUCKETS),
        "edge_checksum": int(sum(BUCKET_BOUNDS_NS)),
        "n": int(hist.n),
        "p50_ns": int(hist.percentile_ns(50)),
        "p95_ns": int(hist.percentile_ns(95)),
        "p99_ns": int(hist.percentile_ns(99)),
    }


def compiled_reference_signals() -> dict[str, Any]:
    """Compiler-facing signals of the reference train step: counted
    FLOPs/bytes (``compiled_cost``), peak scratch (``compiled_memory``),
    and the retrace-sentinel compile count of a 2-step drive.

    The reference step is the telemetry suite's instrumented
    RingTransformer at a tiny shape — already compiled by tier-1, so the
    persistent compile cache makes this cheap on a test box.  These
    signals are compiler-version-scoped: the gate compares them only when
    the baseline was recorded under the same jax version.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from ring_attention_tpu import RingTransformer, create_mesh
    from ring_attention_tpu.utils import (
        compat,
        init_train_metrics,
        make_train_step,
    )
    from ring_attention_tpu.utils.telemetry import (
        compiled_cost,
        compiled_memory,
    )
    from . import recompile

    mesh = create_mesh(ring_size=min(4, len(jax.devices())))
    model = RingTransformer(
        num_tokens=64, dim=32, depth=1, heads=4, dim_head=8, causal=True,
        striped=True, bucket_size=8, mesh=mesh, use_ring=True,
    )
    toks = jnp.asarray(
        np.random.default_rng(0).integers(0, 64, (2, 64)), jnp.int32
    )
    params = model.init(jax.random.PRNGKey(0), toks, return_loss=True)
    opt = optax.adam(1e-3)
    opt_state = opt.init(params)
    step = compat.jit(make_train_step(
        lambda p, t: model.apply(p, t, return_loss=True), opt,
        collect_metrics=True, skip_nonfinite=True, clip_grad_norm=1.0,
    ))
    metrics = init_train_metrics()
    with recompile.CompileCounter() as counter:
        out = step(params, opt_state, metrics, toks)
        jax.block_until_ready(step(*out[:3], toks))
    exe = step.lower(params, opt_state, metrics, toks).compile()
    signals: dict[str, Any] = {"compile_count": counter.total}
    signals.update(compiled_cost(exe))
    mem = compiled_memory(exe)
    for key in ("temp_bytes", "output_bytes"):
        if key in mem:
            signals[key] = mem[key]
    return signals


def collect_current(
    *,
    strategies: tuple[str, ...] | None = (
        "ring", "ulysses", "hybrid", "counter", "ring_compressed",
        "counter_q8", "blockwise_ffn",
    ),
    compiled: bool = True,
    coverage: bool = True,
    multihost: bool = True,
    protocol: bool = True,
) -> dict[str, Any]:
    """The current build's CPU gate signals.

    ``strategies=None`` skips the (compile-paying) fingerprint;
    ``compiled=False`` skips the reference-step compile — the arithmetic
    comms table and the (numpy-only) tile-coverage fingerprint always
    land.  ``multihost=False`` skips the DCN dryrun fingerprint (it
    needs >= 4 devices); ``protocol=False`` skips the fused-ring
    DMA-protocol fingerprint (its extraction cross-check traces on the
    8-device ring).  Each skipped family is simply absent, and
    :func:`check` notes absent families instead of passing them
    silently.
    """
    import jax

    signals: dict[str, Any] = {
        "gate_schema": GATE_SCHEMA_VERSION,
        "jax": jax.__version__,
        "comms": comms_reference_signals(),
        "latency": latency_reference_signals(),
    }
    if coverage:
        from .coverage import coverage_fingerprint

        signals["coverage"] = coverage_fingerprint()
    if strategies:
        from .contracts import collective_fingerprint

        signals["fingerprint"] = collective_fingerprint(tuple(strategies))
    if multihost and len(jax.devices()) >= 4:
        from .contracts import dcn_collective_fingerprint

        signals["multihost"] = dcn_collective_fingerprint()
    if protocol and len(jax.devices()) >= 8:
        from .schedverify import protocol_fingerprint

        signals["protocol"] = protocol_fingerprint()
    if compiled:
        signals["compiled"] = compiled_reference_signals()
    return signals


# ----------------------------------------------------------------------
# The gate
# ----------------------------------------------------------------------


def _flat(tree: Any, prefix: str) -> dict[str, Any]:
    out: dict[str, Any] = {}
    if isinstance(tree, dict):
        for k, v in tree.items():
            out.update(_flat(v, f"{prefix}.{k}"))
    else:
        out[prefix] = tree
    return out


def check_baseline(
    current: dict[str, Any],
    baseline: dict[str, Any],
    *,
    tolerances: dict[str, float] | None = None,
) -> GateReport:
    """Current CPU signals vs the committed baseline.

    Exact families (fingerprint counts, comms reference ints, compile
    count) tolerate nothing — a dropped hop and a grown hop both mean
    the program changed and the baseline must be consciously re-recorded
    (``tools/perf_gate.py --update-baseline``).  Compiled cost/memory
    compare within per-series tolerance, and only under the same jax
    version as the baseline (noted and skipped otherwise — a compiler
    upgrade is not a regression).
    """
    tol = dict(DEFAULT_TOLERANCES)
    tol.update(tolerances or {})
    report = GateReport()
    base_signals = baseline.get("signals", baseline)

    # exact families -----------------------------------------------------
    for family in ("fingerprint", "comms", "coverage", "multihost",
                   "protocol", "latency"):
        base = base_signals.get(family)
        cur = current.get(family)
        if base is None:
            report.notes.append(f"{family}: not in baseline — recorded "
                                f"fresh on the next --update-baseline")
            continue
        if cur is None:
            report.notes.append(f"{family}: not collected this run "
                                f"(skipped family) — not compared")
            continue
        flat_base = _flat(base, family)
        flat_cur = _flat(cur, family)
        for series, want in sorted(flat_base.items()):
            if series not in flat_cur:
                # only a finding when the strategy/config was collected
                # at all — a subset run must not fail on what it skipped
                head = series.split(".")[1] if "." in series else series
                if any(k.startswith(f"{family}.{head}.")
                       or k == f"{family}.{head}" for k in flat_cur):
                    report.findings.append(GateFinding(
                        series, want, None,
                        f"series vanished from the current build "
                        f"(baseline {want})",
                    ))
                else:
                    report.notes.append(
                        f"{series}: not collected this run — not compared"
                    )
                continue
            report.checked.append(series)
            got = flat_cur[series]
            if got != want:
                report.findings.append(GateFinding(
                    series, want, got,
                    f"exact-count regression: baseline {want} -> "
                    f"current {got}",
                ))
        for series in sorted(set(flat_cur) - set(flat_base)):
            report.notes.append(
                f"{series}: new series (no baseline) — recorded on the "
                f"next --update-baseline"
            )

    # compiled family (tolerance + jax-version scoped) -------------------
    base_c = base_signals.get("compiled")
    cur_c = current.get("compiled")
    if base_c is None or cur_c is None:
        which = "baseline" if base_c is None else "current run"
        report.notes.append(f"compiled: absent from {which} — not compared")
        return report
    base_jax = baseline.get("jax", base_signals.get("jax"))
    if base_jax and base_jax != current.get("jax"):
        report.notes.append(
            f"compiled: baseline recorded under jax {base_jax}, running "
            f"{current.get('jax')} — compiler-scoped signals not compared"
        )
        return report
    for key, want in sorted(base_c.items()):
        got = cur_c.get(key)
        series = f"compiled.{key}"
        if got is None:
            report.notes.append(f"{series}: backend reports no value — "
                                f"not compared")
            continue
        report.checked.append(series)
        if key == "compile_count":
            if got > want:
                report.findings.append(GateFinding(
                    series, want, got,
                    f"retrace regression: {want} compile(s) -> {got} for "
                    f"the same 2-step drive",
                ))
            continue
        limit = tol.get(key, tol["temp_bytes"])
        if want and (got - want) / want > limit:
            report.findings.append(GateFinding(
                series, want, got,
                f"regression: baseline {want:,} -> current {got:,} "
                f"(+{(got - want) / want:.1%} > {limit:.0%} tolerance)",
            ))
        elif want and (want - got) / want > limit:
            report.notes.append(
                f"{series}: improved {want:,} -> {got:,} — re-record the "
                f"baseline to lock the win in"
            )
    return report


def check_history(
    history: History,
    *,
    tolerances: dict[str, float] | None = None,
) -> GateReport:
    """Round-over-round checks on the ingested bench history.

    Hardware series compare only between rounds where the probe ran
    (direction-aware: throughput down or latency up beyond tolerance is
    the finding).  Fingerprints compare exactly between consecutive
    rounds that carry one.  Wedged rounds and the ``probe_failure`` rows
    land as notes — the wedge-honest record.
    """
    tol = dict(DEFAULT_TOLERANCES)
    tol.update(tolerances or {})
    report = GateReport()
    ok_rounds = [r for r in history.rounds if r.probe_ok]
    for r in history.wedged_rounds:
        err = str(r.payload.get("error", "no measurement"))[:100]
        report.notes.append(
            f"round {r.number} ({os.path.basename(r.path)}): no hardware "
            f"measurement — {err}"
        )
    if history.rounds:
        report.notes.append(
            f"wedge record: {len(history.wedged_rounds)} of "
            f"{len(history.rounds)} rounds had no hardware measurement; "
            f"{len(history.probe_failures)} probe_failure row(s) in "
            f"docs/hwlogs/results.jsonl"
        )
    if history.hwlog:
        # the standing on-silicon numbers ride the report so a wedged
        # stretch still shows WHAT the last measured truth was (and when)
        standing = ", ".join(
            f"{step} {rec['result'].get('value')}"
            f"{' (' + rec['date'] + ')' if rec.get('date') else ''}"
            for step, rec in sorted(history.hwlog.items())
            if isinstance(rec.get("result"), dict)
            and "value" in rec["result"]
        )
        if standing:
            report.notes.append(f"standing hardware measurements: {standing}")
    # hardware series over ok rounds -------------------------------------
    if len(ok_rounds) < 2:
        if history.rounds:
            report.notes.append(
                "hardware: fewer than 2 measured rounds — CPU signals "
                "are the gate (wedge-honest: nothing passed silently)"
            )
    else:
        prev, last = ok_rounds[-2], ok_rounds[-1]
        limit = tol["hardware"]
        for name, (key, direction) in sorted(HARDWARE_SERIES.items()):
            a, b = prev.payload.get(key), last.payload.get(key)
            if not isinstance(a, (int, float)) or not isinstance(
                b, (int, float)
            ) or not a:
                continue
            series = f"hardware.{name}"
            report.checked.append(series)
            drop = (a - b) / a * direction
            if drop > limit:
                report.findings.append(GateFinding(
                    series, a, b,
                    f"regression r{prev.number} -> r{last.number}: "
                    f"{a:,} -> {b:,} ({'-' if direction > 0 else '+'}"
                    f"{abs(drop):.1%} > {limit:.0%} tolerance)",
                ))
    # fingerprint drift between consecutive carrying rounds ---------------
    for family, getter in (("fingerprint", lambda r: r.fingerprint),
                           ("coverage", lambda r: r.coverage),
                           ("multihost", lambda r: r.multihost),
                           ("protocol", lambda r: r.protocol)):
        fps = [(r.number, getter(r)) for r in history.rounds
               if getter(r) is not None]
        for (n0, fp0), (n1, fp1) in zip(fps, fps[1:]):
            flat0 = _flat(fp0, family)
            flat1 = _flat(fp1, family)
            for series in sorted(set(flat0) & set(flat1)):
                report.checked.append(f"{series}[r{n0}->r{n1}]")
                if flat0[series] != flat1[series]:
                    report.findings.append(GateFinding(
                        series, flat0[series], flat1[series],
                        f"drift r{n0} -> r{n1}: {flat0[series]} -> "
                        f"{flat1[series]}",
                    ))
    return report


def _downgrade_acknowledged_drift(
    report: GateReport, baseline_report: GateReport
) -> None:
    """History fingerprint drift needs the same conscious-override escape
    as the baseline family: an INTENTIONAL collective change lands with
    ``--update-baseline``, after which the current build MATCHES the new
    baseline — but the archived round files still disagree with each
    other forever.  When the same series passed the current-vs-baseline
    check, the historical drift is demoted to a note (it already served
    its purpose: the change is acknowledged).  Without a baseline
    verdict for the series (history-only runs), drift stays a finding.
    """
    acknowledged = {
        s for s in baseline_report.checked
        if s.startswith(("fingerprint.", "coverage.", "multihost.",
                         "protocol."))
        and not any(f.series == s for f in baseline_report.findings)
    }
    kept: list[GateFinding] = []
    for f in report.findings:
        if f.series in acknowledged and "drift" in f.message:
            report.notes.append(
                f"{f.series}: historical {f.message} — acknowledged "
                f"(current build matches docs/perf_baseline.json)"
            )
        else:
            kept.append(f)
    report.findings[:] = kept


def run_gate(
    current: dict[str, Any] | None = None,
    *,
    root: str | None = None,
    baseline_path: str | None = None,
    tolerances: dict[str, float] | None = None,
) -> GateReport:
    """The whole gate: history checks + baseline checks, merged.

    ``current=None`` runs history-only (plus a note that no live signals
    were collected).  A missing baseline file is a note, not a failure —
    but the tier-1 test pins that the committed baseline exists and
    passes, so "delete the baseline" cannot green a regression.
    """
    if root is None:
        root = os.path.dirname(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        )
    if baseline_path is None:
        baseline_path = os.path.join(root, "docs", "perf_baseline.json")
    history = load_history(root)
    report = check_history(history, tolerances=tolerances)
    if current is None:
        report.notes.append("no live signals collected (history-only run)")
        return report
    try:
        with open(baseline_path) as f:
            baseline = json.load(f)
    except OSError:
        report.notes.append(
            f"no baseline at {baseline_path} — run tools/perf_gate.py "
            f"--update-baseline to record one"
        )
        return report
    except ValueError as e:
        report.findings.append(GateFinding(
            "baseline", baseline_path, None,
            f"unreadable baseline JSON: {e}",
        ))
        return report
    b_report = check_baseline(current, baseline, tolerances=tolerances)
    _downgrade_acknowledged_drift(report, b_report)
    report.findings.extend(b_report.findings)
    report.notes.extend(b_report.notes)
    report.checked.extend(b_report.checked)
    return report


def write_baseline(
    current: dict[str, Any], path: str, *, note: str = ""
) -> dict[str, Any]:
    """Record ``current`` as the committed baseline (atomic write)."""
    import time as _time

    payload = {
        "gate_schema": GATE_SCHEMA_VERSION,
        "recorded": _time.strftime("%Y-%m-%d"),
        "jax": current.get("jax"),
        **({"note": note} if note else {}),
        "signals": {
            k: v for k, v in current.items()
            if k not in ("gate_schema", "jax")
        },
    }
    tmp = f"{path}.{os.getpid()}.tmp"
    with open(tmp, "w") as f:
        json.dump(payload, f, indent=1, sort_keys=True)
        f.write("\n")
    os.replace(tmp, path)
    return payload
