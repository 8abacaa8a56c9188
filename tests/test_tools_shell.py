"""Flag-surface smoke tests over the ``tools/`` scripts.

A broken flag in a chip tool is otherwise only discovered during a chip
call, where debugging is the expensive way to spend the budget: every tool
must byte-compile, parse its documented flags, and the CPU-runnable ones
must run end to end.
"""

import json
import os
import py_compile
import shutil
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
KERNEL_VALIDATE = os.path.join(REPO, "tools", "tpu_kernel_validate.py")
TRACE_REPORT = os.path.join(REPO, "tools", "trace_report.py")
CLUSTER_TIMELINE = os.path.join(REPO, "tools", "cluster_timeline.py")
CHECK_CONTRACTS = os.path.join(REPO, "tools", "check_contracts.py")


# ----------------------------------------------------------------------
# Python chip tools: flag-surface smoke
# ----------------------------------------------------------------------


def test_tpu_kernel_validate_compiles():
    py_compile.compile(KERNEL_VALIDATE, doraise=True)


def test_tpu_kernel_validate_segments_flag_parses():
    """``--segments`` (the packed-sequence sweep) must be a real flag:
    ``--help`` exits 0 and documents it — argparse runs before any jax
    work, so this needs no TPU."""
    proc = subprocess.run(
        [sys.executable, KERNEL_VALIDATE, "--help"],
        capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == 0, proc.stderr
    assert "--segments" in proc.stdout


def test_tpu_kernel_validate_hybrid_flag_parses():
    """``--hybrid U`` (the Ulysses x Ring factoring sweep) must be a real
    flag — same contract as ``--segments``: a broken flag is otherwise
    only discovered when a scarce TPU window opens."""
    proc = subprocess.run(
        [sys.executable, KERNEL_VALIDATE, "--help"],
        capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == 0, proc.stderr
    assert "--hybrid" in proc.stdout


def test_tpu_kernel_validate_q8_flag_parses():
    """``--q8`` (the int8 compute sweep, PR 13) must be a real flag —
    same contract as ``--segments``: a broken flag is otherwise only
    discovered when a scarce TPU window opens."""
    proc = subprocess.run(
        [sys.executable, KERNEL_VALIDATE, "--help"],
        capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == 0, proc.stderr
    assert "--q8" in proc.stdout


def test_tpu_kernel_validate_fused_flag_parses():
    """``--fused`` (the single-launch fused-ring sweep, PR 18) must be a
    real flag — same contract as ``--q8``: a broken flag is otherwise
    only discovered when a scarce TPU window opens."""
    proc = subprocess.run(
        [sys.executable, KERNEL_VALIDATE, "--help"],
        capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == 0, proc.stderr
    assert "--fused" in proc.stdout


def test_trace_report_compiles():
    py_compile.compile(TRACE_REPORT, doraise=True)


def test_trace_report_flags_parse():
    """``trace_report.py``'s flag surface (``--xprof`` / ``--last``) must
    parse before the package (and jax) is imported — the telemetry
    analogue of the kernel-validate smoke: a broken report tool is
    otherwise only discovered when someone needs the numbers."""
    proc = subprocess.run(
        [sys.executable, TRACE_REPORT, "--help"],
        capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == 0, proc.stderr
    assert "--xprof" in proc.stdout
    assert "--last" in proc.stdout


def test_trace_report_diff_renders(tmp_path):
    """``--diff OLD NEW`` must produce the side-by-side delta/percent
    table from two metrics runs."""
    old = tmp_path / "old"
    new = tmp_path / "new"
    for d, tps in ((old, 100.0), (new, 80.0)):
        d.mkdir()
        (d / "metrics.jsonl").write_text(
            f'{{"schema": 1, "step": 0, "loss": 2.0, '
            f'"tokens_per_sec": {tps}}}\n'
        )
    proc = subprocess.run(
        [sys.executable, TRACE_REPORT, "--diff", str(old), str(new)],
        capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == 0, proc.stderr
    assert "tokens_per_sec" in proc.stdout
    assert "-20.0%" in proc.stdout
    assert "pct" in proc.stdout


def test_cluster_timeline_compiles():
    py_compile.compile(CLUSTER_TIMELINE, doraise=True)


def test_cluster_timeline_flags_parse():
    """``cluster_timeline.py`` is stdlib-only and its flag surface
    (``--chrome`` / ``--incident`` / ``--last``) must parse without any
    jax import — the tracing analogue of the trace-report smoke."""
    proc = subprocess.run(
        [sys.executable, CLUSTER_TIMELINE, "--help"],
        capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == 0, proc.stderr
    for flag in ("--chrome", "--incident", "--last", "--reference"):
        assert flag in proc.stdout, f"{flag} missing from --help"


def test_cluster_timeline_renders_and_incident_exit_codes(tmp_path):
    """The three exits, each from a real span file: a table on a healthy
    trace (0), exit 3 on ``--incident`` with no anchor, and the
    annotated incident when a chaos kill is present (stdlib-only, no
    jax import in the tool)."""
    span = {"schema": 1, "trace": "t", "proc": 0, "kind": "span",
            "name": "train/step", "span": 2, "parent": None,
            "mono": 1.0, "wall": 100.0, "dur": 0.25,
            "attrs": {"step": 0}}
    trace = tmp_path / "trace"
    trace.mkdir()
    path = trace / "spans_p00000.jsonl"
    path.write_text(json.dumps(span) + "\n")
    proc = subprocess.run(
        [sys.executable, CLUSTER_TIMELINE, str(trace)],
        capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == 0, proc.stderr
    assert "train/step" in proc.stdout

    proc = subprocess.run(
        [sys.executable, CLUSTER_TIMELINE, str(trace), "--incident"],
        capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == 3, (proc.stdout, proc.stderr)
    assert "no incident anchor" in proc.stderr

    kill = {**span, "kind": "instant", "name": "chaos/kill", "span": 3,
            "wall": 101.0, "attrs": {"fault": "kill_at_step"}}
    del kill["dur"]
    path.write_text(json.dumps(span) + "\n" + json.dumps(kill) + "\n")
    proc = subprocess.run(
        [sys.executable, CLUSTER_TIMELINE, str(trace), "--incident"],
        capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == 0, (proc.stdout, proc.stderr)
    assert "chaos/kill on process 0" in proc.stdout

    out = tmp_path / "chrome.json"
    proc = subprocess.run(
        [sys.executable, CLUSTER_TIMELINE, str(trace),
         "--chrome", str(out)],
        capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == 0, proc.stderr
    payload = json.loads(out.read_text())
    assert {e["ph"] for e in payload["traceEvents"]} == {"M", "X", "i"}


def test_check_contracts_compiles():
    py_compile.compile(CHECK_CONTRACTS, doraise=True)


def test_check_contracts_flags_parse():
    """``check_contracts.py`` must keep its documented flag surface
    (``--strategy/--mesh/--json/--memory``): argparse runs before any jax
    device work, so this smoke needs no simulated mesh.  The full
    contract run lives in tests/test_analysis.py; the memory-audit suite
    in tests/test_memory.py."""
    proc = subprocess.run(
        [sys.executable, CHECK_CONTRACTS, "--help"],
        capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == 0, proc.stderr
    for flag in ("--strategy", "--mesh", "--json", "--devices", "--memory",
                 "--coverage", "--dataflow", "--dma", "--elastic"):
        assert flag in proc.stdout, f"{flag} missing from --help"


def test_check_contracts_coverage_exits_zero():
    """Acceptance: ``check_contracts.py --coverage`` proves soundness AND
    tightness for every strategy x layout x masking row on CPU and exits
    0.  Numpy-only after import — no mesh, no compiles, cheap enough for
    a subprocess smoke."""
    proc = subprocess.run(
        [sys.executable, CHECK_CONTRACTS, "--coverage"],
        capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "coverage rows sound and tight" in proc.stdout


def test_check_contracts_dma_exits_zero():
    """Acceptance: ``check_contracts.py --dma`` re-proves the fused-ring
    DMA/semaphore protocol — the rings-2..8 model check plus the jaxpr
    extraction cross-check for the plain and q8 feeds — on CPU virtual
    devices and exits 0."""
    proc = subprocess.run(
        [sys.executable, CHECK_CONTRACTS, "--dma"],
        capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "3/3 DMA-protocol checks hold" in proc.stdout
    assert "protocol model (rings 2-8" in proc.stdout


def test_check_contracts_elastic_exits_zero():
    """Acceptance: ``check_contracts.py --elastic`` holds the elastic
    checkpoint contracts (manifest schema round-trip, resharded-load ==
    direct-load at a changed mesh, corrupt-shard fallback, commit-debris
    sweep) on CPU virtual devices and exits 0.  The quick in-process
    subset (``--no-multiprocess``) runs here; the full 7/7 including the
    spawned two-process rows is the slow-tier
    ``tests/test_multihost.py::test_elastic_cli_multiprocess_rows``."""
    proc = subprocess.run(
        [sys.executable, CHECK_CONTRACTS, "--elastic",
         "--no-multiprocess"],
        capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "4/4 elastic checks hold" in proc.stdout
    as_json = subprocess.run(
        [sys.executable, CHECK_CONTRACTS, "--elastic",
         "--no-multiprocess", "--json"],
        capture_output=True, text=True, timeout=300,
    )
    assert as_json.returncode == 0, as_json.stdout + as_json.stderr
    payload = json.loads(as_json.stdout)
    assert payload["ok"] is True and payload["checked"] == 4


def test_check_contracts_mask_filter():
    """``--coverage --mask EXPR`` re-proves one mask row in isolation;
    an unknown mask name lists the registry instead of tracebacking."""
    proc = subprocess.run(
        [sys.executable, CHECK_CONTRACTS, "--coverage", "--mask",
         "causal&window:24"],
        capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "(causal&window:24)" in proc.stdout
    assert "coverage rows sound and tight" in proc.stdout
    bad = subprocess.run(
        [sys.executable, CHECK_CONTRACTS, "--coverage", "--mask", "wat:7"],
        capture_output=True, text=True, timeout=300,
    )
    assert bad.returncode != 0
    assert "Traceback" not in bad.stderr
    assert "registry" in bad.stderr and "window" in bad.stderr
    # --mask without --coverage is a usage error, not a silent no-op
    usage = subprocess.run(
        [sys.executable, CHECK_CONTRACTS, "--mask", "causal"],
        capture_output=True, text=True, timeout=300,
    )
    assert usage.returncode != 0 and "--coverage" in usage.stderr


def test_check_contracts_knows_counter_variants():
    """The counter-rotation / int8-compression strategies are enumerable
    by name: an unknown strategy's error message lists every CONTRACTS
    key, so this pins the rows' existence without compiling anything
    (the full run lives in tests/test_analysis.py)."""
    proc = subprocess.run(
        [sys.executable, CHECK_CONTRACTS, "--strategy", "nonesuch"],
        capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode != 0
    for name in ("counter", "ring_compressed", "counter_compressed"):
        assert name in proc.stderr, f"{name} missing from strategy listing"


def test_check_contracts_mesh_mismatch_is_a_diagnostic():
    """A --mesh that fits none of the requested strategies must exit with
    a one-line diagnostic, not a traceback (hybrid needs a factored
    mesh); mixed requests skip the mismatches loudly instead of aborting
    the run on the first incompatible strategy.  Argparse-level only: no
    strategy compiles, so this stays cheap."""
    proc = subprocess.run(
        [sys.executable, CHECK_CONTRACTS,
         "--strategy", "hybrid", "--mesh", "1x8"],
        capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode != 0
    assert "Traceback" not in proc.stderr
    assert "matched no requested strategy" in proc.stderr


# ----------------------------------------------------------------------
# Static analysis: the repo-native lint and ruff, alongside bash -n
# ----------------------------------------------------------------------


def test_repo_lint_self_run():
    """The repo lint over the package tree exits clean — the python
    analogue of ``bash -n``: every one-liner fix that landed with rules
    RA001-RA008 stays landed.  Run in the script-path form, which is the
    documented jax-free invocation (the ``-m`` form imports the package
    ``__init__`` chain and therefore jax)."""
    proc = subprocess.run(
        [sys.executable,
         os.path.join(REPO, "ring_attention_tpu", "analysis", "lint.py")],
        capture_output=True, text=True, timeout=120, cwd=REPO,
    )
    assert proc.returncode == 0, f"repo lint:\n{proc.stdout}{proc.stderr}"


def test_ruff_if_available():
    """``ruff check`` with the pyproject config (import hygiene + the
    correctness subset the codebase already satisfies) — the shellcheck
    pattern: enforced where the host has ruff, skipped where it doesn't."""
    if shutil.which("ruff") is None:
        pytest.skip("ruff not installed on this host")
    proc = subprocess.run(
        ["ruff", "check", "ring_attention_tpu", "tools", "tests"],
        capture_output=True, text=True, timeout=120, cwd=REPO,
    )
    assert proc.returncode == 0, f"ruff:\n{proc.stdout}"
