"""Self-run: ``python -m ring_attention_tpu.analysis``.

Lints the whole package tree, runs the f32 accumulator-dtype spot audit
(unless ``--no-audit``), the jaxpr dataflow passes (unless
``--no-dataflow``: the precision-flow auditor over both flash paths /
the int8 hop chain / the counter bwd pack, and the SPMD divergence
checker over every strategy when multiple simulated devices are
available), the tile-coverage prover (unless ``--no-coverage``), the
fused-ring DMA/semaphore protocol verifier (unless ``--no-schedverify``:
the rings-2..8 model check always, plus the jaxpr extraction
cross-check when virtual devices are available), and the
elastic checkpoint contracts (unless ``--no-elastic``).  Exit status
0 = clean.

The ``-m`` form imports the package ``__init__`` chain (which needs
jax); on a host without jax, run the lint as a plain script instead:
``python ring_attention_tpu/analysis/lint.py``.  The full
collective-contract suite needs virtual devices and lives in
``tools/check_contracts.py`` (which also fronts ``--coverage`` /
``--dataflow`` individually).
"""

from __future__ import annotations

import argparse
import os

from . import recompile
from .lint import lint_package


def _request_virtual_devices(n: int = 8) -> None:
    """Ask for simulated host devices BEFORE anything initializes a jax
    backend.  Importing jax does not initialize one, so setting the flag
    at the top of main() is early enough in the normal CLI invocation —
    the precision suite would otherwise initialize a single-device CPU
    backend and starve the divergence suite of its mesh."""
    flag = f"--xla_force_host_platform_device_count={n}"
    if flag not in os.environ.get("XLA_FLAGS", ""):
        os.environ["XLA_FLAGS"] = os.environ.get("XLA_FLAGS", "") + f" {flag}"
    os.environ.setdefault("JAX_PLATFORMS", "cpu")


def _have_virtual_devices(n: int = 2) -> bool:
    import jax

    try:
        return len(jax.devices()) >= n
    except Exception:  # noqa: BLE001 — no backend at all: skip, don't crash
        return False


def main(argv: list[str] | None = None) -> int:
    _request_virtual_devices()
    parser = argparse.ArgumentParser(
        prog="python -m ring_attention_tpu.analysis",
        description="lint the package tree + audit kernel accumulator "
                    "dtypes + precision-flow/divergence dataflow passes + "
                    "tile-coverage prover + fused-ring protocol verifier "
                    "+ elastic checkpoint contracts",
    )
    parser.add_argument("--no-audit", action="store_true",
                        help="skip the (jax-importing) f32 accumulator audit")
    parser.add_argument("--no-dataflow", action="store_true",
                        help="skip the jaxpr precision-flow and SPMD "
                             "divergence passes")
    parser.add_argument("--no-coverage", action="store_true",
                        help="skip the tile-coverage prover")
    parser.add_argument("--no-schedverify", action="store_true",
                        help="skip the fused-ring DMA/semaphore protocol "
                             "verifier (model check + jaxpr extraction)")
    parser.add_argument("--no-elastic", action="store_true",
                        help="skip the elastic checkpoint contracts "
                             "(manifest round-trip, resharded==direct "
                             "load, corrupt-shard fallback, debris sweep)")
    args = parser.parse_args(argv)

    notes: list[str] = []
    failures = [str(v) for v in lint_package()]
    if not args.no_audit:
        failures.extend(recompile.audit_accumulator_dtypes())
    if not args.no_dataflow:
        from . import dataflow

        for name, violations in dataflow.run_precision_suite():
            failures.extend(f"{name}: {v}" if name not in v else v
                            for v in violations)
        if _have_virtual_devices():
            for name, violations in dataflow.run_divergence_suite():
                failures.extend(violations)
        else:
            notes.append(
                "divergence checker skipped: backend already initialized "
                "with < 2 devices (tools/check_contracts.py --dataflow "
                "runs it with virtual devices)"
            )
    if not args.no_coverage:
        from . import coverage

        for report in coverage.run_coverage_suite():
            failures.extend(report.violations)
    if not args.no_schedverify:
        from . import schedverify

        # the extraction cross-check traces on the full-device ring
        if _have_virtual_devices(8):
            for name, violations in schedverify.run_schedverify_suite():
                failures.extend(violations)
        else:
            for name, violations in [(
                "model", schedverify.verify_protocol())]:
                failures.extend(violations)
            notes.append(
                "schedverify extraction skipped: backend already "
                "initialized with < 8 devices (model check still ran; "
                "tools/check_contracts.py --dma re-proves with virtual "
                "devices)"
            )
    if not args.no_elastic:
        # the elastic checks build 4-device sub-meshes
        if _have_virtual_devices(4):
            from ..elastic.verify import run_elastic_suite

            for name, violations in run_elastic_suite():
                failures.extend(f"{name}: {v}" for v in violations)
        else:
            notes.append(
                "elastic checks skipped: backend already initialized "
                "with < 4 devices (tools/check_contracts.py --elastic "
                "runs them with virtual devices)"
            )
    for line in failures:
        print(line)
    for line in notes:
        print(f"note: {line}")
    print(f"{len(failures)} finding(s)" if failures else "clean")
    return 1 if failures else 0


if __name__ == "__main__":
    raise SystemExit(main())
