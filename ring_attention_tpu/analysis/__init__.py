"""Static-analysis subsystem: machine-checked contracts for the package.

The passes, all CPU-runnable in tier-1 (see docs/static_analysis.md):

  - :mod:`~ring_attention_tpu.analysis.contracts` — declarative
    collective/HLO contracts per sequence-parallel strategy, verified
    against optimized HLO and jaxpr structure;
  - :mod:`~ring_attention_tpu.analysis.lint` — repo-native AST lint
    (compat-shim bypasses, unnamed kernels, unscoped collectives, host
    entropy/numpy in traced code, unvalidated entry points);
  - :mod:`~ring_attention_tpu.analysis.recompile` — retrace sentinel
    (each entry point compiles exactly once per shape) and the f32
    accumulator-dtype spot audit;
  - :mod:`~ring_attention_tpu.analysis.dataflow` — jaxpr abstract
    interpretation: the precision-flow auditor (bf16/int8 taint to every
    reduction/accumulator, generalizing the spot audit) and the SPMD
    divergence checker (branch-invariant collective sequences);
  - :mod:`~ring_attention_tpu.analysis.coverage` — the tile-coverage
    prover: the compact skip grids held to a global-position oracle for
    soundness (no live tile skipped), tightness (no dead tile visited),
    and schedule completeness, per strategy x layout x masking row;
  - :mod:`~ring_attention_tpu.analysis.schedverify` — the DMA/semaphore
    protocol verifier for the fused-ring kernel: jaxpr extraction of
    every DMA/semaphore site cross-checked against the declared
    ``PROTOCOL`` table, then a symbolic N-device model check (rings
    2..8) for matched waits, overwrite-before-read races
    (happens-before from semaphore edges), semaphore drain, and
    deadlock freedom under arbitrary compute skew.

CLI: ``tools/check_contracts.py`` (contract suite; ``--coverage`` /
``--dataflow`` for the prover and jaxpr audits) and
``python -m ring_attention_tpu.analysis`` (lint + dtype audit + precision
flow + divergence + coverage + protocol + elastic self-run).
On a host without jax, run the lint as a plain script —
``python ring_attention_tpu/analysis/lint.py`` — which skips this
package ``__init__`` chain entirely.
"""

from .dataflow import (
    JaxprWalker,
    PrecisionFlow,
    audit_precision_flow,
    check_spmd_divergence,
    collective_signature,
    run_divergence_suite,
    run_precision_suite,
)
from .lint import Violation, lint_file, lint_package, lint_source
from .recompile import (
    CompileCounter,
    RetraceError,
    assert_compiles_once,
    audit_accumulator_dtypes,
    audit_donation,
    audit_host_offload,
    audit_remat_residuals,
)

__all__ = [
    "CompileCounter",
    "JaxprWalker",
    "PrecisionFlow",
    "RetraceError",
    "Violation",
    "audit_precision_flow",
    "check_spmd_divergence",
    "collective_signature",
    "run_divergence_suite",
    "run_precision_suite",
    "assert_compiles_once",
    "audit_accumulator_dtypes",
    "audit_donation",
    "audit_host_offload",
    "audit_remat_residuals",
    "lint_file",
    "lint_package",
    "lint_source",
    # imported lazily (contracts pulls in jax + the parallel stack;
    # coverage pulls the kernel module for band_plan; schedverify pulls
    # the kernel module for its PROTOCOL table):
    "contracts",
    "coverage",
    "schedverify",
]


def __getattr__(name: str):
    if name in ("contracts", "coverage", "schedverify"):
        import importlib

        return importlib.import_module(f".{name}", __name__)
    raise AttributeError(name)
