"""Tile-coverage prover + jaxpr dataflow passes, tier-1.

Three layers, mirroring the PR-5 conventions in ``test_analysis.py``:

  - **positive proofs**: every strategy x layout x masking row of the
    coverage matrix is sound AND tight against the global-position
    oracle; the precision-flow and SPMD-divergence suites hold
    package-wide; the ``band_plan`` seam agrees with the launches.
  - **fingerprints**: the coverage fingerprint is deterministic, rides
    the perf gate's exact family, and a doctored tile count fails the
    gate with a one-line finding naming the row.
  - **seam checks**: ``band_plan`` validates its inputs, mirrors the
    launch-time doc-alignment fallback, and its closed-form/enumerated
    tile counts agree (the fuzz in ``tests/test_fuzz.py`` widens this).
"""

import numpy as np
import pytest

from ring_attention_tpu.analysis import coverage, dataflow
from ring_attention_tpu.ops.pallas_flash import (
    _MAX_COMPACT_TILES,
    _TF_EDGE,
    _TF_WORK,
    band_plan,
)


# ----------------------------------------------------------------------
# Positive proofs: the full matrix
# ----------------------------------------------------------------------


@pytest.mark.parametrize("case", coverage.CASES, ids=lambda c: c.name)
def test_coverage_case_sound_and_tight(case):
    """Acceptance: every row reports soundness (no live tile skipped, no
    interior tile hiding dead elements, schedule complete) and tightness
    (no dead tile visited, closed-form == enumeration) on CPU."""
    report = coverage.prove_case(case)
    assert report.ok, "\n".join(report.violations)
    assert report.hops > 0 and (report.tiles > 0 or report.name)


@pytest.mark.parametrize("case", coverage.MASK_CASES, ids=lambda c: c.name)
def test_mask_coverage_case_sound_and_tight(case):
    """Acceptance (PR 11): every mask-algebra row — band masks through
    the shipping band_plan/ring-hop seams, generic masks (prefix-LM,
    dilated, per-head, Or/Not compositions) through the algebra's tile
    classifier — proves sound, tight, and schedule-complete against the
    mask's own global-position oracle."""
    report = coverage.prove_mask_case(case)
    assert report.ok, "\n".join(report.violations)
    assert report.hops > 0


def test_mask_rows_match_legacy_band_rows():
    """The mask-algebra route re-derives the PR-9 rows bit-for-bit: the
    same geometries lowered through ``mask=`` produce exactly the legacy
    matrix's tile accounting (two independent routes, one grid)."""
    fp = coverage.coverage_fingerprint()
    for mask_row, legacy_row in [
        ("mask/single/causal", "single/causal"),
        ("mask/single/causal-window", "single/causal/window"),
        ("mask/ring/causal", "ring/contiguous"),
        ("mask/ring/causal-window", "ring/contiguous/window"),
        ("mask/ring/striped-window", "ring/striped/window"),
        ("mask/ring/limited-passes", "ring/limited-passes"),
        ("mask/counter/causal", "counter/contiguous"),
        ("mask/counter/window", "counter/window"),
    ]:
        assert fp[mask_row] == fp[legacy_row], (mask_row, legacy_row)


def test_coverage_matrix_is_enlarged():
    """Acceptance: the enlarged matrix holds >= 30 rows and is a strict
    superset of the original 16."""
    reports = coverage.run_coverage_suite()
    assert len(reports) >= 30
    names = {r.name for r in reports}
    assert {c.name for c in coverage.CASES} | {"zigzag/causal"} <= names


def test_coverage_zigzag_rect_grid():
    """The zig-zag path's rectangular-grid predicates (traced offsets, no
    tables) against the same oracle — including the ~half tile skip the
    causal band buys."""
    report = coverage.prove_zigzag()
    assert report.ok, "\n".join(report.violations)
    assert 0 < report.work < report.tiles  # the skip is real and partial


def test_precision_suite_package_clean():
    """Acceptance: the precision-flow auditor passes package-wide — both
    flash paths (fwd+bwd through the custom_vjps, Pallas kernel jaxprs
    included), the int8 hop chain, the counter bwd pack, the q8 decode."""
    for name, violations in dataflow.run_precision_suite():
        assert violations == [], f"{name}:\n" + "\n".join(violations)


def test_divergence_suite_all_strategies(devices):
    """Acceptance: branch-invariant collective sequences proven for every
    strategy, both impls, fwd and fwdbwd."""
    for name, violations in dataflow.run_divergence_suite():
        assert violations == [], f"{name}:\n" + "\n".join(violations)


# ----------------------------------------------------------------------
# The band_plan seam
# ----------------------------------------------------------------------


def test_band_plan_matches_launch_tables():
    """The public seam returns exactly the tables a launch would build
    (same internals, public signature) and the closed form matches."""
    plan = band_plan((64, 64), (8, 8), 0)
    assert plan.tiles == len(plan.tile_q) == 36
    assert plan.compact and plan.block_q == plan.block_k == 8
    # block sizes default through the same fitting as the launches
    auto = band_plan((64, 64), None, 0)
    assert (auto.block_q, auto.block_k) == (64, 64)  # min(nq, DEFAULT)


def test_band_plan_hint_forms():
    """int / (hi, lo) / 4-tuple hints normalize identically."""
    a = band_plan((64, 64), (8, 8), 5)
    b = band_plan((64, 64), (8, 8), (5, None))
    c = band_plan((64, 64), (8, 8), (5, 5, 0, 0), windowed=False)
    assert a.hint == b.hint == c.hint == (5, 5, 0, 0)
    w = band_plan((64, 64), (8, 8), (0, -15))
    assert w.windowed and w.hint == (0, 0, -15, -15)
    with pytest.raises(ValueError, match="windowed"):
        band_plan((64, 64), (8, 8), (0, 0, -15, -15))
    with pytest.raises(ValueError, match="hi"):
        band_plan((64, 64), (8, 8), 0, windowed=True)


def test_band_plan_doc_alignment_fallback():
    """A misaligned declared layout mirrors the launch-time fallback:
    band-only tables, doc_aligned=False; aligned layouts drop the
    cross-document tiles."""
    aligned = band_plan((64, 64), (8, 8), 0, doc_starts=(0, 32))
    misaligned = band_plan((64, 64), (8, 8), 0, doc_starts=(0, 33))
    plain = band_plan((64, 64), (8, 8), 0)
    assert aligned.doc_aligned and aligned.work_tiles < plain.work_tiles
    assert not misaligned.doc_aligned
    assert misaligned.work_tiles == plain.work_tiles
    with pytest.raises(ValueError, match="sorted unique"):
        band_plan((64, 64), (8, 8), 0, doc_starts=(16, 32))


def test_band_plan_compact_flag_tracks_smem_cap():
    plan = band_plan((64, 64), (8, 8), 64)  # full rectangle, 64 tiles
    assert plan.tiles == 64 and plan.compact
    assert _MAX_COMPACT_TILES >= plan.tiles


# ----------------------------------------------------------------------
# Fingerprint
# ----------------------------------------------------------------------


def test_coverage_fingerprint_deterministic_and_ok():
    fp1 = coverage.coverage_fingerprint()
    fp2 = coverage.coverage_fingerprint()
    assert fp1 == fp2
    assert fp1["coverage_ok"] is True
    assert fp1["single/causal"]["tiles"] == 36
    # every matrix row lands in the fingerprint — the fixed strategy x
    # layout x masking rows, zig-zag, the mask-algebra rows, and the
    # fused-ring table rows (PR 18)
    assert set(fp1) - {"coverage_ok"} == (
        {c.name for c in coverage.CASES}
        | {"zigzag/causal"}
        | {c.name for c in coverage.MASK_CASES}
        | {c.name for c in coverage.FUSED_CASES}
    )


# ----------------------------------------------------------------------
# The walker itself: descent + fixpoint behavior the passes rely on
# ----------------------------------------------------------------------


def test_walker_descends_into_pallas_kernels():
    """The precision pass must see INSIDE pl.pallas_call — the kernel
    jaxpr's dots and reductions are the actual accumulator contract."""
    import jax
    import jax.numpy as jnp

    from ring_attention_tpu.ops import pallas_flash

    pf = dataflow.PrecisionFlow()
    closed = jax.make_jaxpr(
        lambda q, k, v: pallas_flash.pallas_flash_partials(
            q, k, v, scale=1.0, causal_offset=0, block_q=16, block_k=16,
            interpret=True,
        )
    )(*[jnp.ones((1, 2, 32, 8), jnp.bfloat16)] * 3)
    assert pf.run(closed) == []
    kernel_sinks = [s for s in pf.sinks_checked if "pallas_call" in s]
    assert kernel_sinks, "kernel jaxpr was not walked"


def test_walker_scan_carry_fixpoint():
    """Taint introduced on a later scan iteration still reaches the
    carry's consumers (the fixpoint sweep, not a single pass)."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    def f(x8, y):
        def body(c, _):
            # carry picks up int8-derived content only via the loop
            return c + x8.astype(jnp.float32).sum(), None
        out, _ = lax.scan(body, y, jnp.arange(3))
        return out

    closed = jax.make_jaxpr(f)(jnp.ones((4,), jnp.int8),
                               jnp.float32(0.0))
    violations = dataflow.PrecisionFlow().run(closed, label="toy")
    assert any("int8" in v for v in violations)


def test_collective_signature_structural():
    """Signatures are scan-aware and order-sensitive — the property the
    divergence equality check rests on."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import PartitionSpec as P

    from ring_attention_tpu.parallel.mesh import SEQ_AXIS, create_mesh
    from ring_attention_tpu.utils import compat

    mesh = create_mesh(ring_size=8)
    spec = P("data", None, "seq", None)
    perm = [(j, (j + 1) % 8) for j in range(8)]

    def scanned(q):
        def body(c, _):
            return lax.ppermute(c, SEQ_AXIS, perm), None
        out, _ = lax.scan(body, q, jnp.arange(4))
        return out

    fn = compat.shard_map(scanned, mesh=mesh, in_specs=(spec,),
                          out_specs=spec, check_vma=False)
    x = jnp.ones((1, 2, 64, 8), jnp.float32)
    sig = dataflow.collective_signature(jax.make_jaxpr(fn)(x))
    flat = str(sig)
    assert "scan" in flat and "ppermute" in flat
