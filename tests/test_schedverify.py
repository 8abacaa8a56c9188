"""The DMA/semaphore protocol verifier (``analysis/schedverify.py``).

Three layers, mirroring tests/test_analysis.py:

  - **positive proofs**: the shipped ``fused_ring_remote`` protocol
    model-checks clean for every ring size 2..8 (bare ring AND 2-group
    mesh) — grant balance, no overwrite-before-read, semaphore drain,
    deadlock freedom — and the jaxpr extraction cross-check matches the
    declared ``PROTOCOL`` table site-by-site for the plain and q8 feeds;
  - **negative toys**: both REAL PR-18 review bugs, kept alive as
    protocol variants, must each fail with a one-line diagnostic naming
    the hop/slot (the grant-less push's mid-read overwrite) or the
    hop/device (the logical ring-rank id's replica-group escape) — plus
    tampered tables failing the cross-check;
  - **derivation**: the fused contract's expected counts are DERIVED
    from the verified table (no more hand-pinned numbers), and the
    protocol fingerprint the perf gate pins exactly is deterministic.
"""

import pytest

from ring_attention_tpu.analysis import schedverify as sv
from ring_attention_tpu.analysis.lint import lint_source
from ring_attention_tpu.ops.pallas_ring import PROTOCOL


# ----------------------------------------------------------------------
# Positive proofs: the shipped protocol
# ----------------------------------------------------------------------


@pytest.mark.parametrize("ring", [2, 3, 4, 5, 6, 7, 8])
def test_shipped_protocol_model_checks_clean(ring):
    """Acceptance: the shipped protocol proves clean at every ring size —
    matched waits on both ends, no slot overwritten while a reader holds
    it, semaphores drained, no deadlock — on the bare ring and on the
    2-group mesh (MESH addressing stays inside the replica group)."""
    assert sv.verify_ring(ring=ring, groups=1) == []
    assert sv.verify_ring(ring=ring, groups=2) == []


def test_verify_protocol_full_sweep_clean():
    assert sv.verify_protocol() == []


@pytest.mark.parametrize("quantized", [False, True],
                         ids=["plain", "q8"])
def test_extraction_matches_declared_protocol(devices, quantized):
    """The traced kernel IS the table: every DMA/semaphore equation in
    the pallas jaxpr resolves to named refs and matches a declared row's
    signature, every row's site count matches the trace, and every
    remote op addresses by MESH coordinates — for both feeds (the q8
    payload must not grow its own copies)."""
    ops = sv.extract_fused_schedule(quantized=quantized)
    assert len(ops) == sum(sum(r["sites"].values()) for r in PROTOCOL)
    assert sv.crosscheck_protocol(ops) == []
    # extraction resolved real names, not fallbacks — a "?" would mean
    # ref identity got lost crossing a cond/while boundary
    for op in ops:
        assert "?" not in op.bufs + op.sems, str(op)


def test_run_schedverify_suite_green(devices):
    for name, violations in sv.run_schedverify_suite():
        assert violations == [], f"{name}: " + "\n".join(violations)


# ----------------------------------------------------------------------
# Negative toys: the two PR-18 review bugs
# ----------------------------------------------------------------------


def test_grantless_push_races(ring=4):
    """Review bug #1: dropping the receiver->sender grant handshake lets
    hop i+1's incoming DMA overwrite the slot hop i is still reading.
    The verifier reports the overwrite race with a one-line diagnostic
    naming the slot and hops."""
    violations = sv.verify_ring(sv.grantless_protocol(), ring=ring)
    races = [v for v in violations if "[rule: slot-overwrite-race]" in v]
    assert races, violations
    for v in races:
        assert "\n" not in v  # one-line diagnostics, house style
    # the diagnostic names the slot, the writing hop, and the reading hop
    assert any("kvbuf slot" in v and "written at hop" in v and "hop-" in v
               for v in races), races


def test_grantless_ring2_needs_no_grant():
    """Ring 2 has no granted pushes (the guard window is empty), so the
    grant-less variant is genuinely safe there — the verifier must agree,
    or the race check is too coarse."""
    assert sv.verify_ring(sv.grantless_protocol(), ring=2) == []


def test_grantless_fails_at_every_ring_from_3():
    for ring in (3, 5, 8):
        assert any("[rule: slot-overwrite-race]" in v
                   for v in sv.verify_ring(sv.grantless_protocol(),
                                           ring=ring)), ring


def test_logical_id_escapes_replica_group():
    """Review bug #2: addressing the push by flat ring-rank LOGICAL id.
    Invisible on the bare ring (group 0 IS the mesh) — the verifier must
    pass there, exactly how the bug hid — and on the 2-group mesh it
    reports the replica-group escape (naming hop and devices), the
    resulting recv imbalance, and the deadlock of the starved group."""
    toy = sv.logical_id_protocol()
    assert sv.verify_ring(toy, ring=4, groups=1) == []
    violations = sv.verify_ring(toy, ring=4, groups=2)
    escapes = [v for v in violations if "[rule: dma-device-id]" in v]
    assert escapes, violations
    for v in escapes:
        assert "\n" not in v
    assert any("hop 0" in v and "outside its replica group" in v
               for v in escapes), escapes
    assert any("[rule: dma-matched-wait]" in v for v in violations)
    assert any("[rule: ring-deadlock]" in v for v in violations)


def test_crosscheck_flags_logical_device_id_at_jaxpr_level():
    """The jaxpr-side guard for the same bug: an extracted remote op
    whose DeviceIdType is not MESH flags, whatever the model says."""
    op = sv.ExtractedOp(
        kind="dma_start", path="pallas_call#0::dma_start#2 -> ()",
        bufs=("kvbuf", "kvbuf"), sems=("send_sem", "recv_sem"),
        remote=True, device_id_type="logical", lits=(0, 1),
    )
    violations = sv.crosscheck_protocol([op], protocol=())
    assert any("[rule: dma-device-id]" in v for v in violations)


def test_crosscheck_flags_undeclared_and_miscounted_sites():
    """An op matching no row is undeclared protocol; a row whose traced
    site count disagrees with its ``sites`` declaration is drift."""
    rogue = sv.ExtractedOp(
        kind="semaphore_signal", path="pallas_call#0::semaphore_signal#9",
        bufs=(), sems=("rogue_sem",), remote=True,
        device_id_type="mesh", lits=(1,),
    )
    violations = sv.crosscheck_protocol([rogue])
    assert any("[rule: protocol-coverage]" in v for v in violations)
    # every declared site is now missing from the (near-empty) trace
    assert any("[rule: protocol-sites]" in v for v in violations)


def test_semaphore_drain_catches_unmatched_signal():
    """A protocol with a stray extra grant signal must fail the
    matched-wait and drain checks, naming the semaphore."""
    extra = tuple(
        {**r, "guard": "hop < hops - 1"} if r["row"] == "grant" else r
        for r in PROTOCOL
    )
    violations = sv.verify_ring(extra, ring=4)
    assert any("grant_sem" in v and "[rule: dma-matched-wait]" in v
               for v in violations), violations


def test_missing_drain_deadlocks():
    """Dropping the hop drain starves the matched-wait balance and the
    schedule's semaphores never drain — the wait-side dual of the
    deadlock check."""
    toy = tuple(r for r in PROTOCOL if r["row"] != "hop-drain")
    violations = sv.verify_ring(toy, ring=4)
    assert any("[rule: dma-matched-wait]" in v for v in violations)
    assert any("[rule: semaphore-drain]" in v for v in violations)


# ----------------------------------------------------------------------
# Derivation: contract counts come from the verified table
# ----------------------------------------------------------------------


def test_derived_counts_match_lowered_module():
    """The numbers PR 18 hand-pinned, now derived from the table — and
    the contracts module serves them via FUSED_RING_EXPECTED."""
    from ring_attention_tpu.analysis import contracts

    want = {
        "dma_start": 14, "dma_wait": 14, "semaphore_signal": 3,
        "semaphore_wait": 2, "get_barrier_semaphore": 1, "ppermute": 0,
    }
    assert sv.derived_fused_counts() == want
    assert contracts.FUSED_RING_EXPECTED == want


def test_protocol_fingerprint_deterministic(devices):
    """Two collections must be identical, violations zero, the derived
    counts embedded, and the model's event count per ring size the
    recorded one (an edit to the hop schedule or the PROTOCOL table
    moves it)."""
    fp = sv.protocol_fingerprint()
    assert fp == sv.protocol_fingerprint()
    assert fp["violations"] == 0
    assert fp["rows"] == len(PROTOCOL)
    assert fp["counts"] == sv.derived_fused_counts()
    assert fp["plain_ops"] == fp["q8_ops"] == 34
    assert fp["rings"] == {
        "ring2": 38, "ring3": 102, "ring4": 196, "ring5": 320,
        "ring6": 474, "ring7": 658, "ring8": 872,
    }


# ----------------------------------------------------------------------
# Lint RA015: the verified-seam fence
# ----------------------------------------------------------------------


def test_lint_ra015_primitive_outside_declared_row():
    """Inside the fused module, a primitive call in a function no
    PROTOCOL row names is protocol the model never saw — flagged; a
    declared fn and a reasoned allow are clean."""
    src = (
        'PROTOCOL = (\n'
        '    {"row": "seed", "fn": "_seed", "op": "copy",\n'
        '     "sites": {"dma_start": 1}},\n'
        ')\n'
        'def _seed():\n'
        '    pltpu.make_async_copy(a, b, sem)\n'
        'def _rogue():\n'
        '    pltpu.semaphore_signal(sem, inc=1)\n'
        'def _excused():\n'
        '    pltpu.semaphore_wait(sem, 1)'
        '  # ra: allow(RA015 probe outside the hop schedule)\n'
    )
    violations = lint_source(src, "ring_attention_tpu/ops/pallas_ring.py")
    assert [v.rule for v in violations] == ["RA015"]
    assert violations[0].line == 8
    assert "PROTOCOL row" in violations[0].message


def test_lint_ra015_missing_table_flags_everything():
    """No parseable literal ``PROTOCOL`` assignment = no declared seam:
    every primitive site flags, which keeps the table honest (it cannot
    become computed without the lint noticing)."""
    src = "def f():\n    pltpu.semaphore_wait(s, 1)\n"
    violations = lint_source(src, "ring_attention_tpu/ops/pallas_ring.py")
    assert [v.rule for v in violations] == ["RA015"]


def test_lint_ra015_shipped_module_clean():
    """Package acceptance: every primitive site in the shipped fused
    module is covered by a declared row (RA013's file fence tightened to
    the verified seam, with nothing to excuse)."""
    from pathlib import Path

    import ring_attention_tpu.ops.pallas_ring as pr

    src = Path(pr.__file__).read_text()
    violations = lint_source(src, "ring_attention_tpu/ops/pallas_ring.py")
    assert [str(v) for v in violations] == []
