"""Tile-coverage prover: the skip grids held against an independent oracle.

Every speed claim in this repo rests on trace-time tile dropping — the
compact causal grids of ``ops/pallas_flash.py`` (``band_plan``) driven by
the per-hop band hints of ``parallel/ring.py`` — and until now nothing
*proved* that the compact grids visit exactly the tiles the mask
requires.  A skipped live tile is silently wrong attention (the missing
block never enters the online softmax); a visited dead tile is silent
perf loss; an interior-classified tile that is not actually full-band
adds UNMASKED garbage, because interior tiles compile the mask out.

The oracle here is deliberately independent of the kernels' offset
algebra: every check starts from GLOBAL token positions (FlashAttention's
tiling contract, arXiv 2205.14135 — attention is defined on positions,
tiles are an implementation detail).  For each strategy x layout x
masking row the prover enumerates, per ring hop and per rank:

  - which global query positions the device holds (contiguous, striped,
    zig-zag, counter-rotated — the q block travels under TokenRing,
    arXiv 2412.20501) and which global key positions the circulating
    stream delivers;
  - the ground-truth element mask (causal, sliding window, document
    equality) on those positions;

and holds the system under test to it at three levels:

  **soundness** — no live element is lost: a tile absent from the band
  table, a hop skipped by ``_hop_has_work``, or a "full span" hop must
  contain no live / only live elements respectively, and an
  interior-classified tile must be fully live for EVERY rank;
  **tightness** — no dead tile is visited: every WORK entry is live for
  some rank, every EDGE entry is non-full for some rank, and the
  closed-form ``_band_tile_count`` equals the enumerated table length;
  **schedule completeness** — summing each hop's computed elements per
  q-origin reproduces the intended global mask exactly once (nothing
  dropped between hops, nothing double-counted into the softmax).

All pure numpy + trace-time helpers — CPU, no devices, no compiles.
CLI: ``tools/check_contracts.py --coverage``; ``coverage_fingerprint``
gives the per-row tile counts (a mask change that visits dead tiles fails
``tests/test_coverage.py`` like a contract violation).
"""

from __future__ import annotations

from dataclasses import dataclass, field

import numpy as np

# ---------------------------------------------------------------------------
# Oracle construction (global positions — independent of the band algebra)
# ---------------------------------------------------------------------------


def _positions(layout: str, origin: int, n_local: int, ring: int) -> np.ndarray:
    """Global token positions of ``origin``'s local shard."""
    i = np.arange(n_local)
    if layout == "striped":
        return i * ring + origin
    if layout == "contiguous":
        return origin * n_local + i
    raise ValueError(f"unknown layout {layout!r}")


def _doc_of(doc_starts, total: int) -> np.ndarray:
    """Per-position document id for a declared packing layout."""
    ids = np.zeros(total, np.int64)
    for d, s in enumerate(doc_starts):
        ids[s:] = d
    return ids


def oracle_mask(qpos: np.ndarray, kpos: np.ndarray, window: int | None,
                doc_ids: np.ndarray | None = None) -> np.ndarray:
    """Ground-truth (nq, nk) attend mask from global positions: causal,
    optional exact sliding window, optional document equality."""
    m = kpos[None, :] <= qpos[:, None]
    if window is not None:
        m = m & (kpos[None, :] >= qpos[:, None] - (window - 1))
    if doc_ids is not None:
        m = m & (doc_ids[kpos][None, :] == doc_ids[qpos][:, None])
    return m


def band_mask(nq: int, nk: int, hi, lo) -> np.ndarray:
    """The runtime band predicate the kernels mask edge tiles with:
    attend iff ``lo <= j - i <= hi`` in local indices (``lo=None`` = no
    window; ``hi=None`` = unmasked)."""
    if hi is None:
        return np.ones((nq, nk), bool)
    diff = np.arange(nk)[None, :] - np.arange(nq)[:, None]
    m = diff <= int(hi)
    if lo is not None:
        m = m & (diff >= int(lo))
    return m


# ---------------------------------------------------------------------------
# One hop-instance: what one rank actually computes at one hop
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class HopInstance:
    """One rank's compute at one (hop, stream): the runtime decisions the
    compiled program makes, next to the oracle they must realize."""

    rank: int
    q_origin: int
    kv_origin: int
    oracle: np.ndarray  # (nq, nk) bool ground truth for this pairing
    static_live: np.ndarray  # truth from trace-droppable constraints only
    hi: int | None  # runtime band offsets the kernel masks with
    lo: int | None
    has_work: bool  # the traced hop-level skip decision
    full: bool  # trace-time full-span elision (no mask at all)
    seg_mask: np.ndarray | None = None  # runtime doc mask (misaligned docs)
    kpos: np.ndarray = field(default_factory=lambda: np.zeros(0, np.int64))
    # ^ global key columns this instance computes against (striped layouts
    #   deliver non-contiguous columns — the schedule check indexes them)
    rt_mask: np.ndarray | None = None
    # ^ generic runtime edge mask (mask-algebra lowerings that are not a
    #   band): when present it replaces the band/segment construction as
    #   the runtime predicate under test


def _instance_runtime(x: HopInstance, nq: int, nk: int) -> np.ndarray:
    """The runtime edge-tile mask this instance's kernel would apply —
    the band scalars (optionally intersected with the runtime document
    mask), or the generic lowering's predicate."""
    if x.rt_mask is not None:
        return x.rt_mask
    rt = band_mask(nq, nk, x.hi, x.lo)
    if x.seg_mask is not None:
        rt = rt & x.seg_mask
    return rt


def _tile_slices(plan, qi: int, ki: int):
    bq, bk = plan.block_q, plan.block_k
    return slice(qi * bq, (qi + 1) * bq), slice(ki * bk, (ki + 1) * bk)


def _check_table_structure(plan, label: str) -> list[str]:
    """The accumulator-lifecycle contract of the tables: outer-major
    order, FIRST/LAST exactly bracketing every outer row, inner index
    non-decreasing within a row (the carried online softmax / dq / dkv
    state is initialized at FIRST and written at LAST)."""
    from ..ops.pallas_flash import _TF_FIRST, _TF_LAST

    out: list[str] = []
    outer = plan.tile_q if plan.outer_is_q else plan.tile_k
    inner = plan.tile_k if plan.outer_is_q else plan.tile_q
    outer_n = plan.n_q_blocks if plan.outer_is_q else plan.n_k_blocks
    flags = plan.flags
    if len(flags) == 0:
        return [f"{label}: empty tile table [rule: tile-lifecycle]"]
    rows = 0
    for t in range(len(flags)):
        first = bool(flags[t] & _TF_FIRST)
        prev_last = t == 0 or bool(flags[t - 1] & _TF_LAST)
        if first != prev_last:
            out.append(
                f"{label}: table entry {t} breaks the FIRST/LAST bracketing "
                f"(accumulator would {'re-initialize mid-row' if first else 'carry across rows'}) "
                f"[rule: tile-lifecycle]"
            )
            break
        if first:
            rows += 1
        if not first and outer[t] != outer[t - 1]:
            out.append(
                f"{label}: entry {t} switches outer row {outer[t-1]}->"
                f"{outer[t]} without LAST/FIRST — the carried accumulator "
                f"would mix rows [rule: tile-lifecycle]"
            )
            break
        if not first and inner[t] <= inner[t - 1]:
            out.append(
                f"{label}: entry {t} revisits inner index {int(inner[t])} "
                f"after {int(inner[t-1])} in one outer row "
                f"[rule: tile-lifecycle]"
            )
            break
    if not out:
        if not (flags[-1] & _TF_LAST):
            out.append(
                f"{label}: final table entry lacks LAST — the last outer "
                f"row's output block is never written back "
                f"[rule: tile-lifecycle]"
            )
        elif rows != outer_n:
            out.append(
                f"{label}: table covers {rows} outer rows, grid has "
                f"{outer_n} — a missing row's output block is never "
                f"written [rule: tile-lifecycle]"
            )
    return out


def verify_plan(plan, instances: list[HopInstance], label: str) -> list[str]:
    """Hold one hop's band tables to the oracle across every rank that
    shares the compiled program.  Returns one-line violations."""
    from ..ops.pallas_flash import _TF_EDGE, _TF_WORK

    out: list[str] = []
    nq = plan.n_q_blocks * plan.block_q
    nk = plan.n_k_blocks * plan.block_k

    # closed form vs enumeration — the property every launch's SMEM-cap
    # decision rides on
    if plan.tiles != len(plan.tile_q):
        out.append(
            f"{label}: closed-form _band_tile_count says {plan.tiles} "
            f"tiles, enumerated table has {len(plan.tile_q)} "
            f"[rule: tile-count]"
        )
    out.extend(_check_table_structure(plan, label))

    work = {}
    for t in range(len(plan.flags)):
        if plan.flags[t] & _TF_WORK:
            work[(int(plan.tile_q[t]), int(plan.tile_k[t]))] = bool(
                plan.flags[t] & _TF_EDGE
            )

    active = [x for x in instances if x.has_work and not x.full]
    for x in instances:
        if not x.has_work:
            if x.oracle.any():
                qi, ki = np.argwhere(x.oracle)[0] // (plan.block_q,
                                                      plan.block_k)
                out.append(
                    f"{label}: rank {x.rank} hop-level skip drops live "
                    f"tile (q-tile {int(qi)}, k-tile {int(ki)}) "
                    f"[rule: tile-coverage-sound]"
                )
            continue
        if x.full:
            if not x.oracle.all():
                i, j = np.argwhere(~x.oracle)[0]
                out.append(
                    f"{label}: rank {x.rank} declared-full span holds a "
                    f"masked-out element at local ({int(i)}, {int(j)}) — "
                    f"it would enter the softmax unmasked "
                    f"[rule: tile-coverage-sound]"
                )
            continue
        rt = _instance_runtime(x, nq, nk)
        for qi in range(plan.n_q_blocks):
            for ki in range(plan.n_k_blocks):
                qs, ks = _tile_slices(plan, qi, ki)
                o_tile = x.oracle[qs, ks]
                if (qi, ki) not in work:
                    if o_tile.any():
                        out.append(
                            f"{label}: rank {x.rank} live tile (q-tile "
                            f"{qi}, k-tile {ki}) is absent from the band "
                            f"table — its keys never enter the softmax "
                            f"[rule: tile-coverage-sound]"
                        )
                    continue
                edge = work[(qi, ki)]
                if not edge:
                    if not x.static_live[qs, ks].all():
                        out.append(
                            f"{label}: rank {x.rank} interior tile "
                            f"(q-tile {qi}, k-tile {ki}) holds out-of-band "
                            f"elements but compiles the mask out "
                            f"[rule: tile-coverage-sound]"
                        )
                    continue
                computed = rt[qs, ks]
                if not np.array_equal(computed, o_tile):
                    kept_dead = computed & ~o_tile
                    kind = ("keeps a dead element" if kept_dead.any()
                            else "drops a live element")
                    i, j = np.argwhere(computed ^ o_tile)[0]
                    out.append(
                        f"{label}: rank {x.rank} edge tile (q-tile {qi}, "
                        f"k-tile {ki}) runtime mask {kind} at local "
                        f"({int(qi * plan.block_q + i)}, "
                        f"{int(ki * plan.block_k + j)}) "
                        f"[rule: tile-coverage-sound]"
                    )

    # tightness: aggregated across ranks (the table is one compiled
    # program shared by all of them)
    if active:
        for (qi, ki), edge in sorted(work.items()):
            qs, ks = _tile_slices(plan, qi, ki)
            if not any(x.static_live[qs, ks].any() for x in active):
                out.append(
                    f"{label}: dead tile (q-tile {qi}, k-tile {ki}) is "
                    f"visited — in the table but live for no rank "
                    f"[rule: tile-coverage-tight]"
                )
            elif edge and all(
                _instance_runtime(x, nq, nk)[qs, ks].all() for x in active
            ):
                out.append(
                    f"{label}: tile (q-tile {qi}, k-tile {ki}) is "
                    f"edge-classified but full-band for every rank — it "
                    f"pays the mask an interior tile would skip "
                    f"[rule: tile-coverage-tight]"
                )
    return out


# ---------------------------------------------------------------------------
# The strategy x layout x masking matrix
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class CoverageCase:
    """One row of the prover matrix (ring=1 is the single-sweep path)."""

    name: str
    ring: int = 1
    n_local: int = 32
    block: int = 8
    layout: str = "contiguous"
    window: int | None = None
    passes: int | None = None
    doc_starts: tuple[int, ...] | None = None
    bidirectional: bool = False
    counter: bool = False


CASES: tuple[CoverageCase, ...] = (
    CoverageCase("single/causal", ring=1, n_local=64, block=8),
    CoverageCase("single/causal/window", ring=1, n_local=64, block=8,
                 window=24),
    CoverageCase("single/docs-aligned", ring=1, n_local=64, block=8,
                 doc_starts=(0, 16, 32)),
    CoverageCase("single/docs-aligned/window", ring=1, n_local=64, block=8,
                 window=16, doc_starts=(0, 32)),
    CoverageCase("single/docs-misaligned", ring=1, n_local=64, block=8,
                 doc_starts=(0, 12, 40)),
    CoverageCase("ring/contiguous", ring=4, n_local=16, block=4),
    CoverageCase("ring/contiguous/window", ring=4, n_local=16, block=4,
                 window=24),
    CoverageCase("ring/limited-passes", ring=4, n_local=16, block=4,
                 window=8, passes=2),
    CoverageCase("ring/striped", ring=4, n_local=16, block=4,
                 layout="striped"),
    CoverageCase("ring/striped/window", ring=4, n_local=16, block=4,
                 layout="striped", window=20),
    CoverageCase("ring/bidirectional", ring=4, n_local=16, block=4,
                 bidirectional=True),
    CoverageCase("ring/bidirectional/striped", ring=4, n_local=16, block=4,
                 layout="striped", bidirectional=True),
    CoverageCase("counter/contiguous", ring=4, n_local=16, block=4,
                 counter=True),
    CoverageCase("counter/striped", ring=4, n_local=16, block=4,
                 layout="striped", counter=True),
    CoverageCase("counter/window", ring=4, n_local=16, block=4, window=24,
                 counter=True),
)


def _int_or_none(x):
    return None if x is None else int(x)


def _case_hop_instances(case: CoverageCase):
    """Yield ``(hop_label, stream, hint, windowed, nk, instances)`` per
    (hop, stream) of a case — the runtime/static values straight from the
    ring layer (the system under test), the oracles from global
    positions (the independent truth)."""
    from ..parallel import ring as ring_mod

    W, n = case.ring, case.n_local
    passes = case.passes or W
    striped = case.layout == "striped"
    streams = ring_mod._streams(case.bidirectional and passes == W, n)
    # doc ids span the GLOBAL position space: ring rows index them with
    # positions up to n*W - 1 (a declared layout is global by definition)
    doc_ids = (_doc_of(case.doc_starts, n * W)
               if case.doc_starts is not None else None)
    for i in range(passes):
        if case.counter:
            stream = (1, 0, n)
            full, hint = ring_mod._counter_static_band(
                i, n, True, striped, case.window, W
            )
            instances = []
            for r in range(W):
                qo, ko = ring_mod._counter_origins(r, i, W)
                hi, lo = ring_mod._hop_offsets(
                    qo, ko, n, True, striped, case.window, W
                )
                instances.append(_make_instance(
                    case, r, int(qo), int(ko), _int_or_none(hi),
                    _int_or_none(lo), full, 0, n, doc_ids,
                ))
            yield f"hop{i}", stream, hint, case.window is not None, n, \
                instances
        else:
            for si, stream in enumerate(streams):
                shift, ofs, nk = stream
                full, hint = ring_mod._static_hop_band(
                    stream, i, n, True, striped, case.window, W
                )
                instances = []
                for r in range(W):
                    ko = (r - shift * i) % W
                    hi, lo = ring_mod._stream_offsets(
                        stream, r, i, n, True, striped, case.window, W
                    )
                    instances.append(_make_instance(
                        case, r, r, int(ko), _int_or_none(hi),
                        _int_or_none(lo), full, ofs, nk, doc_ids,
                    ))
                tag = f"hop{i}" + (f"/stream{si}" if len(streams) > 1 else "")
                yield tag, stream, hint, case.window is not None, nk, \
                    instances


def _make_instance(case, rank, q_origin, kv_origin, hi, lo, full, ofs, nk,
                   doc_ids):
    from ..parallel import ring as ring_mod

    W, n = case.ring, case.n_local
    qpos = _positions(case.layout, q_origin, n, W)
    kpos = _positions(case.layout, kv_origin, n, W)[ofs:ofs + nk]
    truth = oracle_mask(qpos, kpos, case.window, doc_ids)
    aligned = (case.doc_starts is not None
               and all(s % case.block == 0 for s in case.doc_starts))
    static_live = (truth if (case.doc_starts is None or aligned)
                   else oracle_mask(qpos, kpos, case.window, None))
    seg_mask = None
    if case.doc_starts is not None and not aligned:
        seg_mask = doc_ids[kpos][None, :] == doc_ids[qpos][:, None]
    has_work = bool(ring_mod._hop_has_work(hi, lo, n, nk))
    return HopInstance(
        rank=rank, q_origin=q_origin, kv_origin=kv_origin, oracle=truth,
        static_live=static_live, hi=None if full else hi,
        lo=None if full else lo, has_work=has_work, full=full,
        seg_mask=seg_mask, kpos=kpos,
    )


@dataclass
class CoverageReport:
    """One matrix row's verdict plus the tile accounting the fingerprint
    and the perf gate pin."""

    name: str
    violations: list[str] = field(default_factory=list)
    hops: int = 0
    tiles: int = 0  # q-major (fwd/dq) table entries summed over hops
    work: int = 0
    edge: int = 0
    tiles_kmajor: int = 0  # dk/dv-pass tables (same hints, k-major)

    @property
    def ok(self) -> bool:
        return not self.violations

    def to_json(self) -> dict:
        return {
            "name": self.name, "ok": self.ok, "hops": self.hops,
            "tiles": self.tiles, "work": self.work, "edge": self.edge,
            "tiles_kmajor": self.tiles_kmajor,
            "violations": self.violations,
        }


def prove_case(case: CoverageCase) -> CoverageReport:
    """Run the full proof for one matrix row: per-hop table checks on the
    q-major AND k-major (backward dk/dv) grids, plus the cross-hop
    schedule-completeness check."""
    from ..ops.pallas_flash import band_plan

    report = CoverageReport(name=case.name)
    W, n = case.ring, case.n_local
    if case.doc_starts is not None and W > 1:
        # doc_starts is the SINGLE-SWEEP declaration (the kernels accept
        # it on local spans only; rings carry documents as segment_ids),
        # so a ring x docs row has no realizable system under test —
        # reject it loudly rather than prove an inconsistent layout
        raise ValueError(
            f"{case.name}: doc_starts rows are single-device (ring=1); "
            f"ring document layouts are segment_ids territory"
        )
    # schedule completeness: per q-origin, count how often each (q, k)
    # global element is computed across the whole hop schedule
    counts = {o: np.zeros((n, n * W), np.int64) for o in range(W)}
    visited = {o: np.zeros(n * W, bool) for o in range(W)}
    doc_ids_g = None
    if case.doc_starts is not None:
        doc_ids_g = _doc_of(case.doc_starts, n * W)  # ring=1 for doc rows

    for tag, stream, hint, windowed, nk, instances in \
            _case_hop_instances(case):
        report.hops += 1
        label = f"{case.name}/{tag}"
        full_hop = instances and instances[0].full
        if full_hop:
            # a trace-time full span runs the plain rectangular grid with
            # NO mask and no tables; the only things to prove are that
            # every computing rank's span is fully live and every
            # skipped rank's span is fully dead
            for x in instances:
                if x.has_work and not x.oracle.all():
                    i, j = np.argwhere(~x.oracle)[0]
                    report.violations.append(
                        f"{label}: rank {x.rank} declared-full span holds "
                        f"a masked-out element at local ({int(i)}, "
                        f"{int(j)}) — it would enter the softmax unmasked "
                        f"[rule: tile-coverage-sound]"
                    )
                elif not x.has_work and x.oracle.any():
                    report.violations.append(
                        f"{label}: rank {x.rank} hop-level skip drops "
                        f"live work [rule: tile-coverage-sound]"
                    )
        elif hint is None:
            report.violations.append(
                f"{label}: causal hop produced no static band hint "
                f"[rule: tile-coverage-sound]"
            )
            continue
        else:
            plan = band_plan((n, nk), (case.block, case.block), hint,
                             windowed=windowed, doc_starts=case.doc_starts)
            report.tiles += len(plan.tile_q)
            report.work += plan.work_tiles
            report.edge += plan.edge_tiles
            report.violations.extend(verify_plan(plan, instances, label))
            # the one-pass backward walks k-major tables built from the
            # same hint — same oracle, transposed accumulator lifecycle;
            # every band tile once is dq's proof as well as dk/dv's
            plan_k = band_plan((n, nk), (case.block, case.block), hint,
                               windowed=windowed,
                               doc_starts=case.doc_starts,
                               outer_is_q=False)
            report.tiles_kmajor += len(plan_k.tile_q)
            report.violations.extend(
                verify_plan(plan_k, instances, label + "/dkv")
            )
        for x in instances:
            if x.has_work:
                visited[x.q_origin][x.kpos] = True
                counts[x.q_origin][:, x.kpos] += (
                    1 if x.full else x.oracle
                )

    # cross-hop: every intended element exactly once, nothing twice
    for o in range(W):
        qpos = _positions(case.layout, o, n, W)
        intended = oracle_mask(qpos, np.arange(n * W), case.window,
                               doc_ids_g)
        intended = intended & visited[o][None, :]
        if not np.array_equal(counts[o], intended.astype(np.int64)):
            diff = counts[o] - intended.astype(np.int64)
            i, j = np.argwhere(diff)[0]
            kind = ("dropped from" if diff[i, j] < 0
                    else "double-counted into")
            report.violations.append(
                f"{case.name}: schedule {kind} the softmax: q-origin {o} "
                f"element (local q {int(i)}, global k {int(j)}) computed "
                f"{int(counts[o][i, j])}x, intended "
                f"{int(intended[i, j])}x [rule: tile-coverage-sound]"
            )
    return report


# ---------------------------------------------------------------------------
# Zig-zag: the rectangular-grid row (traced offsets, no tables)
# ---------------------------------------------------------------------------


def prove_zigzag(ring: int = 4, chunk: int = 8, block: int = 8,
                 ) -> CoverageReport:
    """The zig-zag path uses traced per-chunk offsets on the RECTANGULAR
    grid (no band tables), so the system under test here is the runtime
    tile predicate set — ``_tile_has_work`` / ``_tile_is_edge`` /
    the band mask — against the same global-position oracle."""
    from ..ops import pallas_flash as pf

    report = CoverageReport(name="zigzag/causal")
    n_global = 2 * ring * chunk
    bq = min(block, chunk)
    bk = block
    while n_global % bk:
        bk //= 2
    for r in range(ring):
        for which, start in ((0, r * chunk), (1, (2 * ring - 1 - r) * chunk)):
            report.hops += 1
            qpos = start + np.arange(chunk)
            kpos = np.arange(n_global)
            truth = oracle_mask(qpos, kpos, None)
            offs = np.asarray([start, 0], np.int64)
            label = f"zigzag/rank{r}/chunk{which}"
            for qi in range(chunk // bq):
                for ki in range(n_global // bk):
                    row0, col0 = qi * bq, ki * bk
                    o_tile = truth[row0:row0 + bq, col0:col0 + bk]
                    has_work = bool(pf._tile_has_work(
                        offs, row0, col0, bq, bk, True, False
                    ))
                    edge = bool(pf._tile_is_edge(
                        offs, row0, col0, bq, bk, True, False
                    ))
                    report.tiles += 1
                    if not has_work:
                        if o_tile.any():
                            report.violations.append(
                                f"{label}: live tile (q-tile {qi}, k-tile "
                                f"{ki}) fails the runtime skip predicate "
                                f"[rule: tile-coverage-sound]"
                            )
                        continue
                    report.work += 1
                    if not edge:
                        if not o_tile.all():
                            report.violations.append(
                                f"{label}: interior-classified tile "
                                f"(q-tile {qi}, k-tile {ki}) holds dead "
                                f"elements but skips the mask "
                                f"[rule: tile-coverage-sound]"
                            )
                        continue
                    report.edge += 1
                    # the kernel's iota mask: cols + col0 <= rows + row0 + hi
                    diff = (np.arange(bk)[None, :] + col0) - (
                        np.arange(bq)[:, None] + row0
                    )
                    rt = diff <= start
                    if not np.array_equal(rt, o_tile):
                        report.violations.append(
                            f"{label}: edge tile (q-tile {qi}, k-tile {ki}) "
                            f"runtime band disagrees with the oracle "
                            f"[rule: tile-coverage-sound]"
                        )
                    if o_tile.all():
                        report.violations.append(
                            f"{label}: tile (q-tile {qi}, k-tile {ki}) "
                            f"edge-classified but fully live "
                            f"[rule: tile-coverage-tight]"
                        )
    return report


# ---------------------------------------------------------------------------
# Mask-algebra rows: arbitrary oracles through the certifying compiler
# ---------------------------------------------------------------------------
#
# PR 11 generalizes the fixed matrix above: ``ring_attention_tpu/masks.py``
# lowers arbitrary mask compositions to the same compact tile tables and
# per-hop work/skip schedules, and :func:`prove_mask_lowering` holds every
# emitted grid to the mask's own global-position oracle.  Band-shaped
# masks lower through the SHIPPING seams (band_plan + the ring hop-band
# helpers), so those rows re-certify the real kernels' grids through the
# mask API; generic masks (prefix-LM, dilated, per-head, Or/Not
# compositions) certify the algebra's tile classifier — the extension
# seam future kernels will launch from.


@dataclass(frozen=True)
class MaskCoverageCase:
    """One mask-algebra matrix row: a textual mask expression (through
    the registry parser, so the row also exercises the mini-language)
    over one execution geometry."""

    name: str
    expr: str
    strategy: str = "single"
    layout: str = "contiguous"
    ring: int = 1
    n_local: int = 64
    block: int = 8
    passes: int | None = None


MASK_CASES: tuple[MaskCoverageCase, ...] = (
    MaskCoverageCase("mask/single/full", "full"),
    MaskCoverageCase("mask/single/causal", "causal"),
    MaskCoverageCase("mask/single/causal-window", "causal&window:24"),
    MaskCoverageCase("mask/single/window-2sided", "window:16"),
    MaskCoverageCase("mask/single/prefixlm", "prefix:24"),
    MaskCoverageCase("mask/single/prefix-window", "prefix:16&window:24"),
    MaskCoverageCase("mask/single/dilated", "causal&dilated:4"),
    MaskCoverageCase("mask/single/docs-causal", "causal&docs:0,16,40"),
    MaskCoverageCase("mask/single/docs-misaligned", "causal&docs:0,12,40"),
    MaskCoverageCase("mask/single/prefix-or-docs", "prefix:16|docs:0,32"),
    MaskCoverageCase("mask/single/far-past", "causal&~window:8"),
    MaskCoverageCase("mask/single/perhead",
                     "perhead(causal;causal&window:16)"),
    MaskCoverageCase("mask/ring/causal", "causal", strategy="ring",
                     ring=4, n_local=16, block=4),
    MaskCoverageCase("mask/ring/causal-window", "causal&window:24",
                     strategy="ring", ring=4, n_local=16, block=4),
    MaskCoverageCase("mask/ring/striped-window", "causal&window:20",
                     strategy="ring", layout="striped", ring=4,
                     n_local=16, block=4),
    MaskCoverageCase("mask/ring/limited-passes", "causal&window:8",
                     strategy="ring", ring=4, n_local=16, block=4,
                     passes=2),
    MaskCoverageCase("mask/ring/prefixlm", "prefix:24", strategy="ring",
                     ring=4, n_local=16, block=4),
    MaskCoverageCase("mask/ring/dilated", "causal&dilated:2",
                     strategy="ring", ring=4, n_local=16, block=4),
    MaskCoverageCase("mask/counter/causal", "causal", strategy="counter",
                     ring=4, n_local=16, block=4),
    MaskCoverageCase("mask/counter/window", "causal&window:24",
                     strategy="counter", ring=4, n_local=16, block=4),
    MaskCoverageCase("mask/counter/prefixlm", "prefix:24",
                     strategy="counter", ring=4, n_local=16, block=4),
)


def _expected_pairings(spec, i: int) -> list[tuple[int, int, int]]:
    """``(rank, q_origin, kv_origin)`` rows of hop ``i`` — recomputed
    HERE from the schedule definitions (single sweep; ring: hop ``i``
    delivers origin ``rank - i``; counter-rotation: the Q stream has
    moved ``ceil(i/2)`` times and KV ``floor(i/2)``, pairing invariant
    ``q_origin - kv_origin ≡ i``), independently of the lowering's own
    origin bookkeeping, which is cross-checked against this."""
    if spec.strategy == "single":
        return [(0, 0, 0)]
    W = spec.ring
    if spec.strategy == "counter":
        return [
            (r, (r + (i + 1) // 2) % W, (r - i // 2) % W) for r in range(W)
        ]
    return [(r, r, (r - i) % W) for r in range(W)]


def prove_mask_lowering(mask, spec, lowering=None) -> CoverageReport:
    """Hold one mask lowering (``masks.lower(mask, spec)``) to the
    mask's own oracle: per-hop table soundness/tightness on the q-major
    AND k-major grids, hop-pairing agreement with the independently
    recomputed schedule, and cross-hop exactly-once completeness.

    ``lowering`` overrides the freshly-built one (the negative-toy seam:
    a doctored lowering must fail with a one-line diagnostic naming the
    mask, hop, and tile)."""
    from .. import masks as masks_mod

    mask = masks_mod.static_mask(mask)  # runtime Segments mask in-kernel
    m = mask.head_mask(spec.head) if mask.per_head else mask
    if lowering is None:
        lowering = masks_mod.lower(mask, spec)
    W, n = spec.ring, spec.n_local
    report = CoverageReport(name=f"{m.key}/{spec.strategy}")
    counts = {o: np.zeros((n, n * W), np.int64) for o in range(W)}
    visited = {o: np.zeros(n * W, bool) for o in range(W)}

    for hop in lowering.hops:
        report.hops += 1
        label = f"{m.key}/{spec.strategy}:{spec.layout}/hop{hop.hop}"
        expected = _expected_pairings(spec, hop.hop)
        if len(hop.ranks) != len(expected):
            report.violations.append(
                f"{label}: lowering schedules {len(hop.ranks)} ranks, "
                f"the {spec.strategy} hop has {len(expected)} "
                f"[rule: tile-coverage-sound]"
            )
            continue
        instances = []
        for rp, (r, qo, ko) in zip(hop.ranks, expected):
            if (rp.rank, rp.q_origin, rp.kv_origin) != (r, qo, ko):
                report.violations.append(
                    f"{label}: rank {r} pairing disagrees — lowering says "
                    f"q-origin {rp.q_origin} x kv-origin {rp.kv_origin}, "
                    f"the schedule pairs {qo} x {ko} "
                    f"[rule: tile-coverage-sound]"
                )
            qpos = _positions(spec.layout, qo, n, W)
            kpos = _positions(spec.layout, ko, n, W)
            truth = m.oracle(qpos, kpos)
            instances.append(HopInstance(
                rank=r, q_origin=qo, kv_origin=ko, oracle=truth,
                static_live=truth, hi=rp.hi, lo=rp.lo,
                has_work=rp.has_work, full=hop.full, kpos=kpos,
                rt_mask=rp.rt_mask,
            ))
        if hop.full:
            for x in instances:
                if x.has_work and not x.oracle.all():
                    i, j = np.argwhere(~x.oracle)[0]
                    report.violations.append(
                        f"{label}: rank {x.rank} declared-full span holds "
                        f"a masked-out element at local ({int(i)}, "
                        f"{int(j)}) — it would enter the softmax unmasked "
                        f"[rule: tile-coverage-sound]"
                    )
                elif not x.has_work and x.oracle.any():
                    report.violations.append(
                        f"{label}: rank {x.rank} hop-level skip drops "
                        f"live work [rule: tile-coverage-sound]"
                    )
        elif hop.plan is None or hop.plan_kmajor is None:
            report.violations.append(
                f"{label}: non-full hop lowered without tile tables "
                f"[rule: tile-coverage-sound]"
            )
            continue
        else:
            report.tiles += len(hop.plan.tile_q)
            report.work += hop.plan.work_tiles
            report.edge += hop.plan.edge_tiles
            report.violations.extend(verify_plan(hop.plan, instances,
                                                 label))
            report.tiles_kmajor += len(hop.plan_kmajor.tile_q)
            report.violations.extend(
                verify_plan(hop.plan_kmajor, instances, label + "/dkv")
            )
        for x in instances:
            if x.has_work:
                visited[x.q_origin][x.kpos] = True
                counts[x.q_origin][:, x.kpos] += (
                    1 if x.full else x.oracle
                )

    for o in range(W):
        qpos = _positions(spec.layout, o, n, W)
        intended = m.oracle(qpos, np.arange(n * W))
        intended = intended & visited[o][None, :]
        if not np.array_equal(counts[o], intended.astype(np.int64)):
            diff = counts[o] - intended.astype(np.int64)
            i, j = np.argwhere(diff)[0]
            kind = ("dropped from" if diff[i, j] < 0
                    else "double-counted into")
            report.violations.append(
                f"{m.key}/{spec.strategy}: schedule {kind} the softmax: "
                f"q-origin {o} element (local q {int(i)}, global k "
                f"{int(j)}) computed {int(counts[o][i, j])}x, intended "
                f"{int(intended[i, j])}x [rule: tile-coverage-sound]"
            )
    return report


def prove_mask_case(case: MaskCoverageCase) -> CoverageReport:
    """One mask-algebra matrix row: parse the expression, lower it onto
    the case's geometry, and prove every head variant's grids."""
    from ..masks import GridSpec, parse_mask

    mask = parse_mask(case.expr)
    heads = mask.head_period
    report = CoverageReport(name=case.name)
    for h in range(heads):
        spec = GridSpec(
            strategy=case.strategy, layout=case.layout, ring=case.ring,
            n_local=case.n_local, block_q=case.block,
            block_k=case.block, passes=case.passes, head=h,
        )
        part = prove_mask_lowering(mask, spec)
        report.violations.extend(part.violations)
        report.hops += part.hops
        report.tiles += part.tiles
        report.work += part.work
        report.edge += part.edge
        report.tiles_kmajor += part.tiles_kmajor
    return report


# ---------------------------------------------------------------------------
# Fused ring: the single-launch grid (ops/pallas_ring.py) held to the
# same global-position oracle
# ---------------------------------------------------------------------------

FUSED_CASES: tuple[CoverageCase, ...] = (
    CoverageCase("fused/contiguous", ring=4, n_local=16, block=4),
    CoverageCase("fused/contiguous/window", ring=4, n_local=16, block=4,
                 window=24),
    CoverageCase("fused/limited-passes", ring=4, n_local=16, block=4,
                 window=8, passes=2),
    CoverageCase("fused/striped", ring=4, n_local=16, block=4,
                 layout="striped"),
    CoverageCase("fused/striped/window", ring=4, n_local=16, block=4,
                 layout="striped", window=20),
)


def prove_fused_case(case: CoverageCase) -> CoverageReport:
    """Prove one fused-ring row: the prefetched hop tables
    (``parallel/ring.py::_fused_tables``) and the kernel's per-tile live
    predicate (``ops/pallas_ring.py::_fused_local_kernel``) against the
    global-position oracle.

    The fused kernel has no interior/edge split — every live tile applies
    the band mask in-kernel — so the obligations are: (a) the per-hop
    band exactly realizes the oracle for that (rank, origin) pairing
    (``work=0`` hops must be all-dead); (b) a tile the live predicate
    skips holds no live element and a tile it visits holds at least one;
    (c) summing live elements across the in-launch hop schedule
    reproduces the intended global mask exactly once per element."""
    from ..parallel import ring as ring_mod

    report = CoverageReport(name=case.name)
    W, n, blk = case.ring, case.n_local, case.block
    passes = case.passes or W
    striped = case.layout == "striped"
    for rank in range(W):
        origins, his, los, works = (
            np.asarray(t) for t in ring_mod._fused_tables(
                rank, passes, n, True, striped, case.window, W
            )
        )
        qpos = _positions(case.layout, rank, n, W)
        counts = np.zeros((n, n * W), np.int64)
        visited = np.zeros(n * W, bool)
        for hop in range(passes):
            report.hops += 1
            label = f"{case.name}/rank{rank}/hop{hop}"
            origin, work = int(origins[hop]), bool(works[hop])
            hi, lo = int(his[hop]), int(los[hop])
            if origin != (rank - hop) % W:
                report.violations.append(
                    f"{label}: table origin {origin}, the in-launch KV "
                    f"stream delivers {(rank - hop) % W} at this hop "
                    f"[rule: tile-coverage-sound]"
                )
                continue
            kpos = _positions(case.layout, origin, n, W)
            truth = oracle_mask(qpos, kpos, case.window)
            # (a) the runtime band IS the oracle for this pairing —
            # the sentinel offsets (hi=n, lo=-n) are vacuous over the
            # in-block diff range, so band_mask takes them unconditionally
            rt = (band_mask(n, n, hi, lo) if work
                  else np.zeros((n, n), bool))
            if not np.array_equal(rt, truth):
                i, j = np.argwhere(rt ^ truth)[0]
                kind = ("drops live" if truth[i, j] else "admits dead")
                report.violations.append(
                    f"{label}: band (hi={hi}, lo={lo}, work={int(work)}) "
                    f"{kind} element at local ({int(i)}, {int(j)}) "
                    f"[rule: tile-coverage-sound]"
                )
                continue
            # (b) the kernel's per-tile live predicate, verbatim
            for qi in range(n // blk):
                for kb in range(n // blk):
                    row0, col0 = qi * blk, kb * blk
                    live = (work
                            and col0 <= row0 + blk - 1 + hi
                            and col0 + blk - 1 >= row0 + lo)
                    report.tiles += 1
                    t = truth[row0:row0 + blk, col0:col0 + blk]
                    if live:
                        report.work += 1
                        if not t.any():
                            report.violations.append(
                                f"{label}: live predicate visits dead "
                                f"tile (q {qi}, k {kb}) "
                                f"[rule: tile-coverage-tight]"
                            )
                    elif t.any():
                        report.violations.append(
                            f"{label}: live predicate skips tile "
                            f"(q {qi}, k {kb}) holding live work "
                            f"[rule: tile-coverage-sound]"
                        )
            if work:
                visited[kpos] = True
                counts[:, kpos] += truth
        # (c) exactly-once across the in-launch schedule
        intended = oracle_mask(qpos, np.arange(n * W), case.window)
        intended = intended & visited[None, :]
        if not np.array_equal(counts, intended.astype(np.int64)):
            diff = counts - intended.astype(np.int64)
            i, j = np.argwhere(diff)[0]
            kind = ("dropped from" if diff[i, j] < 0
                    else "double-counted into")
            report.violations.append(
                f"{case.name}: fused schedule {kind} the softmax: rank "
                f"{rank} element (local q {int(i)}, global k {int(j)}) "
                f"computed {int(counts[i, j])}x, intended "
                f"{int(intended[i, j])}x [rule: tile-coverage-sound]"
            )
    return report


def prove_fused() -> list[CoverageReport]:
    """All fused-ring rows (the coverage half of the fused acceptance)."""
    return [prove_fused_case(case) for case in FUSED_CASES]


# ---------------------------------------------------------------------------
# Suite + fingerprint
# ---------------------------------------------------------------------------


def run_coverage_suite() -> list[CoverageReport]:
    """Every matrix row — the fixed strategy x layout x masking rows,
    the zig-zag rectangular-grid row, the mask-algebra rows, and the
    fused-ring single-launch grid.  All-ok == every grid the compiler
    emits is proven sound and tight."""
    reports = [prove_case(case) for case in CASES]
    reports.append(prove_zigzag())
    reports.extend(prove_mask_case(case) for case in MASK_CASES)
    reports.extend(prove_fused())
    return reports


def coverage_fingerprint() -> dict:
    """Exact per-row tile accounting: a future mask/hint change that
    grows (dead tiles visited) or shrinks (live tiles at risk) any row's
    table moves it, and the row's own proof fails."""
    fp: dict = {}
    ok = True
    for report in run_coverage_suite():
        fp[report.name] = {
            "tiles": report.tiles,
            "work": report.work,
            "edge": report.edge,
            "tiles_kmajor": report.tiles_kmajor,
        }
        ok = ok and report.ok
    fp["coverage_ok"] = ok
    return fp
