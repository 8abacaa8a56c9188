"""Pallas (Mosaic) flash-attention kernels for TPU.

TPU-native replacement for the reference's Triton kernels
(``triton_flash_attn.py``): the forward either emits the raw online-softmax
partials ``(acc, m, l)`` for XLA-side merging, or — the ring-hop fast path —
*continues* a carry in-kernel (``carry=...``, the reference's
``LOAD_ACCUMULATED`` resume, ref ``triton_flash_attn.py:124-165``) and on
the final span writes normalized ``q.dtype`` output + lse directly
(``fused``, the reference's ``RETURN_NORMALIZED_OUTPUT``, ref
``triton_flash_attn.py:273-275``), so the f32 accumulator triple never
round-trips HBM between hops.

Masking uses the same unified *banded causal offset* contract as
``ops/flash.py`` (attend iff ``lo <= j - i <= hi``: plain causal hi =
offset, striped diagonal hi = 0/-1, windows via the lo offset), passed as
runtime scalars in SMEM so one compiled kernel
serves every ring position under SPMD (the reference compiles
``CAUSAL_MASK_DIAGONAL`` variants instead, ref ``triton_flash_attn.py:216-221``).

The backward is one kernel without atomics — a k-major pass (grid over KV
blocks, queries streamed) that makes the scores and dP once a tile and
from them dk/dv (VMEM accumulators) and dq (read-add-written in HBM by
hand-started copies) — where the reference's Triton backward needs
sequence-parallel ``atomic_add`` workarounds (ref
``triton_flash_attn.py:763-776``); TPU has no relaxed atomics.

GQA: query heads are served by ``kv_head = q_head // g`` through BlockSpec
index maps (no materialized repeat); dk/dv are emitted per query head and
group-summed outside (ref ``ring_flash_attention.py:370-371``).

The in-kernel carry above still costs one launch PER HOP;
``ops/pallas_ring.py`` builds on this module's seams (``_block_sizes``
tile fitting, ``_online_update`` softmax algebra, the banded-offset mask
contract) to run the WHOLE ring schedule as ONE launch — the next hop's
KV double-buffered via in-kernel async remote DMA, the ``(acc, m, l)``
carry living in VMEM scratch (local tier) or staged per tile through an
HBM spill (remote tier).  ``impl="fused"`` on
``ring_flash_attention`` selects it; the backward retains this module's
one-pass kernel.
"""

from __future__ import annotations

import functools
import logging
import math
import os
import warnings
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

import bisect

from .attention import (
    EPSILON,
    MASK_VALUE,
    PAD_SEGMENT_ID,
    normalize_segment_ids,
)
from . import quant as _quant
from .quant import QuantizedBlockKV
from ..utils import compat
from ..utils.validate import check_attention_args

_log = logging.getLogger(__name__)


def _log_launch(name, nq, nk, h, hk, d, bq, bk, grid, compact=False):
    """Every launch logs its fitted tile at trace time (INFO): the one
    record of which tile a path actually asked Mosaic for —
    ``chip_smoke.py`` reads it instead of re-deriving the fit."""
    _log.info("%s: q=%d kv=%d heads=%d/%d d=%d tile=%dx%d grid=%s%s", name,
              nq, nk, h, hk, d, bq, bk, "compact" if compact else "rect",
              tuple(grid))


# Tuned on one TPU v5e chip, 2026-07-29 (seq 262144, h=8, d=64, bf16,
# causal): 1024x1024 won both sweeps — 57.7 fwd TFLOPs/chip on the
# rectangular grid, 67.6 with the compacted causal grid; >=16MB f32 score
# tiles (2048x2048, 1024x4096) were rejected by Mosaic on this generation.
DEFAULT_BLOCK_Q = 1024
DEFAULT_BLOCK_K = 1024
# Minor (lane) tile of a TPU vreg: a block dimension that is not the full
# array extent must be a multiple of this on the last axis (and of 8 on
# the second-to-last), so spans the kernels tile are padded to it.
LANE = 128
# One-pass backward (_bwd_kernel).  _DQ_SLOTS: the (block_q, d) f32 dq
# tiles a launch keeps in VMEM — the one being accumulated, the next
# one's prefetch and the previous one's write-back (1.5 MB at the default
# block and d = 128).  _TF_QFIRST: its own tile flag, beside the _TF_*
# flags of the compact grids below — the first tile of the k-major walk
# that touches its q block, whose dq tile starts from zeros instead of a
# read.
_DQ_SLOTS = 3
_TF_QFIRST = 16


def _unify_vma(*arrays):
    """pcast every array to the union of all arrays' shard_map varying axes.

    Inside ``shard_map`` the traced causal offset (derived from
    ``axis_index``) varies over fewer mesh axes than q/k/v; pallas requires
    uniform varying-axes types across its operands."""
    union = set()
    for a in arrays:
        if a is not None:
            union |= set(getattr(compat.typeof(a), "vma", frozenset()))

    def cast(a):
        if a is None:
            return None
        missing = tuple(union - set(getattr(compat.typeof(a), "vma", frozenset())))
        return compat.pcast(a, missing, to="varying") if missing else a

    return [cast(a) for a in arrays]


def _sds(shape, dtype, like):
    """ShapeDtypeStruct matching ``like``'s shard_map varying-axes type."""
    vma = getattr(compat.typeof(like), "vma", None)
    if vma:
        return jax.ShapeDtypeStruct(shape, dtype, vma=vma)
    return jax.ShapeDtypeStruct(shape, dtype)


def _interpret_default() -> bool:
    return jax.default_backend() != "tpu"


# log2-space scoring (candidate VPU optimization, A/B flag): fold
# scale*log2(e) into q so the per-tile softmax runs p = exp2(s2 - m2) with
# NO per-element multiply — neither the scale multiply nor exp's internal
# range-scaling one (exp lowers as exp2(x*log2e)).  p/l/acc are value-
# identical (exp2(a*log2e - b*log2e) == exp(a - b)); only the running max
# changes basis and converts back (m = m2*ln2) at the final write, a
# (bq, 1) op per block.  Costs one extra rounding of q by a non-power-of-
# two constant (~2^-24 f32 / ~2^-9 bf16 relative — the level of bf16
# storage noise).  Default OFF until measured on silicon: the win is zero
# if Mosaic dispatches exp at the same rate as exp2
# (unmeasured; ROADMAP S3 holds the A/B rule).
LOG2E = 1.4426950408889634
LN2 = 0.6931471805599453


def _exp2_default() -> bool:
    """Env-var default for log2-space scoring — read at TRACE time.

    The flag is captured when a caller traces (first call / new shapes),
    so toggling ``RING_ATTN_EXP2`` after a jitted caller has compiled
    silently has no effect on that compilation — an A/B harness that
    flips the env var mid-process would re-measure the stale basis.
    In-process A/B therefore passes ``exp2=`` explicitly to the public
    entry points (``pallas_flash_attention`` / ``pallas_flash_partials``
    / ``pallas_flash_fused`` / ``pallas_flash_backward``), which both
    bypasses the env var and keys the jit cache correctly; the env var
    remains the right knob for per-process A/B (``env RING_ATTN_EXP2=1
    python benchmarks/run.py ...``).  The
    attention custom_vjp resolves the flag ONCE per call in
    ``pallas_flash_attention``, so its forward and backward can never
    disagree on the basis.
    """
    return os.environ.get("RING_ATTN_EXP2", "0") == "1"


def _block_sizes(nq: int, nk: int, block_q: int | None, block_k: int | None):
    bq = min(block_q or DEFAULT_BLOCK_Q, nq)
    bk = min(block_k or DEFAULT_BLOCK_K, nk)
    while nq % bq:
        bq //= 2
    while nk % bk:
        bk //= 2
    return max(bq, 1), max(bk, 1)



def _tileable_len(n: int, block: int) -> int:
    """``n`` rounded up to a :data:`LANE` multiple when it has to be tiled
    (longer than one block).  :func:`_block_sizes` halves a block until it
    divides the span, so an odd span — a power-of-two batch after the
    label shift — halves it down to the 1x1 tile Mosaic rejects; a span
    of at most one block runs as a single full-extent block and needs no
    padding."""
    return n if n <= block else n + (-n) % LANE


def _tile_has_work(offs_ref, row0, col0, bq, bk, causal, windowed):
    """Block-level skip predicate: does tile (rows row0.., cols col0..) touch
    the band ``offs[1] <= j - i <= offs[0]``?  True when not causal."""
    if not causal:
        return True
    ok = col0 <= row0 + bq - 1 + offs_ref[0]
    if windowed:
        ok = jnp.logical_and(ok, col0 + bk - 1 >= row0 + offs_ref[1])
    return ok


def _tile_is_edge(offs_ref, row0, col0, bq, bk, causal, windowed):
    """True when the tile straddles the band boundary (some element is
    out-of-band) — only those tiles need the iota/compare/select mask.
    Mirrors the static ``interior`` classification in ``_band_tables`` so
    the compact and rectangular grids run identical per-tile expressions
    (keeping them bit-exact against each other)."""
    if not causal:
        return jnp.bool_(False)
    interior = col0 + bk - 1 <= row0 + offs_ref[0]
    if windowed:
        interior = jnp.logical_and(
            interior, col0 >= row0 + bq - 1 + offs_ref[1]
        )
    return jnp.logical_not(interior)


def _dispatch_tile(offs_ref, row0, col0, bq, bk, causal, windowed, tile):
    """Run ``tile()`` under the band's block-skip / edge-vs-interior
    predicates.  Interior tiles (fully in-band) take a variant with the
    causal/window mask construction statically compiled out — the per-tile
    iota/compare/select is ~half the non-matmul work, and at long sequence
    nearly every tile is interior.  A kv-padding mask still applies there.
    """
    if not causal:
        tile()
        return
    has_work = _tile_has_work(offs_ref, row0, col0, bq, bk, causal, windowed)
    edge = _tile_is_edge(offs_ref, row0, col0, bq, bk, causal, windowed)

    @pl.when(has_work & edge)
    def _compute_edge():
        tile()

    @pl.when(has_work & jnp.logical_not(edge))
    def _compute_interior():
        tile(causal=False, windowed=False)


def _dispatch_tile_compact(tf, tile):
    """Compact-grid analogue of :func:`_dispatch_tile`: the edge/interior
    classification was resolved at table-build time into the ``EDGE`` flag
    (compact grids exist only for static causal bands)."""
    work = (tf & _TF_WORK) != 0
    edge = (tf & _TF_EDGE) != 0

    @pl.when(work & edge)
    def _compute_edge():
        tile()

    @pl.when(work & jnp.logical_not(edge))
    def _compute_interior():
        tile(causal=False, windowed=False)


def _tile_closure(fn, kw, *args):
    """``tile(**over)`` closure for the dispatchers: runs ``fn(*args)`` with
    the kernel's shared tile kwargs, per-call-overridable (the interior fast
    path overrides ``causal``/``windowed``)."""

    def tile(**over):
        fn(*args, **{**kw, **over})

    return tile


def _tile_keep(offs_ref, row0, col0, shape, q_dim, causal, windowed, kvm_ref,
               qseg_ref=None, kseg_ref=None):
    """Per-element keep mask for a score tile, or None if unmasked.

    ``q_dim`` is the tile dimension holding query rows (0 in fwd/dq tiles,
    1 in the transposed dk/dv tiles); the other dimension holds key cols.
    The per-token refs arrive already oriented for the tile (see
    :func:`_token_vectors`): a q-side vector is a ``(bq, 1)`` column when
    ``q_dim == 0`` and a ``(1, bq)`` row when ``q_dim == 1``, the k-side
    one the other way round — so every combination below is a plain
    broadcast, with no in-kernel layout change.  ``qseg_ref``/``kseg_ref`` are
    per-token document ids for packed sequences — attention keeps
    same-document pairs only.
    """
    masked = kvm_ref is not None
    segmented = qseg_ref is not None
    if not (causal or masked or segmented):
        return None
    keep = None
    if causal:
        rows = row0 + lax.broadcasted_iota(jnp.int32, shape, q_dim)
        cols = col0 + lax.broadcasted_iota(jnp.int32, shape, 1 - q_dim)
        keep = cols <= rows + offs_ref[0]
        if windowed:
            keep = jnp.logical_and(keep, cols >= rows + offs_ref[1])
    if masked:
        kvm = kvm_ref[0] != 0
        keep = kvm if keep is None else jnp.logical_and(keep, kvm)
    if segmented:
        same = qseg_ref[0] == kseg_ref[0]
        keep = same if keep is None else jnp.logical_and(keep, same)
    return keep


def _token_vectors(x, column: bool):
    """Lay a per-token ``(rows, n)`` operand out so a ``(1, block)`` slice
    of it is a legal TPU block: ``(rows, n, 1)`` blocked ``(1, block, 1)``
    when the tile wants a column, ``(rows, 1, n)`` blocked ``(1, 1,
    block)`` when it wants a row.  Blocking the 2-D array ``(1, block)``
    directly puts a 1 on the second-to-last block axis, which Mosaic
    accepts only when ``rows == 1``.  Pair with :func:`_token_spec`."""
    return x[:, :, None] if column else x[:, None, :]


def _token_spec(block: int, column: bool, index_map):
    """BlockSpec for a :func:`_token_vectors` operand; ``index_map``
    returns the ``(row, block_index)`` pair of the 2-D layout."""
    def index_3d(*args):
        row, blk = index_map(*args)
        return (row, blk, 0) if column else (row, 0, blk)

    return pl.BlockSpec(
        (1, block, 1) if column else (1, 1, block), index_3d,
        memory_space=pltpu.VMEM,
    )


# ---------------------------------------------------------------------------
# Compacted causal grids
#
# With a rectangular (outer, inner) tile grid, causal masking skips ~half the
# tiles via pl.when — but every skipped tile still costs a grid step and its
# automatic block DMA (measured on v5e at seq 262144: causal ran only 1.64x
# faster than full instead of 2x).  When the band is statically describable —
# offsets that ARE Python ints (the single-device path), or traced offsets
# whose candidate set is bracketed by a caller ``band_hint`` (ring hops: the
# unrolled hop loop knows each hop's possible offsets, parallel/ring.py) —
# we instead flatten the tile space to just the active tiles:
# scalar-prefetched tables map the linear grid step t to its (outer, inner)
# tile and carry first/last/has-work flags for the accumulator lifecycle.
# This is the TPU answer to the reference kernel's per-block early-exit
# (ref ``triton_flash_attn.py:188-199``): same skipping, but resolved at
# trace time into a smaller grid rather than at runtime.
# ---------------------------------------------------------------------------

_TF_FIRST, _TF_LAST, _TF_WORK, _TF_EDGE = 1, 2, 4, 8

# The compact grid's (t_q, t_k, flags) tables are scalar-prefetched into
# SMEM; small blocks at long sequence can blow past it (512x512 at seq
# 262144 is ~131k tiles x 3 tables x 4B ~ 1.6 MB — Mosaic rejects the
# compile).  Beyond this cap the rectangular grid (runtime predicates, no
# tables) is used instead.
_MAX_COMPACT_TILES = 65536


def _warn_demoted(kind: str, tiles: int, stacklevel: int = 4) -> None:
    """Loud demotion (VERDICT r2 weak #5): losing the compact grid is a
    ~1.17x silent perf cliff at the north-star shape; tell the user which
    call fell off and why so they can grow the block size.

    ``stacklevel`` points the warning at the user's call site: 4 for the
    forward (warn <- _flash_fwd_call <- partials/fused wrapper <- user),
    3 for the backward's one-shorter chain."""
    warnings.warn(
        f"pallas flash {kind}: compact causal grid demoted to the "
        f"rectangular grid ({tiles} band tiles > SMEM table cap "
        f"{_MAX_COMPACT_TILES}); skipped tiles now cost a grid step + block "
        f"DMA — use larger block_q/block_k to re-engage the compact grid",
        stacklevel=stacklevel,
    )


def _compact_maps(h: int, hk: int, g: int):
    """Index maps for a compacted grid (bh, t): q-side blocks follow the
    tile table's q entry, kv-side blocks its k entry (GQA head fold).
    ``qm_map`` serves per-token q-side row vectors (segment ids)."""

    def q_map(bh, t, offs, tq, tk, tf):
        return (bh, tq[t], 0)

    def kv_map(bh, t, offs, tq, tk, tf):
        return ((bh // h) * hk + (bh % h) // g, tk[t], 0)

    def kvm_map(bh, t, offs, tq, tk, tf):
        return (bh // h, tk[t])

    def qm_map(bh, t, offs, tq, tk, tf):
        return (bh // h, tq[t])

    def k_out_map(bh, t, offs, tq, tk, tf):
        return (bh, tk[t], 0)

    return q_map, kv_map, kvm_map, qm_map, k_out_map


def _static_band(causal, windowed, causal_offset, window_lo):
    """True when the band is known at trace time (compact grid usable)."""
    if not causal:
        return False
    if not isinstance(causal_offset, (int, np.integer)):
        return False
    return not windowed or isinstance(window_lo, (int, np.integer))


def _normalize_hint(causal, windowed, causal_offset, window_lo, band_hint):
    """Static band bounds ``(hi_work, hi_int, lo_work, lo_int)`` for compact
    table construction, or None when no static description exists.

    Exactly-static offsets collapse to a tight hint.  A caller-supplied
    ``band_hint`` describes *traced* offsets whose value set is known at
    trace time (ring hops: <= ring_size candidates): ``hi_work``/``lo_work``
    bound the band from OUTSIDE (superset — tiles beyond them are skipped
    for every candidate) and ``hi_int``/``lo_int`` from INSIDE
    (conservative — a tile is interior only if in-band for every
    candidate).  Edge tiles still mask with the runtime scalars, so any
    superset is correct; a tight one is fast.  This is the TPU answer to
    the reference kernel's runtime per-block early exit on ring hops
    (ref ``triton_flash_attn.py:188-199``).
    """
    if not causal:
        return None
    if band_hint is not None:
        hi_w, hi_i, lo_w, lo_i = band_hint
        if not windowed:
            lo_w = lo_i = 0
        return (int(hi_w), int(hi_i), int(lo_w), int(lo_i))
    if _static_band(causal, windowed, causal_offset, window_lo):
        hi = int(causal_offset)
        lo = int(window_lo) if windowed else 0
        return (hi, hi, lo, lo)
    return None


def _check_doc_starts(doc_starts, nq: int, nk: int):
    """Validate a declared packing layout: sorted unique int document start
    offsets beginning at 0, shared by queries and keys (``nq == nk``)."""
    if doc_starts is None:
        return None
    if nq != nk:
        raise ValueError(
            f"doc_starts declares one packing layout for q AND kv, which "
            f"needs nq == nk, got ({nq}, {nk})"
        )
    ds = tuple(int(s) for s in doc_starts)
    if not ds or ds[0] != 0 or list(ds) != sorted(set(ds)) or ds[-1] >= nk:
        raise ValueError(
            f"doc_starts must be sorted unique offsets starting at 0 and "
            f"< {nk}, got {doc_starts!r}"
        )
    return ds


def _docs_block_aligned(doc_starts, *block_sizes) -> bool:
    """True when every document boundary lands on every block boundary —
    the precondition for resolving the document mask at trace time."""
    return all(s % b == 0 for s in doc_starts for b in block_sizes)


def _doc_block_span(doc_starts, pos: int, block: int, n_blocks: int,
                    total: int) -> tuple[int, int]:
    """Inclusive block-index range of the document containing token ``pos``
    (block-aligned layouts only: each block then lies in exactly one doc)."""
    d = bisect.bisect_right(doc_starts, pos) - 1
    start = doc_starts[d]
    end = doc_starts[d + 1] if d + 1 < len(doc_starts) else total
    return start // block, min((end - 1) // block, n_blocks - 1)


def _doc_runtime_ids(doc_starts, n: int, batch: int) -> jax.Array:
    """(b, n) int32 segment ids realizing a declared packing layout — the
    in-kernel-mask fallback when the layout isn't block-aligned."""
    starts = jnp.asarray(doc_starts, jnp.int32)
    ids = jnp.searchsorted(starts, jnp.arange(n, dtype=jnp.int32),
                           side="right") - 1
    return jnp.broadcast_to(ids[None, :], (batch, n)).astype(jnp.int32)


def _band_tile_count(n_q_blocks, n_k_blocks, bq, bk, hint, windowed,
                     outer_is_q: bool, doc_starts=None) -> int:
    """Length of the :func:`_band_tables` tables, in closed form per outer
    row (no table construction — the SMEM cap check must not pay for
    building tables it is about to reject).  Pinned against the real
    tables in ``tests/test_pallas_flash.py``.

    ``doc_starts`` (block-aligned declared packing) intersects each outer
    row's active range with its document's block span — the tile-count
    arithmetic of the packed compact grid."""
    hi, _, lo, _ = hint
    outer_n = n_q_blocks if outer_is_q else n_k_blocks
    inner_n = n_k_blocks if outer_is_q else n_q_blocks
    count = 0
    for o in range(outer_n):
        if outer_is_q:
            row0 = o * bq
            # active ki: ki*bk <= row0+bq-1+hi; windowed: ki*bk+bk-1 >= row0+lo
            i_hi = min((row0 + bq - 1 + hi) // bk, inner_n - 1)
            i_lo = max(-((-(row0 + lo - bk + 1)) // bk), 0) if windowed else 0
        else:
            col0 = o * bk
            # active qi: col0 <= qi*bq+bq-1+hi; windowed: col0+bk-1 >= qi*bq+lo
            i_lo = max(-((-(col0 - hi - bq + 1)) // bq), 0)
            i_hi = (min((col0 + bk - 1 - lo) // bq, inner_n - 1)
                    if windowed else inner_n - 1)
        if doc_starts is not None:
            d_lo, d_hi = _doc_block_span(
                doc_starts,
                o * (bq if outer_is_q else bk),
                bk if outer_is_q else bq,
                inner_n,
                n_q_blocks * bq,
            )
            i_lo, i_hi = max(i_lo, d_lo), min(i_hi, d_hi)
        n = i_hi - i_lo + 1
        count += n if n > 0 else 1  # empty rows get a dummy entry
    return count


class BandPlan(NamedTuple):
    """The compact causal grid a flash launch would run, as data.

    The public seam over ``_band_tables`` / ``_band_tile_count`` for the
    tile-coverage prover (``analysis/coverage.py``) and the on-chip tile
    accounting (``tools/tpu_kernel_validate.py``): everything the kernels
    derive from a band description, without launching anything.

    ``tile_q`` / ``tile_k`` / ``flags`` are the scalar-prefetched tables
    (one entry per grid step; ``flags`` is the FIRST|LAST|WORK|EDGE word);
    ``tiles`` is the CLOSED-FORM count from :func:`_band_tile_count` —
    kept separate from ``len(tile_q)`` on purpose, so callers can hold
    the two implementations against each other (``tests/test_fuzz.py``).
    """

    tile_q: np.ndarray
    tile_k: np.ndarray
    flags: np.ndarray
    tiles: int  # closed-form _band_tile_count (== len(tile_q) by contract)
    block_q: int
    block_k: int
    n_q_blocks: int
    n_k_blocks: int
    hint: tuple[int, int, int, int]
    windowed: bool
    outer_is_q: bool
    doc_starts: tuple[int, ...] | None  # layout the TABLES carry (aligned)
    doc_aligned: bool  # False = declared layout fell back to runtime ids
    compact: bool  # tiles within the SMEM cap (the grid the launch uses)

    @property
    def work_tiles(self) -> int:
        return int((self.flags & _TF_WORK != 0).sum())

    @property
    def edge_tiles(self) -> int:
        return int((self.flags & (_TF_WORK | _TF_EDGE)
                    == (_TF_WORK | _TF_EDGE)).sum())


def band_plan(
    shape: tuple[int, int],
    block_sizes: tuple[int | None, int | None] | None = None,
    hint=0,
    windowed: bool | None = None,
    doc_starts: tuple[int, ...] | None = None,
    *,
    outer_is_q: bool = True,
) -> BandPlan:
    """Build the compact-grid tile plan for one banded flash sweep.

    Args:
      shape: ``(nq, nk)`` token extents of the sweep.
      block_sizes: ``(block_q, block_k)``; ``None`` entries take the
        kernel defaults through the same :func:`_block_sizes` fitting the
        launches use.
      hint: the static band — an int ``hi`` (plain causal offset), a
        ``(hi, lo)`` pair (``lo=None`` = no window), or the full
        ``(hi_work, hi_int, lo_work, lo_int)`` 4-tuple a ring hop's
        :func:`~ring_attention_tpu.parallel.ring._static_hop_band`
        produces (see :func:`_normalize_hint`).
      windowed: whether the band has a lower bound.  Inferred for
        int/pair hints; REQUIRED for a 4-tuple (its ``lo`` slots are
        meaningful only when windowed).
      doc_starts: declared packing layout (:func:`_check_doc_starts`).
        When it lands on the chosen block boundaries the tables drop
        cross-document tiles (``doc_aligned=True``); otherwise the plan
        mirrors the launch-time fallback — band-only tables,
        ``doc_aligned=False``, the document mask left to runtime ids.
      outer_is_q: q-major iteration (forward) vs k-major (backward).
    """
    nq, nk = int(shape[0]), int(shape[1])
    bq, bk = _block_sizes(nq, nk, *(block_sizes or (None, None)))
    if isinstance(hint, (int, np.integer)):
        hint = (int(hint), int(hint), 0, 0)
        if windowed is None:
            windowed = False
        elif windowed:
            raise ValueError("band_plan: a windowed band needs a (hi, lo) "
                             "pair or a 4-tuple hint, not a bare hi")
    elif len(hint) == 2:
        hi, lo = hint
        windowed = lo is not None if windowed is None else windowed
        if windowed and lo is None:
            raise ValueError("band_plan: windowed=True needs a lower offset")
        hint = (int(hi), int(hi), int(lo or 0), int(lo or 0))
    elif len(hint) == 4:
        if windowed is None:
            raise ValueError(
                "band_plan: a 4-tuple hint needs an explicit windowed= — "
                "its lo slots are meaningful only under a window"
            )
        hint = tuple(int(x) for x in hint)
    else:
        raise ValueError(f"band_plan: hint {hint!r} must be an int, a "
                         f"(hi, lo) pair, or a 4-tuple")
    doc_starts = _check_doc_starts(doc_starts, nq, nk)
    doc_aligned = (doc_starts is not None
                   and _docs_block_aligned(doc_starts, bq, bk))
    doc_tables = doc_starts if doc_aligned else None
    nqb, nkb = nq // bq, nk // bk
    tiles = _band_tile_count(nqb, nkb, bq, bk, hint, windowed, outer_is_q,
                             doc_starts=doc_tables)
    tq, tk, tf = _band_tables(nqb, nkb, bq, bk, hint, windowed, outer_is_q,
                              doc_starts=doc_tables)
    return BandPlan(
        tile_q=tq, tile_k=tk, flags=tf, tiles=tiles, block_q=bq, block_k=bk,
        n_q_blocks=nqb, n_k_blocks=nkb, hint=hint, windowed=bool(windowed),
        outer_is_q=outer_is_q, doc_starts=doc_tables, doc_aligned=doc_aligned,
        compact=tiles <= _MAX_COMPACT_TILES,
    )


def _band_tables(n_q_blocks, n_k_blocks, bq, bk, hint, windowed,
                 outer_is_q: bool, doc_starts=None):
    """(t_q, t_k, flags) int32 tables enumerating active band tiles.

    Iteration order is outer-major so the inner dimension carries the
    accumulator: q-major for the forward (carry = online softmax), k-major
    for the backward (carry = dk/dv).  Rows with no active tile get one
    dummy entry (flags = FIRST|LAST, no WORK) so their zero-initialized
    output block is still written, matching the rectangular grid's
    behavior for fully-masked rows.

    ``hint`` is ``(hi_work, hi_int, lo_work, lo_int)`` — see
    :func:`_normalize_hint`.  ``WORK`` uses the outer (superset) bounds;
    ``EDGE`` marks tiles not provably interior under the inner bounds, and
    only those construct the iota/compare/select mask (with the *runtime*
    band scalars) — under a long-sequence causal grid ~99% of the active
    tiles are interior.  Superset-only tiles are fully masked at run time;
    their contribution is wiped by the online-softmax rescale exactly like
    any fully-masked edge tile.

    ``doc_starts`` (a block-boundary-aligned declared packing layout, see
    :func:`_check_doc_starts`) additionally drops every cross-document
    tile: each block then lies in exactly one document, so a tile is
    active only when its q and k blocks share one — the packed-sequence
    analogue of the causal skip, resolved at trace time into a smaller
    grid rather than masked at run time.
    """
    hi_w, hi_i, lo_w, lo_i = hint
    tq, tk, tf = [], [], []
    outer_n = n_q_blocks if outer_is_q else n_k_blocks
    inner_n = n_k_blocks if outer_is_q else n_q_blocks

    def doc_of(pos):
        return bisect.bisect_right(doc_starts, pos) - 1

    for o in range(outer_n):
        start = len(tf)
        for i in range(inner_n):
            qi, ki = (o, i) if outer_is_q else (i, o)
            row0, col0 = qi * bq, ki * bk
            active = col0 <= row0 + bq - 1 + hi_w
            if windowed:
                active = active and col0 + bk - 1 >= row0 + lo_w
            if active and doc_starts is not None:
                active = doc_of(row0) == doc_of(col0)
            if active:
                interior = col0 + bk - 1 <= row0 + hi_i and (
                    not windowed or col0 >= row0 + bq - 1 + lo_i
                )
                tq.append(qi)
                tk.append(ki)
                tf.append(_TF_WORK | (0 if interior else _TF_EDGE))
        if len(tf) == start:  # empty row: dummy entry, write zeros
            tq.append(o if outer_is_q else 0)
            tk.append(0 if outer_is_q else o)
            tf.append(0)
        tf[start] |= _TF_FIRST
        tf[-1] |= _TF_LAST
    return (np.asarray(tq, np.int32), np.asarray(tk, np.int32),  # ra: allow(RA009 trace-time static tile tables — python ints, never traced)
            np.asarray(tf, np.int32))  # ra: allow(RA009 trace-time static tile tables — python ints, never traced)


# ---------------------------------------------------------------------------
# Forward kernel
# ---------------------------------------------------------------------------


def _fwd_write(fused, outs, acc, m, l, exp2=False):
    """Final write: raw partials for ring merging, or the fused normalized
    output + lse when no merge follows (the reference's
    ``RETURN_NORMALIZED_OUTPUT``, ref ``triton_flash_attn.py:273-275``) —
    at seq 262144 the raw path round-trips a 512 MB f32 accumulator
    through HBM that the fused path never materializes.

    Under log2-space scoring the running max is in log2 units; it converts
    back to natural units here — a (bq, 1) op per block — so the emitted
    partials/lse contract is basis-independent (ring merging and XLA-path
    interop see identical values either way)."""
    if fused:
        out_ref, lse_ref = outs
        l_safe = jnp.maximum(l[:], EPSILON)
        out_ref[0] = (acc[:] / l_safe).astype(out_ref.dtype)
        m_nat = m[:] * LN2 if exp2 else m[:]
        lse_ref[0] = m_nat + jnp.log(l_safe)
    else:
        acc_ref, m_ref, l_ref = outs
        acc_ref[0] = acc[:]
        m_ref[0] = m[:] * LN2 if exp2 else m[:]
        l_ref[0] = l[:]


def _fwd_kernel(*refs, compact: bool, masked: bool, segmented: bool,
                fused: bool, resume: bool, nk_blocks: int, **tile_kw):
    """Unified forward kernel.

    Ref layout (pallas passes scalar-prefetch, inputs, outputs, scratch
    positionally; the static flags say which are present):
      scalars: offs (+ tq/tk/tf tile tables when ``compact``)
      inputs:  q, k, v (+ q/k/v dequant scales when the tile kwargs
               carry ``quantized`` — the int8 compute path: q/k/v are
               int8 values; the q scale is a per-row (bq, 1) f32 column,
               the k scale a (1, bk) row, the v scale a (1, 1) per-block
               scalar — see _token_vectors)
               (+ kv mask when ``masked``)
               (+ q/kv segment ids when ``segmented`` — packed sequences
                masked in-kernel; a block-aligned declared layout resolves
                them into the compact tables instead and ships no refs)
               (+ carry acc/m/l when ``resume`` — the running online-softmax
                state of previous ring hops, continued in-kernel exactly
                like the reference's ``LOAD_ACCUMULATED`` resume, ref
                ``triton_flash_attn.py:124-165``)
      outputs: (out, lse) when ``fused`` else (acc, m, l)
      scratch: acc (bq, d) f32, m (bq, 1) f32, l (bq, 1) f32
    """
    bq, bk = tile_kw["bq"], tile_kw["bk"]
    # consumed by _fwd_tile too
    tile_kw = dict(tile_kw, masked=masked, segmented=segmented)
    if compact:
        offs_ref, tq_ref, tk_ref, tf_ref = refs[:4]
        idx = 4
    else:
        offs_ref = refs[0]
        idx = 1
    q_ref, k_ref, v_ref = refs[idx:idx + 3]
    idx += 3
    scale_refs = None
    if tile_kw.get("quantized"):
        scale_refs = refs[idx:idx + 3]
        idx += 3
    kvm_ref = refs[idx] if masked else None
    idx += 1 if masked else 0
    qseg_ref = kseg_ref = None
    if segmented:
        qseg_ref, kseg_ref = refs[idx:idx + 2]
        idx += 2
    carry_refs = None
    if resume:
        carry_refs = refs[idx:idx + 3]
        idx += 3
    outs = refs[idx:idx + (2 if fused else 3)]
    acc, m, l = refs[idx + (2 if fused else 3):]

    if compact:
        t = pl.program_id(1)
        tf = tf_ref[t]
        first = (tf & _TF_FIRST) != 0
        last = (tf & _TF_LAST) != 0
        row0, col0 = tq_ref[t] * bq, tk_ref[t] * bk
    else:
        ki = pl.program_id(2)
        first = ki == 0
        last = ki == nk_blocks - 1
        row0, col0 = pl.program_id(1) * bq, ki * bk

    @pl.when(first)
    def _init():
        if resume:
            acc[:] = carry_refs[0][0]
            # carries cross hops in natural units (basis-independent
            # contract, see _fwd_write); log2-space kernels convert on load
            m[:] = (carry_refs[1][0] * LOG2E if tile_kw.get("exp2")
                    else carry_refs[1][0])
            l[:] = carry_refs[2][0]
        else:
            acc[:] = jnp.zeros_like(acc)
            m[:] = jnp.full_like(m, MASK_VALUE)
            l[:] = jnp.zeros_like(l)

    tile = _tile_closure(_fwd_tile, tile_kw, offs_ref, q_ref, k_ref, v_ref,
                         kvm_ref, qseg_ref, kseg_ref, scale_refs, acc, m, l,
                         row0, col0)
    if compact:
        _dispatch_tile_compact(tf, tile)
    else:
        _dispatch_tile(offs_ref, row0, col0, bq, bk, tile_kw["causal"],
                       tile_kw["windowed"], tile)

    @pl.when(last)
    def _write():
        _fwd_write(fused, outs, acc, m, l, exp2=tile_kw.get("exp2", False))


def _softclamp(s, clamp, exp2):
    """Clamp a score tile in natural units: ``c * tanh(s_nat / c)``, with
    ``s`` (and the result) in log2 units when ``exp2`` — the one clamp
    basis transform shared by the fwd tile and the bwd recompute."""
    if exp2:
        return jnp.tanh(s * (LN2 / clamp)) * (clamp * LOG2E)
    return jnp.tanh(s / clamp) * clamp


def _softclamp_grad_factor(s_clamped, clamp, exp2):
    """tanh' = 1 - (clamped_natural / c)^2 from the post-clamp scores
    (log2-basis under ``exp2``); multiplies ds in the backward tile."""
    s_nat = s_clamped * LN2 if exp2 else s_clamped
    return 1.0 - (s_nat / clamp) ** 2


def _int8_dot(a, b, dimension_numbers):
    """int8 x int8 matmul as an f32 tile.  Mosaic accumulates integer
    operands in int32 only ("float acc with int lhs" is a compile error on
    the v5e); the int32 sums are exact and stay below 2^24 for every tile
    this module launches (127^2 x 1024-deep), so the widening to f32 is
    exact too."""
    return lax.dot_general(
        a, b, dimension_numbers, preferred_element_type=jnp.int32
    ).astype(jnp.float32)


def _online_update(s, v, acc, m, l, exp2=False, v_scale=None):
    """One online-softmax accumulator step over a masked score tile ``s``
    against value rows ``v`` — THE shared tile math of every forward-shaped
    kernel in this module (``p`` is cast to ``v.dtype`` so bf16 callers run
    the pv matmul in bf16 and f32 callers in f32).  With ``exp2`` the tile
    is in log2 space (s and m both scaled by log2e), so ``p``/``alpha``/
    ``l``/``acc`` come out value-identical with a cheaper exponential.

    ``v_scale`` (a per-tile f32 scalar) selects the int8 PV path: ``v``
    is then int8 values whose block dequant scale is ``v_scale``, ``p``
    quantizes to int8 per row (``quant.quantize_p`` — per-row absmax, so
    late tiles whose ``p`` is small against the RUNNING max keep their
    resolution), the PV matmul runs on int8 operands into an f32
    accumulator, and the dequant factors fold into one ``(bq, 1)``
    multiply on the product (the per-row p scale rides the free index;
    ``v_scale`` is scalar).  ``l`` sums the SAME quantized ``p`` so
    ``out = acc / l`` stays exactly normalized over the weights actually
    applied."""
    ex = jnp.exp2 if exp2 else jnp.exp
    m_prev = m[:]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    p = ex(s - m_new)
    alpha = ex(m_prev - m_new)
    if v_scale is None:
        l[:] = l[:] * alpha + jnp.sum(p, axis=1, keepdims=True)
        pv = lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        acc[:] = acc[:] * alpha + pv
    else:
        p8, p_scale = _quant.quantize_p(p)
        # scale BEFORE the row-sum on purpose: reassociating to
        # sum(p8) * p_scale is value-identical but would accumulate
        # undequantized int8 content — the exact pattern the precision
        # auditor forbids (dequant-before-reduce, no exceptions)
        l[:] = l[:] * alpha + jnp.sum(
            p8.astype(jnp.float32) * p_scale, axis=1, keepdims=True,
        )
        pv8 = _int8_dot(p8, v, (((1,), (0,)), ((), ())))
        acc[:] = acc[:] * alpha + pv8 * (p_scale * v_scale)
    m[:] = m_new


def _fwd_tile(offs_ref, q_ref, k_ref, v_ref, kvm_ref, qseg_ref, kseg_ref,
              scale_refs, acc, m, l, row0, col0, *, scale, softclamp_value,
              causal, windowed, masked, segmented, bq, bk, exp2=False,
              quantized=False):
    q = q_ref[0]
    k = k_ref[0]
    nt = (((1,), (1,)), ((), ()))
    if quantized:
        # int8 operands: the raw int8 QK^T, widened exactly.  The
        # q/k scales ride the matmul's FREE indices (per-row absmax —
        # row/col vectors on the score tile), so dequantization is exact
        # and the softmax scale (and the log2-space basis factor) folds
        # into the same ONE fused rescale multiply (docs/precision.md)
        qs_ref, ks_ref, _ = scale_refs
        s = _int8_dot(q, k, nt) * (
            (qs_ref[0] * (scale * LOG2E if exp2 else scale)) * ks_ref[0])
    else:
        s = lax.dot_general(q, k, nt, preferred_element_type=jnp.float32)
        if scale != 1.0:  # static: folded into q for power-of-two scales
            s = s * scale
    if softclamp_value is not None:
        s = _softclamp(s, softclamp_value, exp2)

    keep = _tile_keep(
        offs_ref, row0, col0, (bq, bk), 0, causal, windowed,
        kvm_ref if masked else None,
        qseg_ref if segmented else None,
        kseg_ref if segmented else None,
    )
    if keep is not None:
        s = jnp.where(keep, s, MASK_VALUE)

    _online_update(
        s, v_ref[0], acc, m, l, exp2=exp2,
        v_scale=scale_refs[2][0, 0] if quantized else None,  # (1, 1)
    )


class FlashPartials(NamedTuple):
    """Raw online-softmax partials: out = acc / l, lse = m + log l."""

    acc: jax.Array  # (b, h, nq, d) f32
    m: jax.Array  # (b, h, nq) f32
    l: jax.Array  # (b, h, nq) f32


def _flash_fwd_call(
    q, k, v, kv_mask, *,
    scale, causal_offset, window_lo, softclamp_value,
    block_q, block_k, band_hint, interpret, fused, carry=None,
    exp2=None, q_segment_ids=None, kv_segment_ids=None, doc_starts=None,
    compute_dtype=None, kv_quantized=None, name=None,
):
    """Shared forward launcher: one flash sweep over a KV span.

    ``fused=False`` returns mergeable :class:`FlashPartials` (ring hops);
    ``fused=True`` returns ``(out in q.dtype, lse f32)`` with normalization
    folded into the kernel's final write (no-merge callers).  ``carry``
    resumes a previous sweep's ``(acc, m, l)`` state in-kernel (the
    reference's ``LOAD_ACCUMULATED``, ref ``triton_flash_attn.py:124-165``)
    — one HBM read of the carry instead of an XLA-side
    :func:`merge_partials` that reads both operands and writes a third.

    Packed sequences: ``q_segment_ids``/``kv_segment_ids`` mask
    cross-document pairs in-kernel; ``doc_starts`` *declares* the packing
    layout statically, and when it lands on block boundaries under a
    compact causal grid the cross-document tiles are dropped from the grid
    at trace time instead (no refs, no per-tile mask) — misaligned or
    demoted layouts fall back to the in-kernel mask.

    ``compute_dtype="int8"`` runs QK^T and PV on int8 operands: q is
    quantized per q-block and k/v per KV-block (symmetric absmax,
    ``ops/quant.py``), the dequant-scale multiply folds into the per-tile
    softmax rescale, ``p`` quantizes at the fixed full scale for the PV
    matmul, and the ``(acc, m, l)`` state stays f32 end to end
    (``docs/precision.md``).  ``kv_quantized`` (a
    :class:`~ring_attention_tpu.ops.quant.QuantizedBlockKV` whose
    ``block`` equals this launch's fitted ``block_k``) feeds
    pre-quantized K/V directly — the ring's dequant-free hop composition;
    ``k``/``v`` may then be None."""
    b, h, nq, d = q.shape
    if compute_dtype not in (None, "int8"):
        raise ValueError(
            f"compute_dtype={compute_dtype!r}: supported values are None "
            '(model-dtype matmuls) and "int8" (quantized QK^T/PV)'
        )
    quantized = compute_dtype == "int8"
    if kv_quantized is not None and not quantized:
        raise ValueError('kv_quantized requires compute_dtype="int8"')
    kshape = (kv_quantized.k_q if kv_quantized is not None else k).shape
    _, hk, nk, _ = kshape
    g = h // hk
    bq, bk = _block_sizes(nq, nk, block_q, block_k)
    interpret = _interpret_default() if interpret is None else interpret
    doc_starts = _check_doc_starts(doc_starts, nq, nk)

    # power-of-two scale (every d = 4^k head dim, incl. the headline d=64
    # -> 1/8) folds into q exactly (exponent shift, bit-identical scores)
    # BEFORE the launch, deleting the per-tile (bq, bk) VPU multiply from
    # the score path — a builder's roofline estimate (2026-07-29, one v5e
    # chip, d=64) put fwd within ~30% of VPU-bound, so score-path VPU ops
    # are the scarce resource.  Non-power-of-two scales keep the in-kernel
    # multiply: folding those would round q a second time.
    # exp2 (explicit kw, or RING_ATTN_EXP2=1 when None — trace-time
    # capture, see _exp2_default) moves the whole tile into log2 space
    # (fold scale*log2e, exponentials become exp2).
    exp2 = _exp2_default() if exp2 is None else bool(exp2)
    if quantized:
        # int8 q cannot absorb a float fold; the softmax scale (and the
        # log2-space basis factor) ride the per-tile dequant multiply
        # instead — see _fwd_tile's quantized branch
        pass
    elif exp2:
        q = q * jnp.asarray(scale * LOG2E, q.dtype)
        scale = 1.0
    elif scale != 1.0 and math.frexp(float(scale))[0] == 0.5:
        q = q * jnp.asarray(scale, q.dtype)
        scale = 1.0

    causal = causal_offset is not None
    windowed = window_lo is not None and causal
    masked = kv_mask is not None
    resume = carry is not None

    offs = jnp.asarray(
        [
            causal_offset if causal else 0,
            window_lo if windowed else 0,
        ],
        jnp.int32,
    )

    hint = _normalize_hint(causal, windowed, causal_offset, window_lo,
                           band_hint)
    compact = hint is not None
    # trace-time doc skip needs a compact grid AND a block-aligned layout
    doc_tables = (
        doc_starts
        if compact and doc_starts is not None
        and _docs_block_aligned(doc_starts, bq, bk)
        else None
    )

    if compact:
        tiles = _band_tile_count(
            nq // bq, nk // bk, bq, bk, hint, windowed, outer_is_q=True,
            doc_starts=doc_tables,
        )
        compact = tiles <= _MAX_COMPACT_TILES
        if not compact:
            _warn_demoted("fwd", tiles)
            doc_tables = None

    if doc_tables is not None:
        # the tables carry the whole document mask: ship no segment refs
        q_segment_ids = kv_segment_ids = None
    elif doc_starts is not None and q_segment_ids is None:
        # misaligned/demoted declared layout: realize it as runtime ids
        q_segment_ids = kv_segment_ids = _doc_runtime_ids(doc_starts, nq, b)
    segmented = q_segment_ids is not None

    common = dict(
        scale=scale,
        softclamp_value=softclamp_value,
        causal=causal,
        windowed=windowed,
        masked=masked,
        segmented=segmented,
        bq=bq,
        bk=bk,
        exp2=exp2,
        quantized=quantized,
    )

    if compact:
        tq_a, tk_a, tf_a = (
            jnp.asarray(t)
            for t in _band_tables(nq // bq, nk // bk, bq, bk, hint,
                                  windowed, outer_is_q=True,
                                  doc_starts=doc_tables)
        )
        (q, k, v, kv_mask, q_segment_ids, kv_segment_ids, offs, tq_a, tk_a,
         tf_a) = _unify_vma(
            q, k, v, kv_mask, q_segment_ids, kv_segment_ids, offs, tq_a,
            tk_a, tf_a
        )
        scalars = (offs, tq_a, tk_a, tf_a)
        grid = (b * h, tq_a.shape[0])
        q_map, kv_map, kvm_map, qm_map, _ = _compact_maps(h, hk, g)
        semantics = ("parallel", "arbitrary")

        def qsc_map(bh, t, offs, tq, tk, tf):
            return (bh, tq[t])

        def ksc_map(bh, t, offs, tq, tk, tf):
            return ((bh // h) * hk + (bh % h) // g, tk[t])
    else:
        q, k, v, kv_mask, q_segment_ids, kv_segment_ids, offs = _unify_vma(
            q, k, v, kv_mask, q_segment_ids, kv_segment_ids, offs
        )
        scalars = (offs,)
        grid = (b * h, nq // bq, nk // bk)

        def q_map(bh, qi, ki, *_):
            return (bh, qi, 0)

        def kv_map(bh, qi, ki, *_):
            return ((bh // h) * hk + (bh % h) // g, ki, 0)

        def kvm_map(bh, qi, ki, *_):
            return (bh // h, ki)

        def qm_map(bh, qi, ki, *_):
            return (bh // h, qi)

        def qsc_map(bh, qi, ki, *_):
            return (bh, qi)

        def ksc_map(bh, qi, ki, *_):
            return ((bh // h) * hk + (bh % h) // g, ki)

        # batch*head and q-block grid dims are independent (megacore can
        # split them); the kv dim carries the online-softmax state
        semantics = ("parallel", "parallel", "arbitrary")

    kernel = functools.partial(
        _fwd_kernel,
        compact=compact,
        fused=fused,
        resume=resume,
        nk_blocks=nk // bk,
        **common,
    )

    if quantized:
        # q quantizes per row HERE (it is exact bf16 at every call site —
        # ring hops re-quantize the rotating pack's q, cheap VPU work);
        # k/v either arrive pre-quantized (the ring's dequant-free hop
        # feed) or quantize now — k per row (a FREE index of QK^T, so the
        # scale pulls out exactly), v per KV-block (PV contracts over
        # tokens; only a per-block scalar pulls out of that matmul).
        qr, qs = _quant.quantize_rows(q.reshape(b * h, nq, d))
        if kv_quantized is not None:
            if kv_quantized.block != bk:
                raise ValueError(
                    f"kv_quantized was packed at v-block "
                    f"{kv_quantized.block} but this launch fitted "
                    f"block_k={bk}; quantize at the kernel's fitted block "
                    f"(see parallel/ring.py)"
                )
            kr = kv_quantized.k_q.reshape(b * hk, nk, d)
            vr = kv_quantized.v_q.reshape(b * hk, nk, d)
            ks = kv_quantized.k_scale.reshape(b * hk, nk)
            vs = kv_quantized.v_scale.reshape(b * hk, nk // bk)
        else:
            kr, ks = _quant.quantize_rows(k.reshape(b * hk, nk, d))
            vr, vs = _quant.quantize_blocks(v.reshape(b * hk, nk, d), bk)
        qs, ks, vs, kr, vr = (_unify_vma(x, q)[0] for x in (qs, ks, vs, kr, vr))
        qs = qs.astype(jnp.float32)
        ks = ks.astype(jnp.float32)
        vs = vs.astype(jnp.float32)
    else:
        qr = q.reshape(b * h, nq, d)
        kr = k.reshape(b * hk, nk, d)
        vr = v.reshape(b * hk, nk, d)

    in_specs = [
        pl.BlockSpec((1, bq, d), q_map, memory_space=pltpu.VMEM),
        pl.BlockSpec((1, bk, d), kv_map, memory_space=pltpu.VMEM),
        pl.BlockSpec((1, bk, d), kv_map, memory_space=pltpu.VMEM),
    ]
    inputs = [qr, kr, vr]
    if quantized:
        in_specs += [
            _token_spec(bq, True, qsc_map),
            _token_spec(bk, False, ksc_map),
            # one scalar per KV block, indexed like a k block
            pl.BlockSpec((1, 1, 1, 1), lambda *a: (*ksc_map(*a), 0, 0),
                         memory_space=pltpu.VMEM),
        ]
        inputs += [_token_vectors(qs, True), _token_vectors(ks, False),
                   vs[:, :, None, None]]
    if masked:
        in_specs.append(_token_spec(bk, False, kvm_map))
        inputs.append(_token_vectors(kv_mask.astype(jnp.int32), False))
    if segmented:
        in_specs += [
            _token_spec(bq, True, qm_map),
            _token_spec(bk, False, kvm_map),
        ]
        inputs += [
            _token_vectors(q_segment_ids.astype(jnp.int32), True),
            _token_vectors(kv_segment_ids.astype(jnp.int32), False),
        ]
    if resume:
        c_acc, c_m, c_l = (_unify_vma(x, q)[0] for x in carry)
        inputs += [
            c_acc.reshape(b * h, nq, d),
            c_m.reshape(b * h, nq, 1),
            c_l.reshape(b * h, nq, 1),
        ]
        in_specs += [
            pl.BlockSpec((1, bq, d), q_map, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, bq, 1), q_map, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, bq, 1), q_map, memory_space=pltpu.VMEM),
        ]

    if fused:
        out_specs = [
            pl.BlockSpec((1, bq, d), q_map, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, bq, 1), q_map, memory_space=pltpu.VMEM),
        ]
        out_shape = [
            _sds((b * h, nq, d), q.dtype, q),
            _sds((b * h, nq, 1), jnp.float32, q),
        ]
    else:
        out_specs = [
            pl.BlockSpec((1, bq, d), q_map, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, bq, 1), q_map, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, bq, 1), q_map, memory_space=pltpu.VMEM),
        ]
        out_shape = [
            _sds((b * h, nq, d), jnp.float32, q),
            _sds((b * h, nq, 1), jnp.float32, q),
            _sds((b * h, nq, 1), jnp.float32, q),
        ]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(scalars),
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=[
            pltpu.VMEM((bq, d), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
        ],
    )

    # stable kernel names: XProf shows the Mosaic custom-call under this
    # label, so traces attribute time to "which flash sweep" (resume = a
    # ring hop continuing a carry) — docs/observability.md
    if name is None:
        name = "flash_fwd_tile" if fused else "flash_partials_tile"
        if resume:
            name += "_resume"
        if quantized:
            name += "_q8"  # int8 sweeps attribute separately in XProf
    _log_launch(name, nq, nk, h, hk, d, bq, bk, grid, compact)
    results = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=out_shape,
        compiler_params=compat.tpu_compiler_params(
            dimension_semantics=semantics
        ),
        interpret=interpret,
        name=name,
    )(*scalars, *inputs)

    if fused:
        out, lse = results
        return out.reshape(b, h, nq, d), lse.reshape(b, h, nq)
    acc, m, l = results
    return FlashPartials(
        acc.reshape(b, h, nq, d),
        m.reshape(b, h, nq),
        l.reshape(b, h, nq),
    )


def pallas_flash_partials(  # ra: allow(RA007 per-hop kernel launch; ring/zigzag entry points validate first)
    q: jax.Array,  # (b, h, nq, d)
    k: jax.Array,  # (b, hk, nk, d)
    v: jax.Array,  # (b, hk, nk, d)
    kv_mask: jax.Array | None = None,  # (b, nk) bool
    *,
    scale: float,
    causal_offset: jax.Array | int | None = None,
    window_lo: jax.Array | int | None = None,
    softclamp_value: float | None = None,
    block_q: int | None = None,
    block_k: int | None = None,
    band_hint: tuple[int, int, int, int] | None = None,
    carry: FlashPartials | None = None,
    interpret: bool | None = None,
    exp2: bool | None = None,
    segment_ids=None,
    doc_starts: tuple[int, ...] | None = None,
    compute_dtype: str | None = None,
    kv_quantized: QuantizedBlockKV | None = None,
) -> FlashPartials:
    """One flash sweep over a KV span, returning mergeable partials.

    ``window_lo``: absolute band lower offset (see ``ops/flash.py``);
    may be a traced per-device scalar under SPMD.  ``band_hint`` supplies
    static band bounds for traced offsets so the compacted causal grid
    still engages (see :func:`_normalize_hint`).  ``carry`` continues a
    previous sweep's online softmax in-kernel (ring hops) — equivalent to
    ``merge_partials(carry, <this sweep>)`` without the XLA-side merge
    traffic.  ``exp2`` selects log2-space scoring explicitly (None =
    the ``RING_ATTN_EXP2`` env var, captured at trace time — see
    :func:`_exp2_default`); the emitted partials are natural-basis either
    way, so sweeps of different bases merge exactly.

    ``segment_ids`` (a ``(b, n)`` array or ``(q_ids, kv_ids)`` pair) masks
    cross-document pairs for packed sequences; ``doc_starts`` declares the
    packing statically so a block-aligned layout drops cross-document
    tiles from the compact grid at trace time (``docs/packing.md``).

    ``compute_dtype="int8"`` runs QK^T/PV on int8 operands with per-block
    absmax scales and f32 ``(acc, m, l)`` untouched; ``kv_quantized``
    feeds pre-quantized K/V directly (the ring's dequant-free hop
    composition — ``k``/``v`` may then be None).  See
    ``docs/precision.md``.
    """
    q_seg, kv_seg = normalize_segment_ids(
        segment_ids, q,
        kv_quantized.k_q if kv_quantized is not None else k,
        "pallas_flash_partials",
    )
    return _flash_fwd_call(
        q, k, v, kv_mask,
        scale=scale, causal_offset=causal_offset, window_lo=window_lo,
        softclamp_value=softclamp_value, block_q=block_q, block_k=block_k,
        band_hint=band_hint, interpret=interpret, fused=False, carry=carry,
        exp2=exp2, q_segment_ids=q_seg, kv_segment_ids=kv_seg,
        doc_starts=doc_starts, compute_dtype=compute_dtype,
        kv_quantized=kv_quantized,
    )


def pallas_flash_fused(  # ra: allow(RA007 final-hop kernel launch; ring entry points validate first)
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    kv_mask: jax.Array | None = None,
    *,
    scale: float,
    causal_offset: jax.Array | int | None = None,
    window_lo: jax.Array | int | None = None,
    softclamp_value: float | None = None,
    block_q: int | None = None,
    block_k: int | None = None,
    band_hint: tuple[int, int, int, int] | None = None,
    carry: FlashPartials | None = None,
    interpret: bool | None = None,
    exp2: bool | None = None,
    segment_ids=None,
    doc_starts: tuple[int, ...] | None = None,
    compute_dtype: str | None = None,
    kv_quantized: QuantizedBlockKV | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Single-span forward with normalization fused into the final kernel
    write: returns ``(out in q.dtype, lse f32)`` directly.

    For callers with no downstream partial merge (the local/non-ring path,
    or a ring's LAST hop via ``carry``) this replaces ``finalize_partials``
    and skips materializing the f32 ``(acc, m, l)`` triple in HBM entirely
    (ref ``triton_flash_attn.py:273-275`` fuses the same way, and
    ``ring_flash_attention_cuda.py:134,182-186`` fuses it into the last
    hop).  ``band_hint`` (superset bounds for traced offsets) requires a
    ``carry``: a hint's superset-only tiles leave band-empty rows holding
    masked garbage that only a rescale against real content can wipe —
    with a carry the wipe happens in-kernel (by the ring's last hop every
    row's carry holds its own-diagonal content), without one there is no
    later merge to do it.
    """
    if band_hint is not None and carry is None:
        # not an assert: violating this silently yields uniform-weight
        # garbage for band-empty rows, and asserts vanish under python -O
        raise ValueError(
            "pallas_flash_fused: band_hint needs a carry (see docstring)"
        )
    q_seg, kv_seg = normalize_segment_ids(
        segment_ids, q,
        kv_quantized.k_q if kv_quantized is not None else k,
        "pallas_flash_fused",
    )
    return _flash_fwd_call(
        q, k, v, kv_mask,
        scale=scale, causal_offset=causal_offset, window_lo=window_lo,
        softclamp_value=softclamp_value, block_q=block_q, block_k=block_k,
        band_hint=band_hint, interpret=interpret, fused=True, carry=carry,
        exp2=exp2, q_segment_ids=q_seg, kv_segment_ids=kv_seg,
        doc_starts=doc_starts, compute_dtype=compute_dtype,
        kv_quantized=kv_quantized,
    )


# Decode streams the whole KV cache once per kv head; large blocks amortize
# grid steps and keep the DMA pipeline deep (2 x bk x d bf16 double-buffered
# = 4 MB of VMEM at 8192x64 — well under budget).
DEFAULT_BLOCK_DECODE = 8192


def _decode_fold_rows(q, hk):
    """Fold the GQA head group onto query rows — ``(b, h, nq, d) ->
    (b, hk, g*nq(+pad), d)`` — padding rows up to one sublane tile: Mosaic
    handles tiny row blocks unevenly across generations, and the pad rows
    cost nothing against a bandwidth-bound sweep (zero queries -> uniform
    weights -> finite outputs, sliced away by the caller).  One sublane
    tile is 32 / itemsize rows (8 for f32, 16 for bf16/f16, 32 for
    one-byte dtypes) — keyed on itemsize, not a bf16 check."""
    b, h, nq, d = q.shape
    g = h // hk
    rows = g * nq
    min_rows = max(8, 32 // jnp.dtype(q.dtype).itemsize)
    pad = (-rows) % min_rows
    qf = q.reshape(b, hk, rows, d)
    if pad:
        qf = jnp.pad(qf, [(0, 0), (0, 0), (0, pad), (0, 0)])
    return qf, rows, pad


def pallas_flash_decode(
    q: jax.Array,  # (b, h, nq, d) — nq is tiny (typically 1)
    k: jax.Array,  # (b, hk, nk, d)
    v: jax.Array,  # (b, hk, nk, d)
    kv_mask: jax.Array | None = None,  # (b, nk) bool, True = attend
    *,
    scale: float | None = None,
    softclamp_value: float | None = None,
    block_k: int | None = None,
    fused: bool = True,
    interpret: bool | None = None,
):
    """Decode-time flash attention: the KV cache is read once per *KV head*.

    The training kernels grid over ``b*h`` query heads, so under GQA each
    KV block is fetched ``g = h/hk`` times — irrelevant when compute
    dominates, but decode (``nq`` ~ 1) is pure HBM bandwidth: the KV read
    IS the cost.  Here the head group folds onto the query-row dimension
    (``(b, h, nq, d) -> (b, hk, g*nq, d)``) and the sweep grids over
    ``b*hk``, so every cache byte crosses HBM exactly once — the same
    single-kernel decode the reference reaches for via its Triton path
    (ref ``tree_attn_decoding.py:60-72``), minus its g-fold repeat
    (ref ``tree_attn_decoding.py:47-52`` materializes grouped queries).

    No causal band: decode queries attend the whole (masked) cache, like
    the reference decode (ref ``tree_attn_decoding.py:23-103``); cache
    validity (``[0, pos]``, lookback windows, ragged shards) is the
    ``kv_mask``.

    Returns:
      ``fused=True``: ``(out (b, h, nq, d) in q.dtype, lse (b, h, nq) f32)``
      — normalization in the kernel's final write; the single-device path.
      ``fused=False``: raw ``(acc (b, hk, g, nq, d), m, l (b, hk, g, nq))``
      f32 partials in the ``ops.flash.FlashCarry`` layout, for the
      tree-decode cross-device merge (``parallel/tree_decode.py``).
    """
    check_attention_args("pallas_flash_decode", q, k, v, kv_mask)
    b, h, nq, d = q.shape
    hk = k.shape[1]
    g = h // hk
    if scale is None:
        scale = d**-0.5
    qf, rows, pad = _decode_fold_rows(q, hk)
    res = _flash_fwd_call(
        qf, k, v, kv_mask,
        scale=scale, causal_offset=None, window_lo=None,
        softclamp_value=softclamp_value,
        block_q=rows + pad, block_k=block_k or DEFAULT_BLOCK_DECODE,
        band_hint=None, interpret=interpret, fused=fused,
        name="flash_decode",
    )
    if fused:
        out, lse = res
        return (
            out[:, :, :rows].reshape(b, h, nq, d),
            lse[:, :, :rows].reshape(b, h, nq),
        )
    acc, m, l = res
    return (
        acc[:, :, :rows].reshape(b, hk, g, nq, d),
        m[:, :, :rows].reshape(b, hk, g, nq),
        l[:, :, :rows].reshape(b, hk, g, nq),
    )


class QuantizedKV(NamedTuple):
    """Int8 KV cache with per-token dequantization scales.

    Decode at long context is pure HBM bandwidth — the KV read IS the cost
    (measured 1.05 ms/token = 255 GB/s at a 1M-token bf16 cache on one
    v5e).  Storing the cache as int8 with one f32 scale per (head, token)
    row cuts the bytes per k-or-v row from 128 (bf16 at d=64) to 68
    (64 int8 + 4 scale), a 1.88x decode-bandwidth win, at per-row absmax
    quantization error (~0.4% RMS on gaussian activations).  No reference
    equivalent (its decode reads the fp16 cache directly,
    ref ``tree_attn_decoding.py:54-79``)."""

    k_q: jax.Array  # (b, hk, nk, d) int8
    k_scale: jax.Array  # (b, hk, nk) f32
    v_q: jax.Array  # (b, hk, nk, d) int8
    v_scale: jax.Array  # (b, hk, nk) f32


def quantize_kv_cache(k: jax.Array, v: jax.Array) -> QuantizedKV:
    """Per-token symmetric absmax int8 quantization of a KV cache
    (``ops/quant.py::quantize_rows`` — the one int8 codec seam)."""
    k_q, k_scale = _quant.quantize_rows(k)
    v_q, v_scale = _quant.quantize_rows(v)
    return QuantizedKV(k_q, k_scale, v_q, v_scale)


def dequantize_kv_cache(
    kv: QuantizedKV, dtype=jnp.bfloat16
) -> tuple[jax.Array, jax.Array]:
    """Materialize the KV a quantized cache represents (the non-pallas
    decode fallback and the parity-test oracle)."""
    k = _quant.dequantize_rows(kv.k_q, kv.k_scale, dtype)
    v = _quant.dequantize_rows(kv.v_q, kv.v_scale, dtype)
    return k, v


def _decode_q8_kernel(q_ref, kq_ref, ks_ref, vq_ref, vs_ref, *rest,
                      masked, fused, scale, softclamp_value, nk_blocks):
    kvm_ref = rest[0] if masked else None
    rest = rest[1 if masked else 0:]
    outs = rest[:-3]
    acc, m, l = rest[-3:]
    ki = pl.program_id(1)

    @pl.when(ki == 0)
    def _init():
        acc[:] = jnp.zeros_like(acc)
        m[:] = jnp.full_like(m, MASK_VALUE)
        l[:] = jnp.zeros_like(l)

    # dequantize in f32: int8 -> f32 is exact and the scale multiply rides
    # the VPU while the sweep waits on the (now 1.88x smaller) KV DMA;
    # accumulation and final write are the shared _online_update/_fwd_write.
    # The per-token scales arrive lane-major ((1, bk) rows — the layout
    # that costs one f32 per token in HBM) and turn into columns here.
    k = kq_ref[0].astype(jnp.float32) * ks_ref[0, 0][:, None]
    s = lax.dot_general(
        q_ref[0].astype(jnp.float32), k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    s = s * scale
    if softclamp_value is not None:
        s = jnp.tanh(s / softclamp_value) * softclamp_value
    if masked:
        s = jnp.where(kvm_ref[0] != 0, s, MASK_VALUE)

    v = vq_ref[0].astype(jnp.float32) * vs_ref[0, 0][:, None]
    _online_update(s, v, acc, m, l)

    @pl.when(ki == nk_blocks - 1)
    def _write():
        _fwd_write(fused, outs, acc, m, l)


def pallas_flash_decode_q8(
    q: jax.Array,  # (b, h, nq, d) — nq is tiny (typically 1)
    kv: QuantizedKV,
    kv_mask: jax.Array | None = None,  # (b, nk) bool, True = attend
    *,
    scale: float | None = None,
    softclamp_value: float | None = None,
    block_k: int | None = None,
    fused: bool = True,
    interpret: bool | None = None,
):
    """:func:`pallas_flash_decode` against an int8 :class:`QuantizedKV`
    cache: same GQA head-group fold (cache read once per *kv* head), but
    each KV token row crosses HBM as 64 int8 + one f32 scale instead of a
    bf16 row — the decode-bandwidth headline path for million-token
    caches.  Returns the same ``(out, lse)`` / partials contract as
    :func:`pallas_flash_decode`."""
    b, h, nq, d = q.shape
    _, hk, nk, _ = kv.k_q.shape
    g = h // hk
    if scale is None:
        scale = d**-0.5
    interpret = _interpret_default() if interpret is None else interpret
    masked = kv_mask is not None

    qf, rows, pad = _decode_fold_rows(q, hk)
    bq = rows + pad
    bk = min(block_k or DEFAULT_BLOCK_DECODE, nk)
    while nk % bk:
        bk //= 2

    # unify shard_map varying-axes across operands (a cache-validity mask
    # built from axis_index varies over fewer mesh axes than q; pallas
    # requires uniform vma types) — same contract as _flash_fwd_call
    qf, k_q, k_s, v_q, v_s, kv_mask = _unify_vma(
        qf, kv.k_q, kv.k_scale, kv.v_q, kv.v_scale, kv_mask
    )
    q = qf  # out_shape vma derives from the unified q
    qr = qf.reshape(b * hk, bq, d)
    kqr = k_q.reshape(b * hk, nk, d)
    ksr = _token_vectors(k_s.astype(jnp.float32).reshape(b * hk, nk), False)
    vqr = v_q.reshape(b * hk, nk, d)
    vsr = _token_vectors(v_s.astype(jnp.float32).reshape(b * hk, nk), False)

    def q_map(bh, ki):
        del ki
        return (bh, 0, 0)

    def kv_map(bh, ki):
        return (bh, ki, 0)

    def sc_map(bh, ki):
        return (bh, ki)

    def kvm_map(bh, ki):
        return (bh // hk, ki)

    in_specs = [
        pl.BlockSpec((1, bq, d), q_map, memory_space=pltpu.VMEM),
        pl.BlockSpec((1, bk, d), kv_map, memory_space=pltpu.VMEM),
        _token_spec(bk, False, sc_map),
        pl.BlockSpec((1, bk, d), kv_map, memory_space=pltpu.VMEM),
        _token_spec(bk, False, sc_map),
    ]
    inputs = [qr, kqr, ksr, vqr, vsr]
    if masked:
        in_specs.append(_token_spec(bk, False, kvm_map))
        inputs.append(_token_vectors(kv_mask.astype(jnp.int32), False))

    if fused:
        out_specs = [
            pl.BlockSpec((1, bq, d), q_map, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, bq, 1), q_map, memory_space=pltpu.VMEM),
        ]
        out_shape = [
            _sds((b * hk, bq, d), q.dtype, q),
            _sds((b * hk, bq, 1), jnp.float32, q),
        ]
    else:
        out_specs = [
            pl.BlockSpec((1, bq, d), q_map, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, bq, 1), q_map, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, bq, 1), q_map, memory_space=pltpu.VMEM),
        ]
        out_shape = [
            _sds((b * hk, bq, d), jnp.float32, q),
            _sds((b * hk, bq, 1), jnp.float32, q),
            _sds((b * hk, bq, 1), jnp.float32, q),
        ]

    kernel = functools.partial(
        _decode_q8_kernel,
        masked=masked, fused=fused, scale=scale,
        softclamp_value=softclamp_value, nk_blocks=nk // bk,
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=0,
        grid=(b * hk, nk // bk),
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=[
            pltpu.VMEM((bq, d), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
        ],
    )
    _log_launch("flash_decode_q8", nq, nk, h, hk, d, bq, bk,
                (b * hk, nk // bk))
    results = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=out_shape,
        compiler_params=compat.tpu_compiler_params(
            dimension_semantics=("parallel", "arbitrary")
        ),
        interpret=interpret,
        name="flash_decode_q8",
    )(*inputs)

    if fused:
        out, lse = results
        return (
            out.reshape(b, hk, bq, d)[:, :, :rows].reshape(b, h, nq, d),
            lse.reshape(b, hk, bq)[:, :, :rows].reshape(b, h, nq),
        )
    acc, m, l = results
    return (
        acc.reshape(b, hk, bq, d)[:, :, :rows].reshape(b, hk, g, nq, d),
        m.reshape(b, hk, bq)[:, :, :rows].reshape(b, hk, g, nq),
        l.reshape(b, hk, bq)[:, :, :rows].reshape(b, hk, g, nq),
    )


def init_partials(
    b: int, h: int, nq: int, d: int, like: jax.Array | None = None
) -> FlashPartials:
    """Identity element for :func:`merge_partials` (keeps the MASK_VALUE
    sentinel invariant local to this module)."""
    parts = FlashPartials(
        jnp.zeros((b, h, nq, d), jnp.float32),
        jnp.full((b, h, nq), MASK_VALUE, jnp.float32),
        jnp.zeros((b, h, nq), jnp.float32),
    )
    if like is not None:
        parts = FlashPartials(*_unify_vma(*parts, like)[:3])
    return parts


def merge_partials(a: FlashPartials, b: FlashPartials) -> FlashPartials:
    """Exact online-softmax merge of two partial sweeps (associative)."""
    m = jnp.maximum(a.m, b.m)
    ea = jnp.exp(a.m - m)
    eb = jnp.exp(b.m - m)
    return FlashPartials(
        a.acc * ea[..., None] + b.acc * eb[..., None],
        m,
        a.l * ea + b.l * eb,
    )


def finalize_partials(p: FlashPartials) -> tuple[jax.Array, jax.Array]:
    """Returns (out f32 (b,h,n,d), lse (b,h,n))."""
    out = p.acc / jnp.maximum(p.l, EPSILON)[..., None]
    lse = p.m + jnp.log(jnp.maximum(p.l, EPSILON))
    return out, lse


# ---------------------------------------------------------------------------
# Backward kernel
# ---------------------------------------------------------------------------


def _bwd_tables(n_q_blocks, n_k_blocks, bq, bk, hint, windowed, doc_starts):
    """The k-major band tables (:func:`_band_tables`) as the one-pass
    backward walks them: every entry that is the first to touch its q
    block carries ``_TF_QFIRST``, and a q block no entry touches (rows
    the band leaves empty) gets a flag-only entry at the end, so that its
    dq tile is still written, as zeros."""
    tq, tk, tf = (
        t.tolist()
        for t in _band_tables(n_q_blocks, n_k_blocks, bq, bk, hint, windowed,
                              outer_is_q=False, doc_starts=doc_starts)
    )
    touched = set()
    for i, qi in enumerate(tq):
        if qi not in touched:
            touched.add(qi)
            tf[i] |= _TF_QFIRST
    for qi in range(n_q_blocks):
        if qi not in touched:
            tq.append(qi)
            tk.append(tk[-1])  # the resident KV block: nothing to fetch
            tf.append(_TF_QFIRST)
    return (np.asarray(tq, np.int32), np.asarray(tk, np.int32),  # ra: allow(RA009 trace-time static tile tables — python ints, never traced)
            np.asarray(tf, np.int32))  # ra: allow(RA009 trace-time static tile tables — python ints, never traced)


def _bwd_kernel(*refs, compact: bool, masked: bool, segmented: bool,
                nq_blocks: int, **tile_kw):
    """One-pass backward: the grid holds a KV block and streams the query
    blocks of its band (rect grid ``(bh, ki, qi)``; compact grid
    ``(bh, t)`` over the k-major tables of :func:`_bwd_tables`).  Each
    step makes ``sT``/``pT``/``dpT`` once and from them all three
    gradients (:func:`_bwd_tile`).

    dk/dv accumulate in VMEM scratch over the consecutive steps of a KV
    block, as a dk/dv pass would.  A q block's dq is revisited once per KV
    block, *not* consecutively, so it lives in HBM (``dq_hbm``; its rows
    padded to whole :data:`LANE` multiples, the only rows Mosaic lets a
    hand-written copy slice) and every step read-add-writes its
    ``(bq, d)`` tile through ``_DQ_SLOTS`` VMEM slots: the read of step
    ``s + 1`` starts before the compute of step ``s``, the write of step
    ``s`` is waited for two steps later.  The first step to touch a q
    block zero-fills its slot instead of reading.  A step whose tile is
    the one step ``s - 1`` or ``s - 2`` writes (e.g. one q block a KV
    block) is *deferred*: not prefetched, it reads after waiting for that
    write.  The pipeline drains at the end of each ``bh`` row, so rows
    are independent ("parallel").

    Ref layout:
      scalars: offs (+ tq/tk/tf tile tables when ``compact``)
      inputs:  q, do, lse, delta, k, v (+ kv mask when ``masked``)
               (+ q/kv segment ids when ``segmented``)
      outputs: dq (HBM), dk, dv
      scratch: dk, dv (bk, d) f32, dq slots (_DQ_SLOTS, bq, d) f32, read
               and write DMA semaphores (_DQ_SLOTS,) each
    """
    bq, bk = tile_kw["bq"], tile_kw["bk"]
    tile_kw = dict(tile_kw, masked=masked, segmented=segmented)
    if compact:
        offs_ref, tq_ref, tk_ref, tf_ref = refs[:4]
        idx = 4
        step = pl.program_id(1)
        steps = pl.num_programs(1)
        tf = tf_ref[step]
        first = (tf & _TF_FIRST) != 0
        last = (tf & _TF_LAST) != 0
        row0, col0 = tq_ref[step] * bq, tk_ref[step] * bk

        def q_block(s):
            return tq_ref[s]

        def fresh(s):  # no earlier step wrote this step's dq tile
            return (tf_ref[s] & _TF_QFIRST) != 0
    else:
        offs_ref = refs[0]
        idx = 1
        ki, qi = pl.program_id(1), pl.program_id(2)
        first = qi == 0
        last = qi == nq_blocks - 1
        row0, col0 = qi * bq, ki * bk
        step = ki * nq_blocks + qi
        steps = pl.num_programs(1) * nq_blocks

        def q_block(s):
            return lax.rem(s, nq_blocks)

        def fresh(s):
            return s < nq_blocks

    q_ref, do_ref, lse_ref, delta_ref, k_ref, v_ref = refs[idx:idx + 6]
    idx += 6
    kvm_ref = refs[idx] if masked else None
    idx += 1 if masked else 0
    qseg_ref = kseg_ref = None
    if segmented:
        qseg_ref, kseg_ref = refs[idx:idx + 2]
        idx += 2
    dq_hbm, dk_ref, dv_ref, dk, dv, dq_buf, read_sem, write_sem = refs[idx:]

    bh = pl.program_id(0)

    def dq_tile(s):
        return dq_hbm.at[bh, pl.ds(pl.multiple_of(q_block(s) * bq, bq), bq)]

    def read(s):
        slot = lax.rem(s, _DQ_SLOTS)
        return pltpu.make_async_copy(  # ra: allow(RA013 local HBM<->VMEM copy of this launch's own dq tile — no peer, no barrier, drained every bh row)
            dq_tile(s), dq_buf.at[slot], read_sem.at[slot])

    def write(s):
        # a wait only needs the slot's semaphore and the tile's size, so
        # waits on older steps address the current tile (no table reads
        # at negative steps)
        slot = lax.rem(s, _DQ_SLOTS)
        return pltpu.make_async_copy(  # ra: allow(RA013 local HBM<->VMEM copy of this launch's own dq tile — no peer, no barrier, drained every bh row)
            dq_buf.at[slot], dq_tile(step), write_sem.at[slot])

    def deferred(s):
        """Does step ``s`` read a tile that step ``s - 1`` or ``s - 2``
        is still writing?"""
        hit = False
        for back in (1, 2):
            prev = jnp.maximum(s - back, 0)
            hit = hit | ((s >= back) & (q_block(s) == q_block(prev)))
        return hit

    nxt = jnp.minimum(step + 1, steps - 1)
    def_prev, def_cur, def_next = (deferred(jnp.maximum(step - 1, 0)),
                                   deferred(step), deferred(nxt))
    fresh_cur = fresh(step)

    @pl.when((step >= 2) & ~def_prev)
    def _free_slot():  # the slot the next prefetch lands in
        write(step - 2).wait()

    @pl.when(def_cur)
    def _read_after_write():
        write(step - 1).wait()
        read(step).start()

    @pl.when((step + 1 < steps) & ~def_next & ~fresh(nxt))
    def _prefetch():
        read(nxt).start()

    @pl.when(first)
    def _init():
        dk[:] = jnp.zeros_like(dk)
        dv[:] = jnp.zeros_like(dv)

    slot = lax.rem(step, _DQ_SLOTS)

    @pl.when(fresh_cur)
    def _zero_fill():
        dq_buf[slot] = jnp.zeros(dq_buf.shape[1:], dq_buf.dtype)

    @pl.when(~fresh_cur)
    def _await_read():
        read(step).wait()

    tile = _tile_closure(_bwd_tile, tile_kw, offs_ref, q_ref, do_ref, lse_ref,
                         delta_ref, k_ref, v_ref, kvm_ref, qseg_ref, kseg_ref,
                         dk, dv, dq_buf.at[slot], row0, col0)
    if compact:
        _dispatch_tile_compact(tf, tile)
    else:
        _dispatch_tile(offs_ref, row0, col0, bq, bk, tile_kw["causal"],
                       tile_kw["windowed"], tile)
    write(step).start()

    @pl.when(last)
    def _write():
        dk_ref[0] = dk[:]
        dv_ref[0] = dv[:]

    @pl.when(step == steps - 1)
    def _drain():
        @pl.when((step >= 1) & ~def_cur)
        def _():
            write(step - 1).wait()

        write(step).wait()


def _bwd_vmem_limit(bq, bk, d_pad, itemsize, softclamp):
    """Scoped-VMEM request of the one-pass backward, or None while it
    fits the 16 MiB every generation grants by default: the pipeline's
    double-buffered blocks (the ``(bq, 1)`` columns are lane-padded), the
    dk/dv accumulators and dq slots, and the score-sized tiles one step
    keeps live (``sT``/``pT``, ``dpT``, ``dsT`` in f32, the two bf16
    operands and the transposed one; the clamp keeps two more)."""
    blocks = 2 * (2 * bq * d_pad * itemsize + 2 * bq * LANE * 4
                  + 2 * bk * d_pad * itemsize + 2 * bk * d_pad * 4)
    scratch = (2 * bk + _DQ_SLOTS * bq) * d_pad * 4
    live = (8 if softclamp else 6) * bq * bk * 4
    need = blocks + scratch + live
    return need if need > 16 * 2**20 else None


def _bwd_tile(offs_ref, q_ref, do_ref, lse_ref, delta_ref, k_ref, v_ref,
              kvm_ref, qseg_ref, kseg_ref, dk, dv, dq, row0, col0, *, scale,
              softclamp_value, causal, windowed, masked, segmented, bq, bk,
              exp2=False):
    """Five products a tile, all k-major: ``sT``, ``dV += pT·dO``,
    ``dpT``, ``dK += dsT·Q``, ``dQ += dsTᵀ·K``."""
    kb = k_ref[0]
    qb = q_ref[0]
    # sT: (bk, bq) = k . q^T (contract d on both)
    sT = lax.dot_general(
        kb, qb, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )
    if scale != 1.0:  # static: folded into q for power-of-two scales
        sT = sT * scale
    if softclamp_value is not None:
        sT = _softclamp(sT, softclamp_value, exp2)

    ex = jnp.exp2 if exp2 else jnp.exp
    pT = ex(sT - lse_ref[0])
    keep = _tile_keep(
        offs_ref, row0, col0, (bk, bq), 1, causal, windowed,
        kvm_ref if masked else None,
        qseg_ref if segmented else None,
        kseg_ref if segmented else None,
    )
    if keep is not None:
        pT = jnp.where(keep, pT, 0.0)

    dob = do_ref[0]
    dv[:] = dv[:] + lax.dot_general(
        pT.astype(dob.dtype), dob, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    # dpT: (bk, bq) = v . do^T
    dpT = lax.dot_general(
        v_ref[0], dob, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    dsT = pT * (dpT - delta_ref[0])
    if softclamp_value is not None:
        dsT = dsT * _softclamp_grad_factor(sT, softclamp_value, exp2)
    if scale != 1.0:  # folded q̃ makes dsT·q̃ carry the factor exactly,
        dsT = dsT * scale  # and dq is post-scaled once on the output
    dsT = dsT.astype(qb.dtype)
    dk[:] = dk[:] + lax.dot_general(
        dsT, qb, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    # dq: (bq, d) = dsT^T . k (contract the key rows of both), into the
    # first d lanes of its lane-padded slot
    d = kb.shape[-1]
    dq[:, :d] = dq[:, :d] + lax.dot_general(
        dsT, kb, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )


def pallas_flash_backward(
    do: jax.Array,  # (b, h, nq, d)
    q: jax.Array,
    k: jax.Array,  # (b, hk, nk, d)
    v: jax.Array,
    lse: jax.Array,  # (b, h, nq) f32
    delta: jax.Array,  # (b, h, nq) f32
    kv_mask: jax.Array | None = None,
    *,
    scale: float,
    causal_offset: jax.Array | int | None = None,
    window_lo: jax.Array | int | None = None,
    softclamp_value: float | None = None,
    block_q: int | None = None,
    block_k: int | None = None,
    band_hint: tuple[int, int, int, int] | None = None,
    interpret: bool | None = None,
    exp2: bool | None = None,
    segment_ids=None,
    doc_starts: tuple[int, ...] | None = None,
    compute_dtype: str | None = None,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """One-pass flash backward (kernel ``flash_bwd_dkv_dq``). Returns
    (dq, dk, dv), all f32, dk/dv with ``hk`` heads (GQA group-summed).

    One launch walks the band k-major and per tile makes the scores and
    ``dP`` once: five matrix products and one ``exp`` sweep for dv, dk
    and dq together (:func:`_bwd_kernel` says how dq, which no
    consecutive run of steps owns, is accumulated in HBM).

    ``segment_ids``/``doc_starts`` mirror the forward (packed sequences):
    cross-document terms drop out of ``p``, and a block-aligned declared
    layout drops cross-document tiles from the compact grid at trace
    time.

    ``compute_dtype`` is the knob SURFACE for the int8 backward; this
    round only ``None`` (bf16 matmuls) is implemented — the dk/dv/dq
    error budget does not yet admit int8 recompute (docs/precision.md §5),
    so an int8-forward model differentiates through an exact-residual
    bf16 backward."""
    if compute_dtype is not None:
        raise NotImplementedError(
            f"pallas_flash_backward: compute_dtype={compute_dtype!r} — the "
            "backward runs bf16 this round (dk/dv/dq error bounds, "
            "docs/precision.md §5); pass compute_dtype=None"
        )
    b, h, nq, d = q.shape
    _, hk, nk, _ = k.shape
    g = h // hk
    q_seg, kv_seg = normalize_segment_ids(
        segment_ids, q, k, "pallas_flash_backward"
    )
    doc_starts = _check_doc_starts(doc_starts, nq, nk)

    # power-of-two scale folds into q here too (exact, see _flash_fwd_call):
    # sT recomputes unchanged, dk = dsT·q̃ absorbs the factor exactly
    # (dk = scale·dsTᵀ·q = dsTᵀ·(scale·q)), and dq comes out unscaled —
    # multiplied once on the (nq, d) output below instead of per (bq, bk)
    # tile.  Deletes both per-tile score-path multiplies.
    # In exp2 mode (RING_ATTN_EXP2=1) the fold is scale*log2e and lse
    # converts to log2 units once out here, so the in-tile p recompute is
    # a bare exp2; dk then carries a surplus log2e absorbed by a ln2
    # multiply on its (nk, d) output.  Explicit ``exp2=`` overrides the
    # env var (trace-time capture, see _exp2_default).
    exp2 = _exp2_default() if exp2 is None else bool(exp2)
    dq_post_scale = 1.0
    dkv_post_scale = 1.0
    if exp2:
        q = q * jnp.asarray(scale * LOG2E, q.dtype)
        lse = lse * LOG2E
        dq_post_scale = scale
        dkv_post_scale = LN2
        scale = 1.0
    elif scale != 1.0 and math.frexp(float(scale))[0] == 0.5:
        q = q * jnp.asarray(scale, q.dtype)
        dq_post_scale = scale
        scale = 1.0

    bq, bk = _block_sizes(nq, nk, block_q, block_k)
    interpret = _interpret_default() if interpret is None else interpret

    causal = causal_offset is not None
    windowed = window_lo is not None and causal
    masked = kv_mask is not None
    offs = jnp.asarray(
        [causal_offset if causal else 0, window_lo if windowed else 0], jnp.int32
    )

    hint = _normalize_hint(causal, windowed, causal_offset, window_lo,
                           band_hint)
    compact = hint is not None
    # trace-time doc skip needs a compact grid AND a block-aligned layout
    doc_tables = (
        doc_starts
        if compact and doc_starts is not None
        and _docs_block_aligned(doc_starts, bq, bk)
        else None
    )
    tabs = []
    if compact:
        tiles = _band_tile_count(
            nq // bq, nk // bk, bq, bk, hint, windowed, outer_is_q=False,
            doc_starts=doc_tables,
        )
        compact = tiles <= _MAX_COMPACT_TILES
        if compact:
            tabs = [
                jnp.asarray(t)
                for t in _bwd_tables(nq // bq, nk // bk, bq, bk, hint,
                                     windowed, doc_tables)
            ]
        else:
            _warn_demoted("bwd", tiles, stacklevel=3)
            doc_tables = None
    if doc_tables is not None:
        # the tables carry the whole document mask: ship no segment refs
        q_seg = kv_seg = None
    elif doc_starts is not None and q_seg is None:
        # misaligned/demoted declared layout: realize it as runtime ids
        q_seg = kv_seg = _doc_runtime_ids(doc_starts, nq, b)
    segmented = q_seg is not None

    unified = _unify_vma(
        q, k, v, do, lse, delta, kv_mask, q_seg, kv_seg, offs, *tabs
    )
    q, k, v, do, lse, delta, kv_mask, q_seg, kv_seg, offs = unified[:10]
    tabs = unified[10:]

    if compact:
        q_map, kv_map, kvm_map, qsm_map, k_out_map = _compact_maps(h, hk, g)
        scalars = (offs, *tabs)
        grid = (b * h, tabs[0].shape[0])
    else:
        scalars = (offs,)
        grid = (b * h, nk // bk, nq // bq)

        def q_map(bh, ki, qi, *_):
            return (bh, qi, 0)

        def kv_map(bh, ki, qi, *_):
            return ((bh // h) * hk + (bh % h) // g, ki, 0)

        def kvm_map(bh, ki, qi, *_):
            return (bh // h, ki)

        def qsm_map(bh, ki, qi, *_):
            return (bh // h, qi)

        def k_out_map(bh, ki, qi, *_):
            return (bh, ki, 0)

    # a bh row owns its dq tiles and drains its copies, so rows are
    # independent; within a row every step shares dk/dv or a dq tile
    semantics = ("parallel",) + ("arbitrary",) * (len(grid) - 1)
    kernel = functools.partial(
        _bwd_kernel,
        compact=compact,
        masked=masked,
        segmented=segmented,
        nq_blocks=nq // bq,
        scale=scale,
        softclamp_value=softclamp_value,
        causal=causal,
        windowed=windowed,
        bq=bq,
        bk=bk,
        exp2=exp2,
    )

    def q_row_map(*args):
        return q_map(*args)[:2]

    # lse and delta are rows, as the k-major tiles use them: as (bq, 1)
    # columns they were laid out lane-padded, 128 times their bytes
    in_specs = [
        pl.BlockSpec((1, bq, d), q_map, memory_space=pltpu.VMEM),
        pl.BlockSpec((1, bq, d), q_map, memory_space=pltpu.VMEM),
        _token_spec(bq, False, q_row_map),
        _token_spec(bq, False, q_row_map),
        pl.BlockSpec((1, bk, d), kv_map, memory_space=pltpu.VMEM),
        pl.BlockSpec((1, bk, d), kv_map, memory_space=pltpu.VMEM),
    ]
    inputs = [
        q.reshape(b * h, nq, d),
        do.reshape(b * h, nq, d).astype(q.dtype),
        _token_vectors(lse.reshape(b * h, nq), False),
        _token_vectors(delta.reshape(b * h, nq), False),
        k.reshape(b * hk, nk, d),
        v.reshape(b * hk, nk, d),
    ]
    # per-token operands (_token_vectors): the tiles are transposed (keys
    # on rows), so k-side vectors are columns and q-side vectors rows
    if masked:
        in_specs.append(_token_spec(bk, True, kvm_map))
        inputs.append(_token_vectors(kv_mask.astype(jnp.int32), True))
    if segmented:
        in_specs += [
            _token_spec(bq, False, qsm_map),
            _token_spec(bk, True, kvm_map),
        ]
        inputs += [_token_vectors(q_seg.astype(jnp.int32), False),
                   _token_vectors(kv_seg.astype(jnp.int32), True)]

    d_pad = d + (-d) % LANE
    name = "flash_bwd_dkv_dq"
    _log_launch(name, nq, nk, h, hk, d, bq, bk, grid, compact)
    dq, dk_h, dv_h = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(scalars),
            grid=grid,
            in_specs=in_specs,
            out_specs=[
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec((1, bk, d), k_out_map, memory_space=pltpu.VMEM),
                pl.BlockSpec((1, bk, d), k_out_map, memory_space=pltpu.VMEM),
            ],
            scratch_shapes=[
                pltpu.VMEM((bk, d), jnp.float32),
                pltpu.VMEM((bk, d), jnp.float32),
                pltpu.VMEM((_DQ_SLOTS, bq, d_pad), jnp.float32),
                pltpu.SemaphoreType.DMA((_DQ_SLOTS,)),  # ra: allow(RA013 completion semaphores of the launch's own dq copies, see _bwd_kernel)
                pltpu.SemaphoreType.DMA((_DQ_SLOTS,)),  # ra: allow(RA013 completion semaphores of the launch's own dq copies, see _bwd_kernel)
            ],
        ),
        out_shape=[
            _sds((b * h, nq, d_pad), jnp.float32, q),
            _sds((b * h, nk, d), jnp.float32, q),
            _sds((b * h, nk, d), jnp.float32, q),
        ],
        compiler_params=compat.tpu_compiler_params(
            dimension_semantics=semantics,
            vmem_limit_bytes=_bwd_vmem_limit(
                bq, bk, d_pad, q.dtype.itemsize, softclamp_value is not None),
        ),
        interpret=interpret,
        name=name,
    )(*scalars, *inputs)

    # GQA: dk/dv leave the kernel per query head and are summed over the
    # group here.  Summing them in the kernel (the group innermost in the
    # grid) was built and works, but with 1.5 GB less live around the
    # launch XLA's memory scheduler picks another order for the whole
    # train step, whose peak is 0.85 GiB higher (PERF.md section 6, PR 31)
    dk = dk_h.reshape(b, hk, g, nk, d).sum(axis=2)
    dv = dv_h.reshape(b, hk, g, nk, d).sum(axis=2)
    if dkv_post_scale != 1.0:
        # exp2 mode: dsT·q̃ carries a surplus log2e; ln2 restores it
        # (one (nk, d) f32 multiply vs one per (bq, bk) tile)
        dk = dk * dkv_post_scale
    dq = dq[:, :, :d].reshape(b, h, nq, d)
    if dq_post_scale != 1.0:
        dq = dq * dq_post_scale  # f32 output, power-of-two: exact
    return dq, dk, dv


# ---------------------------------------------------------------------------
# User-facing single-device flash attention on the Pallas path
# ---------------------------------------------------------------------------


@functools.partial(
    jax.custom_vjp, nondiff_argnums=(6, 7, 8, 9, 10, 11, 12, 13)
)
def _pallas_flash_core(q, k, v, kv_mask, q_seg, kv_seg, scale, causal_offset,
                       window, softclamp_value, interpret, exp2, doc_starts,
                       compute_dtype=None):
    out, _ = _pallas_flash_fwd_impl(
        q, k, v, kv_mask, q_seg, kv_seg, scale, causal_offset, window,
        softclamp_value, interpret, exp2, doc_starts, compute_dtype
    )
    return out


def _pallas_flash_fwd_impl(q, k, v, kv_mask, q_seg, kv_seg, scale,
                           causal_offset, window, softclamp_value, interpret,
                           exp2, doc_starts, compute_dtype=None):
    window_lo = causal_offset - (window - 1) if window is not None else None
    # fused finalize: the kernel writes normalized q.dtype output + lse, so
    # the f32 (acc, m, l) triple never touches HBM (512 MB saved per call
    # at seq 262144, h=8, d=64)
    out, lse = _flash_fwd_call(
        q, k, v, kv_mask,
        scale=scale, causal_offset=causal_offset, window_lo=window_lo,
        softclamp_value=softclamp_value, block_q=None, block_k=None,
        band_hint=None, interpret=interpret, fused=True, exp2=exp2,
        q_segment_ids=q_seg, kv_segment_ids=kv_seg, doc_starts=doc_starts,
        compute_dtype=compute_dtype,
    )
    # named residuals: lets a remat policy save (out, lse) so the backward's
    # residual recompute elides this kernel (see parallel/ring.py, same names)
    out = checkpoint_name(out, "flash_out")
    lse = checkpoint_name(lse, "flash_lse")
    return out, lse


def _pallas_flash_core_fwd(q, k, v, kv_mask, q_seg, kv_seg, scale,
                           causal_offset, window, softclamp_value, interpret,
                           exp2, doc_starts, compute_dtype=None):
    out, lse = _pallas_flash_fwd_impl(
        q, k, v, kv_mask, q_seg, kv_seg, scale, causal_offset, window,
        softclamp_value, interpret, exp2, doc_starts, compute_dtype
    )
    return out, (q, k, v, kv_mask, q_seg, kv_seg, out, lse)


def _pallas_flash_core_bwd(scale, causal_offset, window, softclamp_value,
                           interpret, exp2, doc_starts, compute_dtype, res,
                           do):
    # the backward stays bf16 regardless of the forward's compute_dtype
    # this round: it recomputes scores from the EXACT residual (q, k, v)
    # — int8 touched only the forward's (out, lse) — docs/precision.md §5
    q, k, v, kv_mask, q_seg, kv_seg, out, lse = res
    window_lo = causal_offset - (window - 1) if window is not None else None
    delta = (do.astype(jnp.float32) * out.astype(jnp.float32)).sum(-1)
    dq, dk, dv = pallas_flash_backward(
        do, q, k, v, lse, delta, kv_mask,
        scale=scale, causal_offset=causal_offset, window_lo=window_lo,
        softclamp_value=softclamp_value, interpret=interpret, exp2=exp2,
        segment_ids=(None if q_seg is None else (q_seg, kv_seg)),
        doc_starts=doc_starts,
    )
    return (dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype),
            None, None, None)


_pallas_flash_core.defvjp(_pallas_flash_core_fwd, _pallas_flash_core_bwd)


def pallas_flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mask: jax.Array | None = None,
    *,
    causal: bool = False,
    window: int | None = None,
    softclamp_value: float | None = None,
    scale: float | None = None,
    head_chunks: int | None = None,
    interpret: bool | None = None,
    exp2: bool | None = None,
    segment_ids=None,
    doc_starts: tuple[int, ...] | None = None,
    compute_dtype: str | None = None,
) -> jax.Array:
    """Exact flash attention on the Pallas TPU kernel path (GQA-aware).

    Same contract as ``ops.flash.flash_attention``; parity-tested against
    the oracle.  On non-TPU backends runs the kernels in interpreter mode.

    ``head_chunks`` splits the launch into that many kernel calls over
    contiguous head groups (GQA groups stay aligned: chunk ``i`` holds q
    heads ``[i*h/c, (i+1)*h/c)`` against kv heads ``[i*hk/c, (i+1)*hk/c)``).
    Each chunk is an independent pallas program — fwd AND bwd via the
    per-chunk custom_vjp — so a shape whose single-program compile blows a
    compiler size limit still runs at full rate, paying only c-1 extra
    kernel launches.  Heads are embarrassingly parallel in attention, so
    outputs are bit-identical to the unsplit launch.

    ``segment_ids`` (``(b, n)`` array or ``(q_ids, kv_ids)`` pair) masks
    cross-document attention for packed sequences — fwd and bwd.
    ``doc_starts`` is the *static* layout declaration: when its boundaries
    land on the kernel block sizes, cross-document tiles leave the compact
    causal grid at trace time (skipped, not masked); see
    ``docs/packing.md`` for the contract.

    ``compute_dtype="int8"`` quantizes the FORWARD's QK^T/PV matmul
    operands (per-block absmax, f32 accumulators untouched); the backward
    stays bf16 from the exact residuals — fwd error ≤ the int8-hop bound
    (``docs/precision.md``).
    """
    check_attention_args("pallas_flash_attention", q, k, v, mask)
    q_seg, kv_seg = normalize_segment_ids(
        segment_ids, q, k, "pallas_flash_attention"
    )
    if doc_starts is not None:
        doc_starts = _check_doc_starts(doc_starts, q.shape[2], k.shape[2])
    if scale is None:
        scale = q.shape[-1] ** -0.5
    if window is not None:
        assert causal, "lookback windows require causal attention"
    if causal:
        mask = None
    nq, nk = q.shape[2], k.shape[2]
    causal_offset = nk - nq if causal else None
    pad_q = _tileable_len(nq, DEFAULT_BLOCK_Q) - nq
    pad_k = _tileable_len(nk, DEFAULT_BLOCK_K) - nk
    if pad_q or pad_k:
        # pad to a tileable length (sliced off below).  The causal band
        # keeps its unpadded offset, which already excludes every pad key
        # from every real query; without a band the pad keys are masked.
        def pad(x, n, axis, value=0):
            widths = [(0, 0)] * x.ndim
            widths[axis] = (0, n)
            return jnp.pad(x, widths, constant_values=value)

        if not causal and pad_k:
            if mask is None:
                mask = jnp.ones((k.shape[0], nk), jnp.bool_)
            mask = pad(mask, pad_k, 1, False)
        q = pad(q, pad_q, 2)
        k, v = pad(k, pad_k, 2), pad(v, pad_k, 2)
        if q_seg is not None:
            q_seg = pad(q_seg, pad_q, 1, PAD_SEGMENT_ID)
            kv_seg = pad(kv_seg, pad_k, 1, PAD_SEGMENT_ID)
    interpret = interpret if interpret is not None else _interpret_default()
    # resolve the log2-space flag ONCE here: the custom_vjp's forward and
    # backward then share one basis even if the env var flips mid-call,
    # and an explicit exp2= keys the jit cache (see _exp2_default)
    exp2 = _exp2_default() if exp2 is None else bool(exp2)
    if head_chunks is not None and head_chunks > 1:
        h, hk = q.shape[1], k.shape[1]
        if h % head_chunks or hk % head_chunks:
            raise ValueError(
                f"pallas_flash_attention: head_chunks={head_chunks} must "
                f"divide both heads={h} and kv_heads={hk}"
            )
        hq_c, hk_c = h // head_chunks, hk // head_chunks
        outs = [
            _pallas_flash_core(
                q[:, i * hq_c:(i + 1) * hq_c],
                k[:, i * hk_c:(i + 1) * hk_c],
                v[:, i * hk_c:(i + 1) * hk_c],
                mask, q_seg, kv_seg, scale, causal_offset, window,
                softclamp_value, interpret, exp2, doc_starts, compute_dtype,
            )
            for i in range(head_chunks)
        ]
        return jnp.concatenate(outs, axis=1)[:, :, :nq]
    return _pallas_flash_core(
        q, k, v, mask, q_seg, kv_seg, scale, causal_offset, window,
        softclamp_value, interpret, exp2, doc_starts, compute_dtype,
    )[:, :, :nq]
