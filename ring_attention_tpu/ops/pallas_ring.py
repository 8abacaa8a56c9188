"""Fused-ring Pallas forward: every ring hop inside ONE kernel launch.

The scan-path ring (``parallel/ring.py``) interleaves per-hop flash kernel
launches with ``lax.ppermute`` KV rotations, leaving XLA to decide how much
of each transfer hides behind compute — PR 8's ``measured_overlap`` exists
precisely because that slack is real.  This module removes the launch
boundary itself, in two tiers:

``fused_ring_local``
    One ``pallas_call`` whose innermost grid dimension walks the certified
    hop schedule (origin / hi / lo / work tables from
    ``parallel/ring.py::_fused_tables``) over an all-gathered KV span,
    carrying the f32 ``(acc, m, l)`` online-softmax state in VMEM scratch
    across every hop — zero per-hop dispatch, zero HBM round-trips of the
    accumulator, zero ``ppermute`` in the forward.  Runs compiled on TPU
    and in interpret mode on CPU (the parity-test tier), and accepts the
    int8 kernel feed from PR 13 (``quant.payload_kernel_feed`` /
    ``quant.quantize_kv_blocks``) so quantized QK^T/PV ride the same
    launch.

``fused_ring_remote``
    The ICI tier: the kernel itself double-buffers the NEXT rank's KV
    shard via async remote DMA (``pltpu.make_async_remote_copy`` into the
    alternate slot of an HBM ring buffer) while the current hop's tiles
    compute.  The circulated buffer and the cross-hop ``(acc, m, l)``
    carry are HBM-resident — compute stages tile-sized blocks through
    VMEM scratch, so the kernel fits arbitrary ``n_local`` — and each
    push is gated by a receiver-to-sender GRANT semaphore (the receiver
    signals its left neighbor once it has drained a slot's last read, so
    compute skew under causal ``works`` schedules can never let a DMA
    overwrite KV mid-read).  Remote descriptors address neighbors by
    per-axis MESH coordinates (:func:`neighbor_mesh_coords`), varying
    only the ring axis — correct on multi-axis (data × seq, hybrid DCN)
    meshes where a ring-rank-only LOGICAL id would target the wrong
    replica group; physical ICI adjacency holds because
    ``parallel/mesh.py::torus_ring_order`` fed mesh construction.  With
    an int8 ``pack_kv`` payload the per-row dequant scales travel inside
    the circulated buffer (bitcast into the trailing ``SCALE_BYTES``
    lanes), so quantized hops need no side-channel collective.  Executes
    on TPU only; on CPU it still *traces* — which is how
    ``analysis/contracts.py`` counts the in-kernel ``dma_start`` /
    semaphore primitives and proves the forward carries zero ppermutes.

Both tiers share ``ops/pallas_flash.py``'s tile math (``_online_update``)
and banded-offset mask contract (attend iff ``lo <= j - i <= hi`` in
per-hop local coordinates), so fused output is tile-order-identical to the
scan path and parity pins can be tight.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .attention import EPSILON, MASK_VALUE
from .pallas_flash import (
    _block_sizes,
    _int8_dot,
    _interpret_default,
    _log_launch,
    _online_update,
    _sds,
    _unify_vma,
)
from . import quant as _quant
from .quant import QuantizedBlockKV
from ..utils import compat
from ..utils.validate import check_attention_args

# One collective_id per concurrently-live barrier semaphore (Mosaic
# requirement); the fused ring is the only in-kernel collective in the
# package so a single id suffices.
COLLECTIVE_ID = 7

__all__ = [
    "COLLECTIVE_ID",
    "PROTOCOL",
    "fitted_blocks",
    "fused_ring_local",
    "fused_ring_remote",
    "neighbor_mesh_coords",
    "remote_supported",
]


def remote_supported() -> bool:
    """Does this jax expose the in-kernel remote-DMA surface we need?"""
    return all(
        hasattr(pltpu, name)
        for name in (
            "make_async_copy",
            "make_async_remote_copy",
            "get_barrier_semaphore",
            "semaphore_signal",
            "semaphore_wait",
            "SemaphoreType",
            "DeviceIdType",
        )
    )


def fitted_blocks(n_local: int, block_q: int | None, block_k: int | None):
    """The (bq, bk) the fused kernel will actually run for ``n_local`` —
    callers packing an int8 feed must quantize V at exactly this bk."""
    return _block_sizes(n_local, n_local, block_q, block_k)


# ---------------------------------------------------------------------------
# Local tier: one launch over an all-gathered KV span
# ---------------------------------------------------------------------------


def _fused_local_kernel(origins_ref, his_ref, los_ref, works_ref, *refs,
                        masked: bool, segmented: bool, quantized: bool,
                        kpb: int, spans: int, scale: float,
                        softclamp_value: float | None, bq: int, bk: int):
    """Grid ``(b, h, n_q_blocks, hops * kpb)``; the innermost dimension is
    the fused hop walk: ``s // kpb`` selects the hop (whose origin rank,
    band offsets and work flag arrive via scalar prefetch), ``s % kpb``
    the KV tile within that hop's block.  The ``(acc, m, l)`` scratch
    persists across the whole walk — the scan path's inter-launch carry,
    without the launches."""
    q_ref, k_ref, v_ref = refs[:3]
    idx = 3
    scale_refs = None
    if quantized:
        scale_refs = refs[idx:idx + 3]
        idx += 3
    kvm_ref = refs[idx] if masked else None
    idx += 1 if masked else 0
    qseg_ref = kseg_ref = None
    if segmented:
        qseg_ref, kseg_ref = refs[idx:idx + 2]
        idx += 2
    out_ref, lse_ref = refs[idx:idx + 2]
    acc, m, l = refs[idx + 2:]

    s_id = pl.program_id(3)
    hop = s_id // kpb
    kb = s_id % kpb
    qi = pl.program_id(2)

    @pl.when(s_id == 0)
    def _init():
        acc[:] = jnp.zeros_like(acc)
        m[:] = jnp.full_like(m, MASK_VALUE)
        l[:] = jnp.zeros_like(l)

    # Tile-level skip mirrors the scan path exactly: the per-hop work flag
    # is `_hop_has_work`, the band predicate is `_tile_has_work` — so the
    # fused walk touches the same tiles in the same order and parity can
    # pin tight.  Sentinel offsets (+-n_local) make both checks vacuous
    # for unbanded hops.
    row0, col0 = qi * bq, kb * bk
    hi, lo = his_ref[hop], los_ref[hop]
    tile_live = (
        (works_ref[hop] != 0)
        & (col0 <= row0 + bq - 1 + hi)
        & (col0 + bk - 1 >= row0 + lo)
    )

    @pl.when(tile_live)
    def _tile():
        q = q_ref[0, 0]
        k = k_ref[0, 0]
        nt = (((1,), (1,)), ((), ()))
        if quantized:
            # int8 QK^T: per-row q/k dequant scales ride the matmul's free
            # indices; the softmax scale folds into the same rescale
            # (docs/precision.md — identical to pallas_flash._fwd_tile).
            qs_ref, ks_ref, _ = scale_refs
            s = _int8_dot(q, k, nt) * (
                (qs_ref[0, 0] * scale) * ks_ref[0, 0])
        else:
            s = lax.dot_general(q, k, nt, preferred_element_type=jnp.float32)
            if scale != 1.0:
                s = s * scale
        if softclamp_value is not None:
            s = jnp.tanh(s / softclamp_value) * softclamp_value

        # Band mask in per-hop LOCAL coordinates — the same contract the
        # scan path passes per launch as SMEM scalars, here indexed per
        # hop from the prefetched schedule.
        rows = lax.broadcasted_iota(jnp.int32, (bq, bk), 0) + row0
        cols = lax.broadcasted_iota(jnp.int32, (bq, bk), 1) + col0
        diff = cols - rows
        keep = (diff <= hi) & (diff >= lo)
        if masked:
            keep = keep & (kvm_ref[0] != 0)
        if segmented:
            keep = keep & (qseg_ref[0] == kseg_ref[0])
        s = jnp.where(keep, s, MASK_VALUE)

        _online_update(
            s, v_ref[0, 0], acc, m, l,
            v_scale=scale_refs[2][0, 0, 0] if quantized else None,  # (1, 1)
        )

    @pl.when(s_id == spans - 1)
    def _write():
        l_safe = jnp.maximum(l[:], EPSILON)
        out_ref[0, 0] = (acc[:] / l_safe).astype(out_ref.dtype)
        lse_ref[0, 0] = m[:] + jnp.log(l_safe)


def fused_ring_local(
    q, k_all, v_all, kv_mask=None, *,
    origins, his, los, works, n_local,
    scale=1.0, softclamp_value=None,
    block_q=None, block_k=None,
    q_segment_ids=None, kv_segment_ids=None,
    kv_quantized: QuantizedBlockKV | None = None,
    interpret=None, name="fused_ring_local",
):
    """Fused-ring forward over a gathered KV span, one launch.

    Args:
      q: ``(b, h, n_local, d)`` — this rank's queries.  With
        ``kv_quantized`` the QK^T side is still quantized per-row here
        (the launcher quantizes q; k arrives pre-quantized in the feed).
      k_all / v_all: ``(b, hk, n_total, d)`` gathered KV in ring order
        (rank-major).  Ignored (may be the quantized values' dequant
        twins) when ``kv_quantized`` is given.
      kv_mask: optional ``(b, n_total)`` bool.
      origins / his / los / works: ``(hops,)`` int32 hop schedule
        (``parallel/ring.py::_fused_tables``) — origin rank per hop, band
        offsets in per-hop local coordinates (sentinels ±n_local when
        unbanded), live flag.
      kv_quantized: PR 13's int8 kernel feed over the GATHERED span
        (``quant.payload_kernel_feed`` / ``quant.quantize_kv_blocks``);
        its ``block`` must equal the fitted bk (``fitted_blocks``).

    Returns:
      ``(out, lse)`` — ``(b, h, n_local, d)`` in q.dtype and
      ``(b, h, n_local)`` f32, the fused-write contract of
      ``pallas_flash`` (lse = m + log l).
    """
    b, h, n_q, d = q.shape
    hk = k_all.shape[1]
    if h % hk:
        raise ValueError(f"query heads {h} not a multiple of kv heads {hk}")
    g = h // hk
    n_total = k_all.shape[2]
    if n_q != n_local:
        raise ValueError(f"q length {n_q} != n_local {n_local}")
    if n_total % n_local:
        raise ValueError(f"gathered span {n_total} not a multiple of {n_local}")
    hops = int(origins.shape[0])

    bq, bk = _block_sizes(n_local, n_local, block_q, block_k)
    kpb = n_local // bk
    spans = hops * kpb
    nqb = n_q // bq

    quantized = kv_quantized is not None
    if quantized:
        if kv_quantized.block != bk:
            raise ValueError(
                f"kv feed block {kv_quantized.block} != fitted bk {bk}; "
                "pack with fitted_blocks()"
            )
        q_in, qs = _quant.quantize_rows(q)
        k_in, ks = kv_quantized.k_q, kv_quantized.k_scale
        v_in, vs = kv_quantized.v_q, kv_quantized.v_scale
    else:
        q_in, k_in, v_in = q, k_all, v_all
        qs = ks = vs = None

    segmented = q_segment_ids is not None
    masked = kv_mask is not None

    def q_map(bi, hd, qi, s, o, hi, lo, w):
        return (bi, hd, qi, 0)

    def kblock(s, o):
        return o[s // kpb] * kpb + s % kpb

    def kv_map(bi, hd, qi, s, o, hi, lo, w):
        return (bi, hd // g, kblock(s, o), 0)

    # Per-token vectors keep a trailing unit axis when the tile wants a
    # column ((..., n, 1) blocked (..., bq, 1)) and a unit second-to-last
    # axis when it wants a row ((..., 1, n) blocked (..., 1, bk)): a
    # (..., 1, block) slice of the plain (..., rows, n) layout is not a
    # legal TPU block (pallas_flash._token_vectors).
    def krow_map(bi, hd, qi, s, o, hi, lo, w):
        return (bi, 0, kblock(s, o))

    in_specs = [
        pl.BlockSpec((1, 1, bq, d), q_map),
        pl.BlockSpec((1, 1, bk, d), kv_map),
        pl.BlockSpec((1, 1, bk, d), kv_map),
    ]
    operands = [q_in, k_in, v_in]
    if quantized:
        in_specs += [
            pl.BlockSpec((1, 1, bq, 1), q_map),
            pl.BlockSpec((1, 1, 1, bk), lambda bi, hd, qi, s, o, hi, lo, w:
                         (bi, hd // g, 0, kblock(s, o))),
            pl.BlockSpec((1, 1, 1, 1, 1), lambda bi, hd, qi, s, o, hi, lo, w:
                         (bi, hd // g, kblock(s, o), 0, 0)),
        ]
        operands += [qs[..., None], ks[:, :, None, :], vs[..., None, None]]
    if masked:
        in_specs.append(pl.BlockSpec((1, 1, bk), krow_map))
        operands.append(kv_mask.astype(jnp.int32)[:, None, :])
    if segmented:
        in_specs.append(
            pl.BlockSpec((1, bq, 1), lambda bi, hd, qi, s, o, hi, lo, w:
                         (bi, qi, 0)))
        in_specs.append(pl.BlockSpec((1, 1, bk), krow_map))
        operands += [q_segment_ids.astype(jnp.int32)[:, :, None],
                     kv_segment_ids.astype(jnp.int32)[:, None, :]]

    kernel = functools.partial(
        _fused_local_kernel,
        masked=masked, segmented=segmented, quantized=quantized,
        kpb=kpb, spans=spans, scale=float(scale),
        softclamp_value=softclamp_value, bq=bq, bk=bk,
    )

    tables = [jnp.asarray(t, jnp.int32) for t in (origins, his, los, works)]
    unified = _unify_vma(*tables, *operands)
    tables, operands = unified[:4], unified[4:]
    like = operands[0]

    if interpret is None:
        interpret = _interpret_default()

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(b, h, nqb, spans),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, 1, bq, d), q_map),
            pl.BlockSpec((1, 1, bq, 1), q_map),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, d), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
        ],
    )
    _log_launch(name, n_q, n_total, h, hk, d, bq, bk, (b, h, nqb, spans))
    out, lse = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            _sds((b, h, n_q, d), q.dtype, like),
            _sds((b, h, n_q, 1), jnp.float32, like),
        ],
        compiler_params=compat.tpu_compiler_params(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
        ),
        interpret=interpret,
        name=name if not quantized else name + "_q8",
    )(*tables, *operands)
    return out, lse[..., 0]


# ---------------------------------------------------------------------------
# Remote tier: in-kernel async ICI DMA, double-buffered
# ---------------------------------------------------------------------------


def neighbor_mesh_coords(axis_name, ring_size: int):
    """``(2, naxes)`` int32 MESH coordinates of the ``[left, right]`` ring
    neighbors — per-axis indices over EVERY bound mesh axis, varying only
    along ``axis_name``.

    The remote-DMA/semaphore primitives take ``DeviceIdType.MESH``
    coordinates: the Mosaic lowering linearizes them over the WHOLE mesh
    (``coord . strides`` in mesh-axis order), so on a mesh with axes
    beyond the ring (``data``, ``dcn``, hybrid's node axis) every replica
    addresses the neighbor in its OWN replica group.  A bare ring-axis
    index with ``DeviceIdType.LOGICAL`` — the obvious spelling — is wrong
    there: logical ids span the full mesh, and every replica outside the
    first row would push its KV into a different replica group.

    Returns ``None`` when the bound axes cannot be introspected (exotic
    jax) or ``axis_name`` is not a single bound axis — callers degrade to
    the gather-based local tier.
    """
    names = compat.bound_axis_names()
    if names is None:
        return None
    try:
        if axis_name not in names:
            return None
    except TypeError:  # tuple-of-axes collectives have no single ring axis
        return None
    rank = lax.axis_index(axis_name)
    rows = []
    for nbr in ((rank - 1) % ring_size, (rank + 1) % ring_size):
        rows.append(jnp.stack([
            jnp.asarray(nbr if a == axis_name else lax.axis_index(a))
            for a in names
        ]))
    return jnp.stack(rows).astype(jnp.int32)


# ---------------------------------------------------------------------------
# The declared DMA/semaphore protocol of the remote kernel
# ---------------------------------------------------------------------------
#
# One row per copy / semaphore-handshake site group in
# ``_fused_remote_kernel``, in kernel program order.  This table IS the
# verified seam: ``analysis/schedverify.py`` extracts the actual
# primitives from the traced kernel jaxpr, cross-checks them against
# these rows (buffers, semaphores, remoteness, per-kind site counts),
# and model-checks the composed N-device schedule built from the rows —
# matched start/wait on both ends, no slot overwritten while a
# concurrent reader holds it, semaphore drain, deadlock freedom.  The
# fused contract's primitive counts (``contracts.check_fused_ring_
# contract``) are DERIVED from the ``sites`` fields, and lint RA015
# fences every remote-DMA/semaphore call site in this module to the
# functions named in ``fn`` — edit the kernel's hop schedule and the
# verifier, the contract, and the lint all move with this table or fail
# loudly.
#
# Field semantics (all values literal — the table is read by ``ast``
# from the lint, never imported there):
#   row        stable id, named in diagnostics
#   fn         innermost kernel function containing the primitive calls
#   op         copy | remote_copy | remote_drain | barrier | sem_signal
#              | sem_wait
#   src / dst  buffer names (kernel scratch/input refs); *_slot is a
#              python expression over ``hop``/``hops`` selecting the
#              kvbuf ring-buffer slot (None = not the circulated buffer)
#   sem / send_sem / recv_sem   semaphore scratch names ("barrier" is
#              the collective-id barrier from get_barrier_semaphore)
#   guard      hop-range predicate (expression over ``hop``/``hops``)
#   tile       grid position within the hop: "first" ((bhi, qi) ==
#              (0, 0)), "all" (every tile), "last" (the final tile) —
#              fixes program order inside a hop
#   to         remote target: None (local) | "left" | "right"
#   addressing "mesh" = per-axis MESH coordinates over every bound mesh
#              axis (neighbor_mesh_coords); the verifier proves this
#              resolves inside the sender's replica group on multi-axis
#              meshes, where a ring-rank LOGICAL id would not
#   inc/value  semaphore increment / wait decrement
#   sites      traced-jaxpr equation counts this row accounts for, by
#              primitive — summed into the fused contract's expected
#              counts
PROTOCOL = (
    # hop 0, first tile: local KV -> slot 0, then the seed barrier (no
    # peer pushes into an unseeded neighbor's alternate slot).
    {"row": "seed-k", "fn": "_seed", "op": "copy",
     "src": "k_src", "src_slot": None, "dst": "kvbuf", "dst_slot": "0",
     "sem": "load_sem", "guard": "hop == 0", "tile": "first", "to": None,
     "sites": {"dma_start": 1, "dma_wait": 1}},
    {"row": "seed-v", "fn": "_seed", "op": "copy",
     "src": "v_src", "src_slot": None, "dst": "kvbuf", "dst_slot": "0",
     "sem": "load_sem", "guard": "hop == 0", "tile": "first", "to": None,
     "sites": {"dma_start": 1, "dma_wait": 1}},
    {"row": "seed-barrier", "fn": "_seed", "op": "barrier",
     "sem": "barrier", "signal_to": ("left", "right"), "inc": 1,
     "value": 2, "addressing": "mesh", "guard": "hop == 0",
     "tile": "first",
     "sites": {"get_barrier_semaphore": 1, "semaphore_signal": 2,
               "semaphore_wait": 1}},
    # first tile, hop < hops-1: consume one receiver grant (the RIGHT
    # neighbor finished reading the slot this push will overwrite), then
    # start the async HBM->HBM push of the current slot into the right
    # neighbor's alternate slot.  Two dma_start sites: the static
    # cur == 0 / cur == 1 slot branches.
    {"row": "push-grant", "fn": "_flow", "op": "sem_wait",
     "sem": "grant_sem", "value": 1, "guard": "0 < hop < hops - 1",
     "tile": "first", "sites": {"semaphore_wait": 1}},
    {"row": "push-kv", "fn": "_copy", "op": "remote_copy",
     "src": "kvbuf", "src_slot": "hop % 2",
     "dst": "kvbuf", "dst_slot": "(hop + 1) % 2",
     "send_sem": "send_sem", "recv_sem": "recv_sem",
     "to": "right", "addressing": "mesh", "guard": "hop < hops - 1",
     "tile": "first", "sites": {"dma_start": 2}},
    # every tile: stage the (acc, m, l) carry in from its HBM spill,
    # stream KV blocks of the CURRENT slot through VMEM, spill the
    # carry back out.
    {"row": "carry-load-acc", "fn": "_load_state", "op": "copy",
     "src": "accb", "src_slot": None, "dst": "acc", "dst_slot": None,
     "sem": "load_sem", "guard": "hop > 0", "tile": "all", "to": None,
     "sites": {"dma_start": 1, "dma_wait": 1}},
    {"row": "carry-load-m", "fn": "_load_state", "op": "copy",
     "src": "mb", "src_slot": None, "dst": "m", "dst_slot": None,
     "sem": "load_sem", "guard": "hop > 0", "tile": "all", "to": None,
     "sites": {"dma_start": 1, "dma_wait": 1}},
    {"row": "carry-load-l", "fn": "_load_state", "op": "copy",
     "src": "lb", "src_slot": None, "dst": "l", "dst_slot": None,
     "sem": "load_sem", "guard": "hop > 0", "tile": "all", "to": None,
     "sites": {"dma_start": 1, "dma_wait": 1}},
    # 4 starts (2 prologue + 2 in-loop prefetch, k and v parts), 2 waits
    # (the loop body's per-part waits serve prologue and prefetch alike).
    {"row": "kv-stage", "fn": "kv_copies", "op": "copy",
     "src": "kvbuf", "src_slot": "hop % 2", "dst": "kvv",
     "dst_slot": None, "sem": "kv_sems", "guard": "True", "tile": "all",
     "to": None, "sites": {"dma_start": 4, "dma_wait": 2}},
    {"row": "carry-store-acc", "fn": "_store_state", "op": "copy",
     "src": "acc", "src_slot": None, "dst": "accb", "dst_slot": None,
     "sem": "load_sem", "guard": "hop < hops - 1", "tile": "all",
     "to": None, "sites": {"dma_start": 1, "dma_wait": 1}},
    {"row": "carry-store-m", "fn": "_store_state", "op": "copy",
     "src": "m", "src_slot": None, "dst": "mb", "dst_slot": None,
     "sem": "load_sem", "guard": "hop < hops - 1", "tile": "all",
     "to": None, "sites": {"dma_start": 1, "dma_wait": 1}},
    {"row": "carry-store-l", "fn": "_store_state", "op": "copy",
     "src": "l", "src_slot": None, "dst": "lb", "dst_slot": None,
     "sem": "load_sem", "guard": "hop < hops - 1", "tile": "all",
     "to": None, "sites": {"dma_start": 1, "dma_wait": 1}},
    # last tile, hop < hops-1: drain the outbound send and the inbound
    # landing (4 dma_wait sites: 2 static slot branches x send+recv),
    # then grant the LEFT neighbor's next push — it targets exactly the
    # slot this hop finished reading.  The last granted push is hop
    # hops-2 consuming the grant from hop hops-3, so signals and waits
    # balance and grant_sem drains to zero.
    {"row": "hop-drain", "fn": "_wait", "op": "remote_drain",
     "send_sem": "send_sem", "recv_sem": "recv_sem",
     "guard": "hop < hops - 1", "tile": "last",
     "sites": {"dma_wait": 4}},
    {"row": "grant", "fn": "_grant", "op": "sem_signal",
     "sem": "grant_sem", "inc": 1, "to": "left", "addressing": "mesh",
     "guard": "hop < hops - 2", "tile": "last",
     "sites": {"semaphore_signal": 1}},
)


def _fused_remote_kernel(his_ref, los_ref, works_ref, nbrs_ref, *refs,
                         quantized: bool, hops: int, naxes: int, bh: int,
                         nqb: int, n_local: int, d: int, scale: float,
                         softclamp_value: float | None, bq: int, bk: int):
    """Grid ``(hops, bh, n_q_blocks)`` — hop outermost so every tile of hop
    ``i`` computes against HBM ring-buffer slot ``i % 2`` while hop
    ``i+1``'s payload streams into the other slot.  Per hop: the FIRST
    tile starts the async HBM->HBM push of the current slot to the next
    rank's alternate slot, every tile stages ``(bq, bk)`` blocks of the
    current slot through VMEM and folds them into its ``(acc, m, l)``
    carry (itself staged per-tile through VMEM from an HBM spill buffer —
    the carry for the whole shard cannot be VMEM-resident at model
    sizes), and the LAST tile waits on the DMA pair — the overlap window
    is the whole hop's compute.

    Cross-device flow control is a receiver->sender grant: finishing hop
    ``i`` (all tiles computed, outbound send of slot ``i % 2`` drained)
    signals the LEFT neighbor's ``grant_sem``; that neighbor must consume
    one grant before its hop ``i+1`` push, which targets exactly the slot
    hop ``i`` was reading.  Without it a one-hop compute skew — guaranteed
    under causal schedules, where per-rank live-hop counts differ — would
    let the incoming DMA overwrite KV mid-read."""
    if quantized:
        q_ref, qs_ref, k_src, v_src = refs[:4]
        idx = 4
    else:
        q_ref, k_src, v_src = refs[:3]
        idx = 3
    out_ref, lse_ref = refs[idx:idx + 2]
    kvbuf, accb, mb, lb = refs[idx + 2:idx + 6]
    (kvv, acc, m, l, load_sem, kv_sems, send_sem, recv_sem,
     grant_sem) = refs[idx + 6:]

    hop = pl.program_id(0)
    bhi = pl.program_id(1)
    qi = pl.program_id(2)
    cur = lax.rem(hop, 2)

    def nbr(row):
        # MESH coords over every mesh axis — see neighbor_mesh_coords.
        return tuple(nbrs_ref[row, a] for a in range(naxes))

    @pl.when((hop == 0) & (bhi == 0) & (qi == 0))
    def _seed():
        # Local KV into slot 0 (HBM->HBM), then a neighbor barrier:
        # nobody pushes into a peer's alternate slot before that peer has
        # seeded.
        for part, src in enumerate((k_src, v_src)):
            cp = pltpu.make_async_copy(src, kvbuf.at[0, part], load_sem)
            cp.start()
            cp.wait()
        barrier = pltpu.get_barrier_semaphore()
        pltpu.semaphore_signal(barrier, inc=1, device_id=nbr(0),
                               device_id_type=pltpu.DeviceIdType.MESH)
        pltpu.semaphore_signal(barrier, inc=1, device_id=nbr(1),
                               device_id_type=pltpu.DeviceIdType.MESH)
        pltpu.semaphore_wait(barrier, 2)

    def _copy(src_slot, dst_slot):
        return pltpu.make_async_remote_copy(
            src_ref=kvbuf.at[src_slot],
            dst_ref=kvbuf.at[dst_slot],
            send_sem=send_sem,
            recv_sem=recv_sem,
            device_id=nbr(1),
            device_id_type=pltpu.DeviceIdType.MESH,
        )

    @pl.when((bhi == 0) & (qi == 0) & (hop < hops - 1))
    def _push():
        # Flow control: the hop-i push writes the neighbor's slot
        # (i+1) % 2 — the slot it reads during its hop i-1.  One grant ==
        # "I finished hop i-1"; hop 0's target slot has never been read,
        # so only the seed barrier gates it.
        @pl.when(hop > 0)
        def _flow():
            pltpu.semaphore_wait(grant_sem, 1)

        # Static slot branches: the DMA descriptor's refs must be static.
        @pl.when(cur == 0)
        def _():
            _copy(0, 1).start()

        @pl.when(cur == 1)
        def _():
            _copy(1, 0).start()

    row0 = qi * bq
    live = (
        (works_ref[hop] != 0)
        & (0 <= row0 + bq - 1 + his_ref[hop])
        & (n_local - 1 >= row0 + los_ref[hop])
    )
    state = ((accb, acc), (mb, m), (lb, l))

    @pl.when(hop == 0)
    def _init():
        acc[:] = jnp.zeros_like(acc)
        m[:] = jnp.full_like(m, MASK_VALUE)
        l[:] = jnp.zeros_like(l)

    @pl.when((hop > 0) & (live | (hop == hops - 1)))
    def _load_state():
        cps = [
            pltpu.make_async_copy(
                hb.at[bhi, pl.dslice(row0, bq)], vref, load_sem)
            for hb, vref in state
        ]
        for cp in cps:
            cp.start()
        for cp in cps:
            cp.wait()

    @pl.when(live)
    def _compute():
        q = q_ref[0]
        hi, lo = his_ref[hop], los_ref[hop]
        # Only the KV blocks the band touches: rows [row0, row0+bq) keep
        # cols j with lo <= j - i <= hi, clamped to the shard.  `live`
        # guarantees a non-empty range.
        kb_lo = jnp.maximum(row0 + lo, 0) // bk
        kb_hi = jnp.minimum(row0 + bq - 1 + hi, n_local - 1) // bk

        def kv_copies(kb, buf):
            # Double-buffered HBM->VMEM staging of one (bk, dd) K and V
            # block of the CURRENT slot; per-buffer DMA semaphore.
            return [
                pltpu.make_async_copy(
                    kvbuf.at[cur, part, bhi, pl.dslice(kb * bk, bk)],
                    kvv.at[buf, part],
                    kv_sems.at[buf],
                )
                for part in (0, 1)
            ]

        for cp in kv_copies(kb_lo, 0):
            cp.start()

        def body(i, carry):
            kb = kb_lo + i
            buf = lax.rem(i, 2)

            @pl.when(kb < kb_hi)
            def _prefetch():
                for cp in kv_copies(kb + 1, 1 - buf):
                    cp.start()

            for cp in kv_copies(kb, buf):
                cp.wait()

            kblk = kvv[buf, 0]
            vblk = kvv[buf, 1]
            k = kblk[:, :d] if quantized else kblk
            nt = (((1,), (1,)), ((), ()))
            if quantized:
                ks = lax.bitcast_convert_type(
                    kblk[:, d:d + _quant.SCALE_BYTES], jnp.float32)
                s = _int8_dot(q, k, nt) * (
                    (qs_ref[0] * scale) * ks[None, :])
            else:
                s = lax.dot_general(q, k, nt,
                                    preferred_element_type=jnp.float32)
                if scale != 1.0:
                    s = s * scale
            if softclamp_value is not None:
                s = jnp.tanh(s / softclamp_value) * softclamp_value
            rows = lax.broadcasted_iota(jnp.int32, (bq, bk), 0) + row0
            cols = lax.broadcasted_iota(jnp.int32, (bq, bk), 1) + kb * bk
            diff = cols - rows
            keep = (diff <= hi) & (diff >= lo)
            s = jnp.where(keep, s, MASK_VALUE)
            if quantized:
                # pack_kv(v_block=n_local) broadcast the whole-block v
                # scale to every row — row 0 of any slice recovers it.
                vs = lax.bitcast_convert_type(
                    vblk[0, d:d + _quant.SCALE_BYTES], jnp.float32)
                _online_update(s, vblk[:, :d], acc, m, l, v_scale=vs)
            else:
                _online_update(s, vblk, acc, m, l)
            return carry

        lax.fori_loop(0, kb_hi - kb_lo + 1, body, 0)

    @pl.when((hop < hops - 1) & (live | (hop == 0)))
    def _store_state():
        cps = [
            pltpu.make_async_copy(
                vref, hb.at[bhi, pl.dslice(row0, bq)], load_sem)
            for hb, vref in state
        ]
        for cp in cps:
            cp.start()
        for cp in cps:
            cp.wait()

    @pl.when((bhi == bh - 1) & (qi == nqb - 1) & (hop < hops - 1))
    def _wait():
        @pl.when(cur == 0)
        def _():
            _copy(0, 1).wait()

        @pl.when(cur == 1)
        def _():
            _copy(1, 0).wait()

        # Slot `cur` is now dead here (every tile computed, outbound send
        # drained just above): grant the LEFT neighbor's next push — it
        # targets exactly this slot.  The last granted push is hop
        # hops-2, consuming the grant from hop hops-3: signals and waits
        # balance, the semaphore drains to zero.
        @pl.when(hop < hops - 2)
        def _grant():
            pltpu.semaphore_signal(grant_sem, inc=1, device_id=nbr(0),
                                   device_id_type=pltpu.DeviceIdType.MESH)

    @pl.when(hop == hops - 1)
    def _write():
        l_safe = jnp.maximum(l[:], EPSILON)
        out_ref[0] = (acc[:] / l_safe).astype(out_ref.dtype)
        lse_ref[0] = m[:] + jnp.log(l_safe)


def fused_ring_remote(
    q, k, v, *,
    his, los, works, nbr_coords,
    scale=1.0, softclamp_value=None, block_q=None, block_k=None,
    payload=None, collective_id=COLLECTIVE_ID,
    name="fused_ring_remote",
):
    """Fused-ring forward with in-kernel async remote KV circulation.

    Call inside ``shard_map``: ``q`` ``(b, h, n_local, d)``, ``k``/``v``
    ``(b, hk, n_local, d)`` are this rank's shards; ``nbr_coords`` is the
    int32 ``(2, naxes)`` MESH-coordinate pair of the ``[rank-1, rank+1]``
    ring neighbors over EVERY mesh axis (:func:`neighbor_mesh_coords` —
    physical adjacency holds because ``torus_ring_order`` fed mesh
    construction).  KV is sent to ``rank+1`` each hop, so hop ``i`` holds
    origin ``(rank - i) % W`` — the same visit order as the scan path,
    which is what makes ``his``/``los``/``works`` (from
    ``_fused_tables``) directly reusable.

    The circulated double buffer and the cross-hop ``(acc, m, l)`` carry
    live in HBM (``ANY``-space buffers the caller discards); compute
    stages ``(bq, bk)`` blocks and per-tile carries through small VMEM
    scratch, so VMEM footprint is tile-sized and independent of
    ``n_local`` — whole-shard VMEM residency does not compile at model
    sizes (32k-token shards are hundreds of MB against ~16 MB of VMEM).

    ``payload`` selects the int8 wire: a ``quant.pack_kv(k, v,
    v_block=n_local)`` buffer ``(2, b, hk, n_local, d + SCALE_BYTES)``
    circulates INSTEAD of k/v, dequant scales riding its trailing lanes;
    q is quantized per-row here.  GQA is materialized (kv heads repeated
    to h) before folding to ``(b*h, n, d)`` — the remote tier trades that
    copy for whole-hop DMA granularity; masked/segmented configs take the
    local tier instead.

    TPU-execute only; traces on any backend (the contract row counts the
    lowered ``dma_start``/semaphore ops from exactly this trace).
    """
    check_attention_args("fused_ring_remote", q, k, v, None,
                         equal_qkv_len=True)
    b, h, n_q, d = q.shape
    hk = k.shape[1]
    g = h // hk
    n_local = n_q
    hops = int(his.shape[0])
    naxes = int(nbr_coords.shape[-1])
    quantized = payload is not None
    if jax.default_backend() == "tpu":
        # Mosaic (TPU v5 lite, libtpu 0.0.34, 2026-09-26) refuses the
        # kernel's hand-written HBM slices: every `.at[...]` window of an
        # ANY-space buffer must be aligned to the (8, 128) tiling, and
        # neither the d=64 rows of the circulated KV buffer ("Slice shape
        # along dimension 4 must be aligned to tiling (128), but is 64")
        # nor the (bq, 1) windows of the m/l spill at any head width
        # ("... dimension 2 ... but is 1") are.  Tracing still works on
        # other backends (the contract and protocol checks count its
        # DMA/semaphore primitives from exactly this trace).
        raise NotImplementedError(
            "fused_ring_remote: refused by Mosaic on TPU — HBM slices of "
            "its ring buffer and m/l spill are not aligned to the "
            "128-lane tiling (ROADMAP S4/D2); the gather-fed "
            "fused_ring_local and impl=\"pallas\" run"
        )

    bq, bk = _block_sizes(n_local, n_local, block_q, block_k)
    nqb = n_local // bq
    bh = b * h

    def fold(x):
        if x.shape[1] != h:
            x = jnp.repeat(x, g, axis=1)
        return x.reshape(bh, *x.shape[2:])

    q_f = fold(q)
    hbm = pl.BlockSpec(memory_space=pltpu.ANY)
    if quantized:
        q8, qs = _quant.quantize_rows(q_f)
        operands = [q8, qs[..., None], fold(payload[0]), fold(payload[1])]
        dd = d + _quant.SCALE_BYTES
        kv_dtype = jnp.int8
        in_specs = [
            pl.BlockSpec((1, bq, d), lambda hop, bhi, qi, hi, lo, w, nb:
                         (bhi, qi, 0)),
            pl.BlockSpec((1, bq, 1), lambda hop, bhi, qi, hi, lo, w, nb:
                         (bhi, qi, 0)),
            hbm,
            hbm,
        ]
    else:
        operands = [q_f, fold(k), fold(v)]
        dd = d
        kv_dtype = k.dtype
        in_specs = [
            pl.BlockSpec((1, bq, d), lambda hop, bhi, qi, hi, lo, w, nb:
                         (bhi, qi, 0)),
            hbm,
            hbm,
        ]

    kernel = functools.partial(
        _fused_remote_kernel,
        quantized=quantized, hops=hops, naxes=naxes, bh=bh, nqb=nqb,
        n_local=n_local, d=d, scale=float(scale),
        softclamp_value=softclamp_value, bq=bq, bk=bk,
    )
    tables = [jnp.asarray(t, jnp.int32)
              for t in (his, los, works, nbr_coords)]
    unified = _unify_vma(*tables, *operands)
    tables, operands = unified[:4], unified[4:]
    like = operands[0]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(hops, bh, nqb),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, bq, d), lambda hop, bhi, qi, hi, lo, w, nb:
                         (bhi, qi, 0)),
            pl.BlockSpec((1, bq, 1), lambda hop, bhi, qi, hi, lo, w, nb:
                         (bhi, qi, 0)),
            # HBM working buffers, returned-and-dropped: the circulated
            # double buffer and the cross-hop (acc, m, l) spill.
            hbm,
            hbm,
            hbm,
            hbm,
        ],
        scratch_shapes=[
            pltpu.VMEM((2, 2, bk, dd), kv_dtype),
            pltpu.VMEM((bq, d), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.SemaphoreType.DMA,
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA,
            pltpu.SemaphoreType.DMA,
            pltpu.SemaphoreType.REGULAR,
        ],
    )
    _log_launch(name, n_local, n_local * hops, h, hk, d, bq, bk,
                (hops, bh, nqb))
    out_f, lse_f, *_hbm_work = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            _sds((bh, n_local, d), q.dtype, like),
            _sds((bh, n_local, 1), jnp.float32, like),
            _sds((2, 2, bh, n_local, dd), kv_dtype, like),
            _sds((bh, n_local, d), jnp.float32, like),
            _sds((bh, n_local, 1), jnp.float32, like),
            _sds((bh, n_local, 1), jnp.float32, like),
        ],
        compiler_params=compat.tpu_compiler_params(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
            collective_id=collective_id,
        ),
        interpret=False,
        name=name if not quantized else name + "_q8",
    )(*tables, *operands)
    out = out_f.reshape(b, h, n_local, d)
    lse = lse_f.reshape(b, h, n_local)
    return out, lse
