"""Certified block-sparse mask algebra.

Splash-attention (SNIPPETS.md [3]) showed that long-context attention
workloads are really a composable *mask algebra* — causal bands, local
windows, prefix-LM bidirectionality, per-head mixtures, document
packings — and that the win is resolving each mask into block-sparse
kernel work at trace time.  This module is that algebra for this repo,
built around the two seams the earlier PRs proved out:

  - every mask carries an **oracle**: an exact predicate over *global*
    ``(q_pos, k_pos, head)`` coordinates (``Mask.oracle``) — the ground
    truth the certifier holds every lowering to;
  - every mask carries a **lowering**: compact ``BandPlan``-style tile
    tables plus per-hop work/skip schedules for each execution geometry
    (``lower`` over a :class:`GridSpec` — single sweep, ring hops in
    contiguous or striped layout, TokenRing counter-rotation; q-major
    AND k-major tables for the backward passes).  Band-shaped masks
    lower through the REAL seams — ``ops.pallas_flash.band_plan`` and
    the hop-band helpers of ``parallel/ring.py`` — so certifying them
    certifies the shipping kernels' grids; other masks lower through
    the generic tile classifier here (closed forms per leaf, exact
    refinement at combinators), the extension seam for future kernels.

The certifying-compiler contract: a lowering is only *admitted* with a
machine-checked certificate (``certify`` -> ``analysis/coverage.py``'s
prover) that it is **sound** (no live tile skipped, edge masks
elementwise-equal to the oracle), **tight** (no dead tile visited,
closed-form tile count == enumeration), and **complete** (each element
enters the online softmax exactly once across hops).  Certificates are
computed at trace time on first use and cached by
``(mask, shape, blocks, strategy, layout)`` — in memory and optionally
on disk next to the compile cache — so the proof is paid once; an
uncertifiable lowering raises :class:`MaskCertificationError` with a
one-line diagnostic naming the mask, hop, and tile.

Execution wiring: masks whose canonical form the kernels already speak
(``Causal``, causal sliding windows, document packings, runtime
segments) map onto the existing knobs via :func:`kernel_form` and run
the proven fast paths (``ops.attention(mask=...)``,
``RingAttention(mask=...)``, ``causal=True`` is sugar for ``Causal()``).
Masks beyond the kernel surface (prefix-LM, dilated, per-head, ``Or``/
``Not`` compositions) still certify and lower to grids — the
:class:`MaskLoweringError` they raise at execution names exactly what
the kernels support today.

Elementwise certificates are enumerated up to ``CERT_ELEMENTWISE_MAX``
total positions per side; larger calls are proven on the leading
``CERT_ELEMENTWISE_MAX`` positions plus the closed-form-vs-enumeration
tile accounting at the full shape (CPU-countable even at 262144,
``tests/test_masks.py``'s ``window_262k`` test).  Pure numpy at
module level; jax/kernel imports stay inside functions.

See ``docs/masks.md`` for the lowering table per strategy and the
certification semantics.
"""

from __future__ import annotations

import hashlib
import json
import os
import re
from bisect import bisect_right
from dataclasses import dataclass, field

import numpy as np

__all__ = [
    "Mask", "Full", "Causal", "SlidingWindow", "Dilated", "Striped",
    "PrefixLM", "DocumentMask", "Segments", "PerHead",
    "And", "Or", "Not",
    "GridSpec", "KernelForm", "Certificate",
    "MaskLoweringError", "MaskCertificationError", "MaskParseError",
    "band_form", "kernel_form", "lower", "certify", "require_certified",
    "parse_mask", "MASK_REGISTRY", "dense_mask",
]

# Above this many positions per side, certify() proves the elementwise
# half on the leading CERT_ELEMENTWISE_MAX positions and the tile
# accounting at the full shape (an O(n^2) oracle at 262k is 6.9e10
# elements — not a trace-time cost anyone should pay).
CERT_ELEMENTWISE_MAX = 2048


class MaskLoweringError(ValueError):
    """The mask has no lowering for the requested target (named in the
    message, along with the forms the target supports)."""


class MaskCertificationError(ValueError):
    """A lowering failed its soundness/tightness/completeness proof.
    The message is the first violation line: mask, hop, tile."""


class MaskParseError(ValueError):
    """A textual mask expression did not parse; lists the registry."""


# ---------------------------------------------------------------------------
# The algebra
# ---------------------------------------------------------------------------


class Mask:
    """Base class: combinators plus the oracle/lowering contract.

    Subclasses are frozen dataclasses (hashable — they key the
    certificate cache and sit as static flax module attributes).
    """

    def __and__(self, other: "Mask") -> "Mask":
        return And((self, other))

    def __or__(self, other: "Mask") -> "Mask":
        return Or((self, other))

    def __invert__(self) -> "Mask":
        return Not(self)

    # -- oracle ---------------------------------------------------------
    def oracle(self, qpos, kpos, head: int = 0, doc_ids=None) -> np.ndarray:
        """Exact ``(len(qpos), len(kpos))`` bool truth over GLOBAL token
        positions — the independent ground truth every lowering is
        certified against."""
        raise NotImplementedError

    # -- exact tile classification (the generic lowering's closed forms) -
    def tile_status(self, qlo: int, qhi: int, klo: int, khi: int,
                    head: int = 0) -> tuple[bool, bool]:
        """Exact ``(any_live, all_live)`` of the tile spanning global
        rows ``[qlo, qhi]`` x cols ``[klo, khi]`` (inclusive,
        contiguous).  Leaves use closed forms; combinators combine them
        and refine the genuinely ambiguous cases elementwise."""
        raise NotImplementedError

    @property
    def key(self) -> str:
        """Canonical textual form — the certificate-cache key half and
        the diagnostic name; round-trips through :func:`parse_mask` for
        every parseable form."""
        raise NotImplementedError

    @property
    def per_head(self) -> bool:
        return False

    @property
    def head_period(self) -> int:
        """Number of distinct head variants (1 for head-independent
        masks; combinators take the lcm of their children) — what a
        certificate must enumerate."""
        return 1

    def head_mask(self, head: int) -> "Mask":
        """The mask head ``head`` actually attends under (identity for
        head-independent masks)."""
        return self

    def __repr__(self) -> str:
        return f"{type(self).__name__}<{self.key}>"


def _lcm_all(values) -> int:
    import math

    out = 1
    for v in values:
        out = out * v // math.gcd(out, v)
    return out


def static_mask(mask: "Mask") -> "Mask":
    """The trace-time part of a mask: :class:`Segments` leaves (runtime
    per-token ids, masked in-kernel) drop out of conjunctions — the
    grids a lowering emits are those of the remaining static terms,
    exactly like the misaligned-document fallback.  A ``Segments``
    under ``Or``/``Not`` has no sound static grid and stays (its oracle
    raises with the DocumentMask pointer)."""
    if isinstance(mask, Segments):
        return Full()
    if isinstance(mask, And):
        kept = tuple(static_mask(m) for m in mask.operands
                     if not isinstance(m, Segments))
        if not kept:
            return Full()
        return kept[0] if len(kept) == 1 else And(kept)
    if isinstance(mask, PerHead):
        return PerHead(tuple(static_mask(m) for m in mask.masks))
    return mask


def _tile_eval(mask: Mask, qlo, qhi, klo, khi, head) -> tuple[bool, bool]:
    """Elementwise refinement for combinator tiles the tri-state rules
    cannot decide (exact, O(tile))."""
    m = mask.oracle(np.arange(qlo, qhi + 1), np.arange(klo, khi + 1), head)
    return bool(m.any()), bool(m.all())


@dataclass(frozen=True)
class Full(Mask):
    """Every query attends every key."""

    def oracle(self, qpos, kpos, head=0, doc_ids=None):
        return np.ones((len(qpos), len(kpos)), bool)

    def tile_status(self, qlo, qhi, klo, khi, head=0):
        return True, True

    @property
    def key(self):
        return "full"


@dataclass(frozen=True)
class Causal(Mask):
    """Attend iff ``k_pos <= q_pos``."""

    def oracle(self, qpos, kpos, head=0, doc_ids=None):
        return np.asarray(kpos)[None, :] <= np.asarray(qpos)[:, None]

    def tile_status(self, qlo, qhi, klo, khi, head=0):
        return klo <= qhi, khi <= qlo

    @property
    def key(self):
        return "causal"


@dataclass(frozen=True)
class SlidingWindow(Mask):
    """Attend iff ``|q_pos - k_pos| < window`` (two-sided local band).

    Compose with :class:`Causal` for the usual causal sliding window —
    ``Causal() & SlidingWindow(w)`` keeps exactly the last ``w`` keys,
    matching the kernels' ``window=`` contract — or use standalone for
    bidirectional local attention."""

    window: int

    def __post_init__(self):
        if int(self.window) < 1:
            raise ValueError(f"SlidingWindow needs window >= 1, "
                             f"got {self.window}")

    def oracle(self, qpos, kpos, head=0, doc_ids=None):
        d = np.asarray(kpos)[None, :] - np.asarray(qpos)[:, None]
        return np.abs(d) < int(self.window)

    def tile_status(self, qlo, qhi, klo, khi, head=0):
        w = int(self.window)
        # diff d = k - q ranges over [klo - qhi, khi - qlo]
        any_live = klo - qhi < w and khi - qlo > -w
        all_live = klo - qhi > -w and khi - qlo < w
        return any_live, all_live

    @property
    def key(self):
        return f"window:{int(self.window)}"


@dataclass(frozen=True)
class Dilated(Mask):
    """Attend iff ``(q_pos - k_pos) % stride == offset`` — the dilated /
    strided sparse pattern (LongNet-style; the stripe/zigzag schedules of
    Striped Attention, arXiv 2311.09431, are the ``stride = ring``
    member of this family)."""

    stride: int
    offset: int = 0

    def __post_init__(self):
        if int(self.stride) < 1:
            raise ValueError(f"Dilated needs stride >= 1, got {self.stride}")
        if not 0 <= int(self.offset) < int(self.stride):
            raise ValueError(
                f"Dilated offset must be in [0, stride), got {self.offset}"
            )

    def oracle(self, qpos, kpos, head=0, doc_ids=None):
        d = np.asarray(qpos)[:, None] - np.asarray(kpos)[None, :]
        return d % int(self.stride) == int(self.offset)

    def tile_status(self, qlo, qhi, klo, khi, head=0):
        s, o = int(self.stride), int(self.offset)
        d_lo, d_hi = qlo - khi, qhi - klo  # d = q - k range
        # any: an integer d in [d_lo, d_hi] with d ≡ o (mod s)
        any_live = (d_hi - o) // s >= -((o - d_lo) // s)
        all_live = s == 1 or (d_lo == d_hi and (d_lo - o) % s == 0)
        return any_live, all_live

    @property
    def key(self):
        o = int(self.offset)
        return f"dilated:{int(self.stride)}" + (f"+{o}" if o else "")


# the issue's Dilated/Striped(stride) are one pattern; keep both names
Striped = Dilated


@dataclass(frozen=True)
class PrefixLM(Mask):
    """Attend iff ``k_pos < prefix_len`` or ``k_pos <= q_pos`` —
    bidirectional over the prompt prefix, causal after (T5/PaLM-style
    prefix language modeling)."""

    prefix_len: int

    def __post_init__(self):
        if int(self.prefix_len) < 0:
            raise ValueError(
                f"PrefixLM needs prefix_len >= 0, got {self.prefix_len}"
            )

    def oracle(self, qpos, kpos, head=0, doc_ids=None):
        k = np.asarray(kpos)[None, :]
        return (k < int(self.prefix_len)) | (k <= np.asarray(qpos)[:, None])

    def tile_status(self, qlo, qhi, klo, khi, head=0):
        p = int(self.prefix_len)
        return (klo < p or klo <= qhi), (khi < p or khi <= qlo)

    @property
    def key(self):
        return f"prefix:{int(self.prefix_len)}"


@dataclass(frozen=True)
class DocumentMask(Mask):
    """Attend iff ``q_pos`` and ``k_pos`` lie in the same document of a
    DECLARED packing layout: ``doc_starts`` are sorted unique global
    start offsets beginning at 0 (the trace-time twin of runtime
    :class:`Segments`; block-aligned layouts compile the document mask
    into the tile tables, misaligned ones fall back to in-kernel
    runtime ids — see docs/masks.md)."""

    doc_starts: tuple[int, ...]

    def __post_init__(self):
        ds = tuple(int(s) for s in self.doc_starts)
        if not ds or ds[0] != 0 or list(ds) != sorted(set(ds)):
            raise ValueError(
                f"DocumentMask doc_starts must be sorted unique offsets "
                f"starting at 0, got {self.doc_starts!r}"
            )
        object.__setattr__(self, "doc_starts", ds)

    def _doc_of_scalar(self, pos: int) -> int:
        return bisect_right(self.doc_starts, pos) - 1

    def _doc_of(self, pos) -> np.ndarray:
        return np.searchsorted(
            np.asarray(self.doc_starts), np.asarray(pos), side="right"
        ) - 1

    def oracle(self, qpos, kpos, head=0, doc_ids=None):
        return self._doc_of(qpos)[:, None] == self._doc_of(kpos)[None, :]

    def tile_status(self, qlo, qhi, klo, khi, head=0):
        dq_lo, dq_hi = self._doc_of_scalar(qlo), self._doc_of_scalar(qhi)
        dk_lo, dk_hi = self._doc_of_scalar(klo), self._doc_of_scalar(khi)
        any_live = dq_lo <= dk_hi and dk_lo <= dq_hi
        all_live = dq_lo == dq_hi == dk_lo == dk_hi
        return any_live, all_live

    @property
    def key(self):
        return "docs:" + ",".join(str(s) for s in self.doc_starts)


@dataclass(frozen=True)
class Segments(Mask):
    """Runtime packed-sequence masking: attend iff the per-token segment
    ids (a RUNTIME array, supplied at call time) match.  Has no static
    oracle — certification rows use :class:`DocumentMask`, the declared
    trace-time layout; :func:`kernel_form` maps this leaf onto the
    ``segment_ids`` execution path."""

    def oracle(self, qpos, kpos, head=0, doc_ids=None):
        if doc_ids is None:
            raise MaskLoweringError(
                "Segments is a runtime mask (per-token ids supplied at "
                "call time); a static oracle needs doc_ids — declare the "
                "layout with DocumentMask to certify it"
            )
        ids = np.asarray(doc_ids)
        return ids[np.asarray(qpos)][:, None] == ids[np.asarray(kpos)][None, :]

    def tile_status(self, qlo, qhi, klo, khi, head=0):
        raise MaskLoweringError(
            "Segments has no trace-time tile classification (runtime "
            "ids); use DocumentMask for a declared layout"
        )

    @property
    def key(self):
        return "segments"


@dataclass(frozen=True)
class PerHead(Mask):
    """Per-head mask selection: head ``h`` attends under
    ``masks[h % len(masks)]`` (splash-attention's ``MultiHeadMask``)."""

    masks: tuple[Mask, ...]

    def __post_init__(self):
        ms = tuple(self.masks)
        if not ms or not all(isinstance(m, Mask) for m in ms):
            raise ValueError("PerHead needs a non-empty tuple of masks")
        if any(m.per_head for m in ms):
            raise ValueError("PerHead masks cannot nest PerHead")
        object.__setattr__(self, "masks", ms)

    def oracle(self, qpos, kpos, head=0, doc_ids=None):
        return self.head_mask(head).oracle(qpos, kpos, head, doc_ids)

    def tile_status(self, qlo, qhi, klo, khi, head=0):
        return self.head_mask(head).tile_status(qlo, qhi, klo, khi, head)

    @property
    def per_head(self):
        return True

    @property
    def head_period(self):
        return len(self.masks)

    def head_mask(self, head: int) -> Mask:
        return self.masks[head % len(self.masks)]

    @property
    def key(self):
        return "perhead(" + ";".join(m.key for m in self.masks) + ")"


@dataclass(frozen=True)
class And(Mask):
    """Intersection of the operand masks."""

    operands: tuple[Mask, ...]

    def __post_init__(self):
        flat: list[Mask] = []
        for m in self.operands:
            flat.extend(m.operands if isinstance(m, And) else (m,))
        object.__setattr__(self, "operands", tuple(flat))

    def oracle(self, qpos, kpos, head=0, doc_ids=None):
        out = self.operands[0].oracle(qpos, kpos, head, doc_ids)
        for m in self.operands[1:]:
            out = out & m.oracle(qpos, kpos, head, doc_ids)
        return out

    def tile_status(self, qlo, qhi, klo, khi, head=0):
        stats = [m.tile_status(qlo, qhi, klo, khi, head)
                 for m in self.operands]
        if not all(any_live for any_live, _ in stats):
            return False, False
        if all(all_live for _, all_live in stats):
            return True, True
        # children each touch the tile but none fills it alone — the
        # intersection may still be empty; decide exactly
        return _tile_eval(self, qlo, qhi, klo, khi, head)

    @property
    def per_head(self):
        return any(m.per_head for m in self.operands)

    @property
    def head_period(self):
        return _lcm_all(m.head_period for m in self.operands)

    def head_mask(self, head):
        return And(tuple(m.head_mask(head) for m in self.operands))

    @property
    def key(self):
        return "(" + "&".join(m.key for m in self.operands) + ")"


@dataclass(frozen=True)
class Or(Mask):
    """Union of the operand masks."""

    operands: tuple[Mask, ...]

    def __post_init__(self):
        flat: list[Mask] = []
        for m in self.operands:
            flat.extend(m.operands if isinstance(m, Or) else (m,))
        object.__setattr__(self, "operands", tuple(flat))

    def oracle(self, qpos, kpos, head=0, doc_ids=None):
        out = self.operands[0].oracle(qpos, kpos, head, doc_ids)
        for m in self.operands[1:]:
            out = out | m.oracle(qpos, kpos, head, doc_ids)
        return out

    def tile_status(self, qlo, qhi, klo, khi, head=0):
        stats = [m.tile_status(qlo, qhi, klo, khi, head)
                 for m in self.operands]
        if any(all_live for _, all_live in stats):
            return True, True
        if not any(any_live for any_live, _ in stats):
            return False, False
        any_live = True  # some child touches the tile
        # full only if the union covers it — decide exactly
        _, all_live = _tile_eval(self, qlo, qhi, klo, khi, head)
        return any_live, all_live

    @property
    def per_head(self):
        return any(m.per_head for m in self.operands)

    @property
    def head_period(self):
        return _lcm_all(m.head_period for m in self.operands)

    def head_mask(self, head):
        return Or(tuple(m.head_mask(head) for m in self.operands))

    @property
    def key(self):
        return "(" + "|".join(m.key for m in self.operands) + ")"


@dataclass(frozen=True)
class Not(Mask):
    """Complement of the operand mask."""

    operand: Mask

    def oracle(self, qpos, kpos, head=0, doc_ids=None):
        return ~self.operand.oracle(qpos, kpos, head, doc_ids)

    def tile_status(self, qlo, qhi, klo, khi, head=0):
        any_live, all_live = self.operand.tile_status(
            qlo, qhi, klo, khi, head
        )
        return not all_live, not any_live

    @property
    def per_head(self):
        return self.operand.per_head

    @property
    def head_period(self):
        return self.operand.head_period

    def head_mask(self, head):
        return Not(self.operand.head_mask(head))

    @property
    def key(self):
        return "~" + self.operand.key


# ---------------------------------------------------------------------------
# Canonical band / kernel forms (the execution mapping)
# ---------------------------------------------------------------------------


def band_form(mask: Mask) -> tuple[int | None, int | None] | None:
    """``(hi, lo)`` of a pure band mask — attend iff
    ``lo <= k_pos - q_pos <= hi`` with ``None`` meaning unbounded — or
    ``None`` when the mask is not a band.  This is the repo's unified
    banded-offset contract (``ops/flash.py``), in global coordinates."""
    if isinstance(mask, Full):
        return (None, None)
    if isinstance(mask, Causal):
        return (0, None)
    if isinstance(mask, SlidingWindow):
        w = int(mask.window)
        return (w - 1, -(w - 1))
    if isinstance(mask, And):
        hi: int | None = None
        lo: int | None = None
        for m in mask.operands:
            b = band_form(m)
            if b is None:
                return None
            mhi, mlo = b
            hi = mhi if hi is None else (hi if mhi is None else min(hi, mhi))
            lo = mlo if lo is None else (lo if mlo is None else max(lo, mlo))
        return (hi, lo)
    return None


@dataclass(frozen=True)
class KernelForm:
    """A mask resolved onto the knobs the shipping kernels speak:
    ``causal``/``window`` (the banded-offset contract), a declared
    ``doc_starts`` packing, and/or runtime ``segment_ids``."""

    causal: bool = False
    window: int | None = None
    doc_starts: tuple[int, ...] | None = None
    needs_segment_ids: bool = False


_KERNEL_FORMS = (
    "Full() / None", "Causal()", "Causal() & SlidingWindow(w)",
    "... & DocumentMask(starts)", "... & Segments()",
)


def kernel_form(mask: Mask) -> KernelForm:
    """Map a mask onto the existing kernel knobs, or raise
    :class:`MaskLoweringError` naming the supported forms.

    Masks that fail here still certify and lower to grids (the
    extension seam for future kernels); they just have no fast
    execution path yet."""
    terms = mask.operands if isinstance(mask, And) else (mask,)
    docs: list[DocumentMask] = []
    segments = False
    band_terms: list[Mask] = []
    for t in terms:
        if isinstance(t, DocumentMask):
            docs.append(t)
        elif isinstance(t, Segments):
            segments = True
        else:
            band_terms.append(t)
    if len(docs) > 1:
        raise MaskLoweringError(
            f"mask {mask.key!r}: at most one DocumentMask per "
            f"conjunction (merge the layouts first)"
        )
    band = band_form(And(tuple(band_terms)) if len(band_terms) > 1
                     else (band_terms[0] if band_terms else Full()))
    if band is None:
        raise MaskLoweringError(
            f"mask {mask.key!r} has no kernel lowering yet — the kernels "
            f"speak {', '.join(_KERNEL_FORMS)}; it still certifies and "
            f"lowers to grids (analysis/coverage.py)"
        )
    hi, lo = band
    if hi is None and lo is None:
        causal, window = False, None
    elif hi == 0 and lo is None:
        causal, window = True, None
    elif hi == 0 and lo is not None and lo <= 0:
        causal, window = True, 1 - lo
    else:
        raise MaskLoweringError(
            f"mask {mask.key!r} lowers to the band [{lo}, {hi}] which the "
            f"kernel entry points do not expose (they speak "
            f"{', '.join(_KERNEL_FORMS)}); it still certifies and lowers "
            f"to grids"
        )
    return KernelForm(
        causal=causal, window=window,
        doc_starts=docs[0].doc_starts if docs else None,
        needs_segment_ids=segments,
    )


# ---------------------------------------------------------------------------
# Lowering: mask -> tile grids + hop schedules per execution geometry
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class GridSpec:
    """One execution geometry a mask lowers onto (the cache key's
    geometry half).

    ``strategy``: ``"single"`` (one sweep), ``"ring"`` (KV rotation —
    also the hybrid outer ring, whose ring leg is this schedule at the
    outer ring size), or ``"counter"`` (TokenRing counter-rotation).
    ``layout``: ``"contiguous"`` or ``"striped"`` token placement.
    """

    strategy: str = "single"
    layout: str = "contiguous"
    ring: int = 1
    n_local: int = 64
    block_q: int = 8
    block_k: int = 8
    passes: int | None = None
    head: int = 0

    def __post_init__(self):
        if self.strategy not in ("single", "ring", "counter"):
            raise ValueError(
                f"GridSpec strategy {self.strategy!r}: known strategies "
                f"are single, ring, counter (hybrid = ring at the outer "
                f"ring size; zigzag stays causal-only, see docs/masks.md)"
            )
        if self.layout not in ("contiguous", "striped"):
            raise ValueError(f"GridSpec layout {self.layout!r}")
        if self.strategy == "single" and self.ring != 1:
            raise ValueError("single-sweep specs have ring == 1")
        if self.n_local % self.block_q or self.n_local % self.block_k:
            raise ValueError(
                f"blocks ({self.block_q}, {self.block_k}) must divide "
                f"n_local {self.n_local}"
            )

    @property
    def n_total(self) -> int:
        return self.ring * self.n_local

    @property
    def n_passes(self) -> int:
        return min(self.passes or self.ring, self.ring)


def positions(layout: str, origin: int, n_local: int, ring: int) -> np.ndarray:
    """Global token positions of rank/origin ``origin``'s local shard."""
    i = np.arange(n_local)
    if layout == "striped":
        return i * ring + origin
    if layout == "contiguous":
        return origin * n_local + i
    raise ValueError(f"unknown layout {layout!r}")


@dataclass
class RankPlan:
    """One rank's runtime decisions at one hop — what the compiled
    program would do, recorded for the certifier to hold to the oracle."""

    rank: int
    q_origin: int
    kv_origin: int
    has_work: bool
    hi: int | None = None  # runtime band scalars (band lowerings)
    lo: int | None = None
    rt_mask: np.ndarray | None = None  # generic runtime edge mask


@dataclass
class LoweredHop:
    """One hop of a lowering: the shared tile tables (q-major and
    k-major) plus every rank's runtime schedule decisions."""

    hop: int
    full: bool  # trace-time full-span elision (no mask, no tables)
    plan: object | None  # BandPlan (band route) or GenericPlan
    plan_kmajor: object | None
    ranks: list[RankPlan] = field(default_factory=list)
    nk: int = 0  # key extent this hop attends


@dataclass
class Lowering:
    """A mask's grids for one :class:`GridSpec` — what the compiler
    emits, as data.  ``route`` records which seam produced it
    (``"band"`` = the shipping band_plan/ring-hop machinery,
    ``"generic"`` = the algebra's tile classifier)."""

    mask: Mask
    spec: GridSpec
    route: str
    hops: list[LoweredHop] = field(default_factory=list)

    @property
    def tiles(self) -> int:
        return sum(len(h.plan.tile_q) for h in self.hops
                   if h.plan is not None)


@dataclass
class GenericPlan:
    """Duck-type of :class:`~ring_attention_tpu.ops.pallas_flash.BandPlan`
    for generic (non-band) lowerings: same tables, flags, and
    closed-form-vs-enumeration contract, built from the algebra's exact
    tile classifier instead of the band arithmetic."""

    tile_q: np.ndarray
    tile_k: np.ndarray
    flags: np.ndarray
    tiles: int
    block_q: int
    block_k: int
    n_q_blocks: int
    n_k_blocks: int
    outer_is_q: bool

    @property
    def work_tiles(self) -> int:
        from .ops.pallas_flash import _TF_WORK

        return int((self.flags & _TF_WORK != 0).sum())

    @property
    def edge_tiles(self) -> int:
        from .ops.pallas_flash import _TF_EDGE, _TF_WORK

        return int((self.flags & (_TF_WORK | _TF_EDGE)
                    == (_TF_WORK | _TF_EDGE)).sum())


def _tables_from_classes(work: np.ndarray, interior: np.ndarray,
                         bq: int, bk: int, outer_is_q: bool) -> GenericPlan:
    """Build FIRST/LAST-bracketed tile tables from per-tile (work,
    interior) classifications — the same dummy-row and accumulator-
    lifecycle contract as ``ops.pallas_flash._band_tables``."""
    from .ops.pallas_flash import _TF_EDGE, _TF_FIRST, _TF_LAST, _TF_WORK

    nqb, nkb = work.shape
    outer_n = nqb if outer_is_q else nkb
    inner_n = nkb if outer_is_q else nqb
    tq, tk, tf = [], [], []
    for o in range(outer_n):
        start = len(tf)
        for i in range(inner_n):
            qi, ki = (o, i) if outer_is_q else (i, o)
            if work[qi, ki]:
                tq.append(qi)
                tk.append(ki)
                tf.append(_TF_WORK
                          | (0 if interior[qi, ki] else _TF_EDGE))
        if len(tf) == start:  # empty row: dummy entry, write zeros
            tq.append(o if outer_is_q else 0)
            tk.append(0 if outer_is_q else o)
            tf.append(0)
        tf[start] |= _TF_FIRST
        tf[-1] |= _TF_LAST
    return GenericPlan(
        tile_q=np.asarray(tq, np.int32), tile_k=np.asarray(tk, np.int32),
        flags=np.asarray(tf, np.int32), tiles=len(tf), block_q=bq,
        block_k=bk, n_q_blocks=nqb, n_k_blocks=nkb, outer_is_q=outer_is_q,
    )


def _hop_pairings(spec: GridSpec):
    """``(hop, [(rank, q_origin, kv_origin)])`` per hop — the visit
    schedule of each strategy, recomputed here from first principles
    (the certifier recomputes it independently and cross-checks)."""
    W = spec.ring
    if spec.strategy == "single":
        return [(0, [(0, 0, 0)])]
    out = []
    for i in range(spec.n_passes):
        if spec.strategy == "counter":
            from .parallel.ring import _counter_origins

            rows = []
            for r in range(W):
                qo, ko = _counter_origins(r, i, W)
                rows.append((r, int(qo), int(ko)))
        else:  # ring: rank r holds its own q, hop i delivers origin r-i
            rows = [(r, r, (r - i) % W) for r in range(W)]
        out.append((i, rows))
    return out


def _lower_band(mask: Mask, spec: GridSpec, band) -> Lowering:
    """Band-shaped masks lower through the SHIPPING seams: the ring-hop
    band helpers of ``parallel/ring.py`` (causal-style bands) and
    ``ops.pallas_flash.band_plan`` tables — certifying this lowering
    certifies the real kernels' grids."""
    from .ops.pallas_flash import band_plan
    from .parallel import ring as ring_mod

    hi_g, lo_g = band
    causal_style = hi_g == 0  # the ring layer's causal(+window) contract
    window = None if lo_g is None else 1 - lo_g
    windowed = window is not None
    striped = spec.layout == "striped"
    n = spec.n_local
    low = Lowering(mask=mask, spec=spec, route="band")

    if spec.strategy != "single" and not causal_style:
        raise MaskLoweringError(
            f"mask {mask.key!r}: the ring/counter hop schedules lower "
            f"causal-style bands (hi == 0) only; band [{lo_g}, {hi_g}] "
            f"lowers on single-sweep specs or through the generic route"
        )

    for i, rows in _hop_pairings(spec):
        if spec.strategy == "single":
            hi_l, lo_l = hi_g, lo_g  # nq == nk: global diff == local diff
            full = (hi_l is None or hi_l >= n - 1) and (
                lo_l is None or lo_l <= -(n - 1)
            )
            plan = plan_k = None
            if not full:
                hint_hi = n - 1 if hi_l is None else hi_l
                hint = (hint_hi, hint_hi, lo_l or 0, lo_l or 0)
                plan = band_plan((n, n), (spec.block_q, spec.block_k),
                                 hint, windowed=windowed)
                plan_k = band_plan((n, n), (spec.block_q, spec.block_k),
                                   hint, windowed=windowed,
                                   outer_is_q=False)
            ranks = [RankPlan(
                0, 0, 0, has_work=True, hi=None if full else hi_l,
                lo=None if full else lo_l,
            )]
            low.hops.append(LoweredHop(
                hop=i, full=full, plan=plan, plan_kmajor=plan_k,
                ranks=ranks, nk=n,
            ))
            continue
        stream = (1, 0, n)
        if spec.strategy == "counter":
            full, hint = ring_mod._counter_static_band(
                i, n, True, striped, window, spec.ring
            )
        else:
            full, hint = ring_mod._static_hop_band(
                stream, i, n, True, striped, window, spec.ring
            )
        ranks = []
        for r, qo, ko in rows:
            hi, lo = ring_mod._hop_offsets(
                qo, ko, n, True, striped, window, spec.ring
            )
            hi = None if hi is None else int(hi)
            lo = None if lo is None else int(lo)
            has_work = bool(ring_mod._hop_has_work(hi, lo, n, n))
            ranks.append(RankPlan(
                r, qo, ko, has_work=has_work,
                hi=None if full else hi, lo=None if full else lo,
            ))
        plan = plan_k = None
        if not full:
            plan = band_plan((n, n), (spec.block_q, spec.block_k), hint,
                             windowed=windowed)
            plan_k = band_plan((n, n), (spec.block_q, spec.block_k), hint,
                               windowed=windowed, outer_is_q=False)
        low.hops.append(LoweredHop(
            hop=i, full=bool(full), plan=plan, plan_kmajor=plan_k,
            ranks=ranks, nk=n,
        ))
    return low


def _lower_generic(mask: Mask, spec: GridSpec) -> Lowering:
    """Generic lowering: exact per-tile classification from the
    algebra's closed forms (refined elementwise only at genuinely
    ambiguous combinator tiles), shared tables = union over ranks,
    interior = full for every working rank — the same hint semantics
    the band route compiles."""
    if spec.layout != "contiguous":
        raise MaskLoweringError(
            f"mask {mask.key!r}: the generic lowering places tokens "
            f"contiguously; striped layouts lower band-shaped masks only"
        )
    head = spec.head
    n, bq, bk = spec.n_local, spec.block_q, spec.block_k
    nqb, nkb = n // bq, n // bk
    low = Lowering(mask=mask, spec=spec, route="generic")
    for i, rows in _hop_pairings(spec):
        any_l = np.zeros((len(rows), nqb, nkb), bool)
        all_l = np.zeros((len(rows), nqb, nkb), bool)
        for x, (r, qo, ko) in enumerate(rows):
            q0, k0 = qo * n, ko * n
            for qi in range(nqb):
                for ki in range(nkb):
                    a, f = mask.tile_status(
                        q0 + qi * bq, q0 + qi * bq + bq - 1,
                        k0 + ki * bk, k0 + ki * bk + bk - 1, head,
                    )
                    any_l[x, qi, ki] = a
                    all_l[x, qi, ki] = f
        rank_any = any_l.any(axis=(1, 2))
        work = any_l.any(axis=0)
        # interior: full for every rank that computes this hop at all
        interior = work & (all_l[rank_any].all(axis=0)
                           if rank_any.any() else work)
        full = bool(rank_any.any()) and all(
            bool(all_l[x].all()) or not rank_any[x]
            for x in range(len(rows))
        )
        ranks = []
        for x, (r, qo, ko) in enumerate(rows):
            rt = None
            if not full and rank_any[x]:
                rt = mask.oracle(
                    positions("contiguous", qo, n, spec.ring),
                    positions("contiguous", ko, n, spec.ring),
                    head,
                )
            ranks.append(RankPlan(
                r, qo, ko, has_work=bool(rank_any[x]), rt_mask=rt,
            ))
        plan = plan_k = None
        if not full:
            plan = _tables_from_classes(work, interior, bq, bk, True)
            plan_k = _tables_from_classes(work, interior, bq, bk, False)
        low.hops.append(LoweredHop(
            hop=i, full=full, plan=plan, plan_kmajor=plan_k, ranks=ranks,
            nk=n,
        ))
    return low


def lower(mask: Mask, spec: GridSpec) -> Lowering:
    """Lower ``mask`` onto ``spec``: band-shaped masks through the
    shipping band seams, everything else through the generic tile
    classifier.  Runtime :class:`Segments` terms drop out first
    (:func:`static_mask` — they mask in-kernel, not in the grids).
    Raises :class:`MaskLoweringError` when neither route applies (the
    diagnostic names the mask and the supported routes)."""
    mask = static_mask(mask)
    m = mask.head_mask(spec.head) if mask.per_head else mask
    band = band_form(m)
    if band is not None:
        hi, lo = band
        causal_style = hi == 0
        if spec.strategy == "single" or causal_style:
            return _lower_band(m, spec, band)
    return _lower_generic(m, spec)


def dense_mask(mask: Mask, nq: int, nk: int, heads: int = 1,
               q_offset: int = 0, k_offset: int = 0) -> np.ndarray:
    """Materialized oracle over a contiguous span — ``(nq, nk)`` bool,
    or ``(heads, nq, nk)`` for per-head masks.  The O(n^2) reference a
    fallback execution path or a parity test compares against."""
    qpos = q_offset + np.arange(nq)
    kpos = k_offset + np.arange(nk)
    if mask.per_head:
        return np.stack([
            mask.oracle(qpos, kpos, h) for h in range(heads)
        ])
    return mask.oracle(qpos, kpos, 0)


# ---------------------------------------------------------------------------
# Certification: prove a lowering, cache the certificate
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class Certificate:
    """One proven (mask, spec) row: the verdict plus the tile accounting
    the coverage fingerprint and the perf gate pin."""

    key: str
    ok: bool
    violations: tuple[str, ...]
    hops: int
    tiles: int
    work: int
    edge: int
    tiles_kmajor: int
    proof_n: int  # positions per side the elementwise half enumerated

    def to_json(self) -> dict:
        return {
            "key": self.key, "ok": self.ok,
            "violations": list(self.violations), "hops": self.hops,
            "tiles": self.tiles, "work": self.work, "edge": self.edge,
            "tiles_kmajor": self.tiles_kmajor, "proof_n": self.proof_n,
        }


_CERT_MEMO: dict[str, Certificate] = {}
_CERT_SCHEMA = 1


def cert_cache_key(mask: Mask, spec: GridSpec) -> str:
    """The (mask, shape, blocks, strategy, layout) cache key."""
    return (
        f"v{_CERT_SCHEMA}|{mask.key}|{spec.strategy}|{spec.layout}|"
        f"ring{spec.ring}|n{spec.n_local}|b{spec.block_q}x{spec.block_k}|"
        f"p{spec.n_passes}|h{spec.head}"
    )


def cert_cache_dir() -> str | None:
    """On-disk certificate cache directory: ``RING_ATTN_CERT_CACHE``,
    else a ``mask_certificates`` subdir of the configured jax compile
    cache (the proof lives next to the compile it certifies), else
    memory-only."""
    env = os.environ.get("RING_ATTN_CERT_CACHE")
    if env:
        return env
    try:
        import jax

        base = jax.config.jax_compilation_cache_dir
    except Exception:  # jax absent or too old — memory-only cache
        base = None
    if base:
        return os.path.join(base, "mask_certificates")
    return None


def _disk_load(key: str, cache_dir: str | None) -> Certificate | None:
    if not cache_dir:
        return None
    path = os.path.join(
        cache_dir, hashlib.sha256(key.encode()).hexdigest()[:24] + ".json"
    )
    try:
        with open(path) as f:
            data = json.load(f)
        if data.get("key") != key or not data.get("ok"):
            return None
        return Certificate(
            key=key, ok=True, violations=(), hops=int(data["hops"]),
            tiles=int(data["tiles"]), work=int(data["work"]),
            edge=int(data["edge"]),
            tiles_kmajor=int(data["tiles_kmajor"]),
            proof_n=int(data["proof_n"]),
        )
    except (OSError, ValueError, KeyError, TypeError):
        return None  # any corrupt cache entry re-proves, never aborts


def _disk_store(cert: Certificate, cache_dir: str | None) -> None:
    if not cache_dir or not cert.ok:
        return  # failures are re-proven (and re-diagnosed) every run
    try:
        os.makedirs(cache_dir, exist_ok=True)
        path = os.path.join(
            cache_dir,
            hashlib.sha256(cert.key.encode()).hexdigest()[:24] + ".json",
        )
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(cert.to_json(), f)
        os.replace(tmp, path)
    except OSError:
        pass  # a read-only cache dir must never fail the proof itself


def _proof_spec(spec: GridSpec) -> GridSpec:
    """The spec the elementwise half actually enumerates: the leading
    ``CERT_ELEMENTWISE_MAX`` positions when the full shape would cost an
    O(n^2) oracle (the tile-accounting half still runs at full shape)."""
    if spec.n_total <= CERT_ELEMENTWISE_MAX:
        return spec
    n_local = max(spec.block_q, spec.block_k,
                  CERT_ELEMENTWISE_MAX // spec.ring)
    n_local -= n_local % max(spec.block_q, spec.block_k)
    n_local = max(n_local, max(spec.block_q, spec.block_k))
    return GridSpec(
        strategy=spec.strategy, layout=spec.layout, ring=spec.ring,
        n_local=n_local, block_q=spec.block_q, block_k=spec.block_k,
        passes=spec.passes, head=spec.head,
    )


def certify(mask: Mask, spec: GridSpec, *, use_cache: bool = True,
            cache_dir: str | None = None) -> Certificate:
    """Prove ``mask``'s lowering on ``spec`` sound, tight, and complete
    (``analysis/coverage.py::prove_mask_lowering``), caching the
    certificate by (mask, shape, blocks, strategy, layout).

    Per-head masks certify every distinct head variant (the lcm period
    across combinators); the certificate aggregates their tile
    accounting.  Runtime ``Segments`` terms are stripped first — the
    certificate describes the static grids, which is also what the
    launch emits (runtime ids mask in-kernel).
    """
    mask = static_mask(mask)
    key = cert_cache_key(mask, spec)
    if use_cache:
        hit = _CERT_MEMO.get(key)
        if hit is not None:
            return hit
        cache_dir = cache_dir if cache_dir is not None else cert_cache_dir()
        hit = _disk_load(key, cache_dir)
        if hit is not None:
            _CERT_MEMO[key] = hit
            return hit
    from .analysis.coverage import prove_mask_lowering

    pspec = _proof_spec(spec)
    heads = mask.head_period
    violations: list[str] = []
    hops = tiles = work = edge = tiles_k = 0
    for h in range(heads):
        hspec = GridSpec(
            strategy=pspec.strategy, layout=pspec.layout, ring=pspec.ring,
            n_local=pspec.n_local, block_q=pspec.block_q,
            block_k=pspec.block_k, passes=pspec.passes, head=h,
        )
        report = prove_mask_lowering(mask, hspec)
        violations.extend(report.violations)
        hops += report.hops
        tiles += report.tiles
        work += report.work
        edge += report.edge
        tiles_k += report.tiles_kmajor
    if pspec is not spec:
        # full-shape tile accounting: closed form vs enumeration on the
        # real grid (CPU-countable even at 262k)
        try:
            full_low = lower(mask, spec)
            for hop in full_low.hops:
                for plan in (hop.plan, hop.plan_kmajor):
                    if plan is not None and plan.tiles != len(plan.tile_q):
                        violations.append(
                            f"{mask.key}/{spec.strategy}/hop{hop.hop}: "
                            f"closed-form count {plan.tiles} != enumerated "
                            f"{len(plan.tile_q)} at full shape "
                            f"[rule: tile-count]"
                        )
        except MaskLoweringError as e:
            violations.append(f"{mask.key}: full-shape lowering failed: {e}")
    cert = Certificate(
        key=key, ok=not violations, violations=tuple(violations),
        hops=hops, tiles=tiles, work=work, edge=edge,
        tiles_kmajor=tiles_k, proof_n=pspec.n_total,
    )
    if use_cache:
        _CERT_MEMO[key] = cert
        _disk_store(cert, cache_dir)
    return cert


def require_certified(mask: Mask, spec: GridSpec, **kw) -> Certificate:
    """``certify``, raising :class:`MaskCertificationError` with the
    first violation (one line: mask, hop, tile) on failure."""
    cert = certify(mask, spec, **kw)
    if not cert.ok:
        raise MaskCertificationError(cert.violations[0])
    return cert


def spec_for_call(strategy: str, *, n: int, ring: int = 1,
                  striped: bool = False, block_q: int | None = None,
                  block_k: int | None = None,
                  passes: int | None = None) -> GridSpec:
    """The :class:`GridSpec` an attention call's lowering runs under —
    the bridge from model-layer knobs (``sequence_parallel``, layout,
    kernel block fitting) to the certificate cache key.

    ``ulysses`` attends the full sequence locally after its all-to-all
    (a single sweep); ``hybrid`` is the ring schedule at the OUTER ring
    size; ``zigzag`` stays causal-only at the model layer and keeps its
    dedicated prover row.
    """
    from .ops.pallas_flash import _block_sizes

    name = {"ring": "ring", "counter": "counter", "single": "single",
            "ulysses": "single", "hybrid": "ring",
            "zigzag": "single"}.get(strategy)
    if name is None:
        raise ValueError(f"spec_for_call: unknown strategy {strategy!r}")
    if name != "single" and ring <= 1:
        name = "single"
    r = ring if name != "single" else 1
    n_local = n // r if r else n
    bq, bk = _block_sizes(n_local, n_local, block_q, block_k)
    return GridSpec(
        strategy=name, layout="striped" if (striped and name != "single")
        else "contiguous", ring=r, n_local=n_local, block_q=bq,
        block_k=bk, passes=passes,
    )


# ---------------------------------------------------------------------------
# The textual mini-language (tools/check_contracts.py --mask)
# ---------------------------------------------------------------------------

MASK_REGISTRY: dict[str, str] = {
    "full": "Full() — every pair attends",
    "causal": "Causal() — k <= q",
    "window": "window:W — SlidingWindow(W), |q - k| < W",
    "prefix": "prefix:P — PrefixLM(P), bidirectional prefix + causal",
    "dilated": "dilated:S[+O] — Dilated(S, O), (q - k) % S == O",
    "docs": "docs:0,16,32 — DocumentMask(starts)",
    "segments": "Segments() — runtime per-token ids",
    "perhead": "perhead(a;b;...) — per-head mask selection",
}

_TOKEN_RE = re.compile(
    r"\s*(perhead\(|[()&|~;]|[a-z]+(?::[0-9,+]+)?)\s*"
)


def _leaf(tok: str) -> Mask:
    name, _, arg = tok.partition(":")
    if name == "full":
        return Full()
    if name == "causal":
        return Causal()
    if name == "segments":
        return Segments()
    if name == "window":
        if not arg:
            raise MaskParseError("window needs an argument: window:W")
        return SlidingWindow(int(arg))
    if name == "prefix":
        if not arg:
            raise MaskParseError("prefix needs an argument: prefix:P")
        return PrefixLM(int(arg))
    if name == "dilated":
        if not arg:
            raise MaskParseError("dilated needs an argument: dilated:S[+O]")
        stride, _, off = arg.partition("+")
        return Dilated(int(stride), int(off) if off else 0)
    if name == "docs":
        if not arg:
            raise MaskParseError("docs needs arguments: docs:0,16,32")
        return DocumentMask(tuple(int(s) for s in arg.split(",")))
    raise MaskParseError(
        f"unknown mask {name!r}; the registry knows: "
        + "; ".join(f"{k} ({v})" for k, v in sorted(MASK_REGISTRY.items()))
    )


def parse_mask(expr: str) -> Mask:
    """Parse the tiny textual form: leaves from :data:`MASK_REGISTRY`,
    combinators ``&`` (and), ``|`` (or), ``~`` (not), parentheses, and
    ``perhead(a;b)``.  Examples: ``causal&window:512``,
    ``prefix:128|docs:0,64``, ``perhead(causal;causal&window:64)``.
    """
    tokens: list[str] = []
    pos = 0
    s = expr.strip()
    while pos < len(s):
        m = _TOKEN_RE.match(s, pos)
        if not m or not m.group(1):
            raise MaskParseError(
                f"cannot tokenize mask expression at {s[pos:]!r}; the "
                f"registry knows: " + ", ".join(sorted(MASK_REGISTRY))
            )
        tokens.append(m.group(1))
        pos = m.end()
    tokens.append("$")
    idx = [0]

    def peek() -> str:
        return tokens[idx[0]]

    def eat(tok: str | None = None) -> str:
        t = tokens[idx[0]]
        if tok is not None and t != tok:
            raise MaskParseError(f"expected {tok!r}, got {t!r} in {expr!r}")
        idx[0] += 1
        return t

    def atom() -> Mask:
        t = peek()
        if t == "~":
            eat()
            return Not(atom())
        if t == "(":
            eat()
            m = or_expr()
            eat(")")
            return m
        if t == "perhead(":
            eat()
            parts = [or_expr()]
            while peek() == ";":
                eat()
                parts.append(or_expr())
            eat(")")
            return PerHead(tuple(parts))
        if t in ("&", "|", ")", ";", "$"):
            raise MaskParseError(f"expected a mask at {t!r} in {expr!r}")
        eat()
        return _leaf(t)

    def and_expr() -> Mask:
        m = atom()
        while peek() == "&":
            eat()
            m = m & atom()
        return m

    def or_expr() -> Mask:
        m = and_expr()
        while peek() == "|":
            eat()
            m = m | and_expr()
        return m

    out = or_expr()
    if peek() != "$":
        raise MaskParseError(f"trailing input {peek()!r} in {expr!r}")
    return out
