"""RingAttention module: the user-facing attention layer.

TPU-native equivalent of the reference's ``RingAttention``
(ref ``ring_attention.py:283-466``): prenorm + fused qkv projection, GQA head
split, shard-aware rotary, and dispatch to the ring path (``shard_map`` +
``lax.ppermute``) or a single-device oracle (``force_regular_attn``).

Auto-sharding follows the reference's model-top recipe (pad -> stripe ->
shard, ref ``ring_attention.py:389-403``) but expressed as layouts: a pure
stripe permutation plus a ``NamedSharding`` constraint; XLA inserts the
minimal collective instead of a hand-written all-gather
(cf. ``sharded_batch_to_sharded_seq``, ref ``ring_attention.py:223-262``).

Beyond the reference: ``decode_step`` — single-token incremental decoding
against a KV cache sharded over the ring, merged with tree attention
(the reference ships ``tree_attn_decode`` standalone only,
ref ``tree_attn_decoding.py:23-103``).
"""

from __future__ import annotations

import math
import warnings

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .. import masks as mask_algebra
from ..ops.attention import PAD_SEGMENT_ID, default_attention
from ..ops.flash import flash_attention
from ..ops.pallas_flash import (
    QuantizedKV,
    _doc_runtime_ids,
    dequantize_kv_cache as _dequantize,
    pallas_flash_attention,
    pallas_flash_decode,
    pallas_flash_decode_q8,
    quantize_kv_cache,
)
from ..ops.pallas_latent import (
    latent_decode_attention,
    pallas_flash_decode_latent,
)
from ..ops.rotary import (
    YarnScaling,
    apply_rotary,
    hybrid_positions,
    ring_positions,
    rotary_freqs,
    turn,
)
from ..parallel.hybrid import hybrid_attention
from ..parallel.mesh import (
    RING_AXIS,
    SEQ_AXIS,
    ULYSSES_AXIS,
    data_partition,
    is_factored,
    seq_partition,
    seq_world,
)
from ..parallel.ring import ring_flash_attention
from ..parallel.sharding import (
    layout_for,
    layout_permute,
    layout_unpermute,
    pad_seq_and_mask,
    pad_to_multiple,
)
from ..parallel.tree_decode import tree_attn_decode
from ..parallel.ulysses import ulysses_attention
from ..parallel.zigzag import zigzag_attention, zigzag_positions
from ..utils import compat
from ..utils.validate import check_model_input
from .layers import RMSNorm
from .moe import COUNTERS


class RingAttention(nn.Module):
    """Sequence-parallel attention layer.

    Attributes mirror the reference constructor (ref
    ``ring_attention.py:284-337``); ``kv_heads`` expresses GQA directly
    (the reference's ``heads // num_grouped_query_heads``).
    """

    dim: int
    heads: int = 8
    dim_head: int = 64
    kv_heads: int | None = None
    causal: bool = False
    # mask-algebra expression (ring_attention_tpu.masks): the general
    # form of the masking knobs — ``causal=True`` is sugar for
    # ``mask=Causal()``, ``mask=Causal() & SlidingWindow(w)`` replaces
    # ``max_lookback_seq_len=w``, ``... & DocumentMask(starts)`` declares
    # a packed layout, ``... & Segments()`` requires runtime segment_ids.
    # The lowering is certified sound/tight/complete at trace time
    # against the mask's own oracle (masks.require_certified, cached);
    # expressions beyond the kernel surface raise MaskLoweringError
    # naming the supported forms.  Mutually exclusive with causal=True
    # and max_lookback_seq_len (compose them into the mask instead)
    mask: mask_algebra.Mask | None = None
    striped: bool = False
    bucket_size: int = 512
    use_ring: bool = True
    force_regular_attn: bool = False
    rotary: bool = True
    rotary_theta: float = 10000.0
    softclamp_value: float | None = None
    max_lookback_seq_len: int | None = None
    auto_shard: bool = False
    mesh: Mesh | None = None
    use_pallas: bool = False
    # kernel-path selection with graceful degradation (overrides use_pallas
    # when set): "fused" | "pallas" | "xla" | "auto".  "auto" resolves
    # through utils/resilience.py at trace time — the fused ring
    # (ops/pallas_ring.py: one launch, in-kernel remote KV DMA) when its
    # probe passes, else the scan-path Pallas kernels, else the XLA flash
    # path, with a one-shot warning and a queryable degradation record.
    # "fused" applies to the "ring" strategy and the hybrid outer ring;
    # other strategies run it as "pallas".  use_pallas remains as the
    # explicit legacy switch.
    impl: str | None = None
    # split the (non-ring) pallas launch into this many per-head-group
    # kernel programs — bit-identical results; an escape hatch for
    # compiler program-size limits at large heads x seq.  Measured
    # unnecessary on one v5e chip (h=32 x seq 262144 compiles as ONE
    # program — CHANGES.md PR 21); ROADMAP D1 removes it
    pallas_head_chunks: int | None = None
    # store the decode KV cache as per-token-absmax int8 (+ f32 scales):
    # 1.88x fewer cache HBM bytes per decode step at d=64 — the binding
    # resource at long context — for ~1% output error (see
    # ops/pallas_flash.py QuantizedKV).  Cache entries become
    # (values int8, scales f32) tuples; decode attends via the q8 kernel
    # (use_pallas) or a dequantized oracle fallback
    quantize_cache: bool = False
    # context-parallel scheme over the seq mesh axis (or axes):
    #   "ring"    — KV rotation (+ striped load balance); the reference's core
    #   "zigzag"  — Llama-3 chunk pairing + all-gathered KV (causal only)
    #   "ulysses" — all-to-all head parallelism (not in the reference)
    #   "hybrid"  — Ulysses x Ring 2-D factoring: all-to-all over the inner
    #               `ulysses` mesh axis, ring over the outer `ring` axis —
    #               ulysses_size x fewer ring hops at equal world size;
    #               requires a factored mesh (create_mesh(ulysses_size=U))
    sequence_parallel: str = "ring"
    # circulate KV halves in opposite ring directions (full-duplex ICI);
    # applies when the local shard length is even, unidirectional with a
    # warning otherwise (odd shards only arise from padding edge cases)
    ring_bidirectional: bool = False
    # dtype for the circulating dk/dv ring accumulators in the backward:
    # None = float32 (exact); "bfloat16" halves backward ring bandwidth
    # (ref ring_flash_attention_cuda.py:255-260) at bf16 round-off cost
    ring_dkv_dtype: str | None = None
    # TokenRing counter-rotation (arXiv 2412.20501): circulate the Q shard
    # + its online-softmax accumulators one ring direction while the KV
    # stream rotates the other — each full-duplex ICI direction carries
    # about half the rotation traffic, and the backward drops the
    # circulating dkv payload entirely (parallel/ring.py::_counter_fwd).
    # Supersedes ring_bidirectional (the two schedules cannot compose —
    # docs/ring_overlap.md); applies to the pure ring and the hybrid
    # outer ring alike
    ring_counter_rotate: bool = False
    # "int8": ship each forward KV hop as per-token absmax int8 values +
    # bitcast f32 scales in one payload — same hop count, ~dtype_bytes *
    # d/(d+4)-x fewer bytes per hop; quantized once at ring entry, f32
    # accumulators untouched (parallel/collectives.quantize_ring_payload)
    ring_hop_compression: str | None = None
    # "int8": run the forward's QK^T and PV matmuls on int8 operands
    # (v5e/v5p MXUs run int8 at ~2x bf16 peak) with per-row q/k and
    # per-KV-block v absmax scales, f32 (acc, m, l) untouched; the
    # backward stays bf16 from the exact residuals this round.  Pallas
    # kernels only — requires impl="pallas"/use_pallas on the "ring" or
    # "hybrid" strategies (or the local path); composes with
    # ring_hop_compression="int8" into the dequant-free ring
    # (docs/precision.md)
    compute_dtype: str | None = None
    # RMSNorm over dim_head on q and on k (one weight vector each), before
    # the rotation
    qk_norm: bool = False
    # multiply sigmoid(prenorm(x) W_g), heads * dim_head wide, into the
    # attention output before to_out
    out_gate: bool = False
    norm_eps: float = 1e-12
    # the softmax scale, where it is not ``dim_head ** -0.5`` (which every
    # kernel and oracle applies): the ratio of the two is folded into the
    # queries once, at ``_project_qkv``'s end, so it reaches every path
    softmax_scale: float | None = None
    dtype: jnp.dtype | None = None

    def setup(self):
        h, kvh, dh = self.heads, self._kv_heads(), self.dim_head
        self.prenorm = RMSNorm(self.dim, self.norm_eps)
        self.to_qkv = nn.Dense(
            (h + 2 * kvh) * dh, use_bias=False, dtype=self.dtype
        )
        if self.qk_norm:
            self.q_norm = RMSNorm(dh, self.norm_eps)
            self.k_norm = RMSNorm(dh, self.norm_eps)
        if self.out_gate:
            self.to_gate = nn.Dense(h * dh, use_bias=False, dtype=self.dtype)
        self.to_out = nn.Dense(self.dim, use_bias=False, dtype=self.dtype)

    def _kv_heads(self) -> int:
        kvh = self.kv_heads or self.heads
        assert self.heads % kvh == 0
        return kvh

    def _mask_form(self) -> mask_algebra.KernelForm | None:
        """The algebra mask resolved onto the kernel knobs (or None).
        Raises on conflicting legacy knobs and on masks beyond the
        kernel surface (MaskLoweringError names the supported forms)."""
        if self.mask is None:
            return None
        if self.causal:
            raise ValueError(
                "RingAttention: mask= replaces causal=True (causal=True "
                "is sugar for mask=Causal()); set only one"
            )
        if self.max_lookback_seq_len is not None:
            raise ValueError(
                "RingAttention: mask= replaces max_lookback_seq_len — "
                "compose SlidingWindow(w) into the mask instead"
            )
        return mask_algebra.kernel_form(self.mask)

    def _eff_causal(self) -> bool:
        form = self._mask_form()
        return self.causal if form is None else form.causal

    def _eff_lookback(self) -> int | None:
        form = self._mask_form()
        return self.max_lookback_seq_len if form is None else form.window

    def _certify_mask(self, n: int) -> None:
        """Trace-time certificate for the grids this call's strategy
        lowers the mask to — proven on first use, cached by (mask,
        shape, blocks, strategy, layout) next to the compile cache."""
        if self.mask is None:
            return
        ring = (self.use_ring and not self.force_regular_attn
                and self._ring_size() > 1)
        if not ring:
            strategy, ring_size = "single", 1
        elif self.sequence_parallel == "hybrid":
            strategy = ("counter" if self.ring_counter_rotate else "ring")
            ring_size = self._ring_size() // self._ulysses_size()
        elif self.sequence_parallel == "ring":
            strategy = ("counter" if self.ring_counter_rotate else "ring")
            ring_size = self._ring_size()
        else:  # ulysses attends the full span locally; zigzag is
            strategy, ring_size = "single", 1  # causal-only (own row)
        passes = None
        if strategy in ("ring", "counter") and ring_size > 1:
            _, _, _, passes = self._ring_leg(n // ring_size)
        mask_algebra.require_certified(
            self.mask,
            mask_algebra.spec_for_call(
                strategy, n=n, ring=ring_size, striped=self.striped,
                passes=passes,
            ),
        )

    def _kernel_impl(self) -> str:
        """Resolve the kernel path for this call (trace time, cached probe):
        "fused" | "pallas" | "xla".  Counter-rotation has no fused form
        (the alternating Q/KV schedule cannot ride one launch), so a
        resolved "fused" degrades to the scan-path Pallas ring there."""
        if self.impl is None:
            resolved = "pallas" if self.use_pallas else "xla"
        else:
            from ..utils import resilience

            resolved = resilience.resolve_ring_impl(self.impl)
        if resolved == "fused" and self.ring_counter_rotate:
            return "pallas"
        return resolved

    def _use_pallas(self) -> bool:
        """True when this call runs on Pallas kernels (scan-path or fused)."""
        return self._kernel_impl() in ("pallas", "fused")

    def _compute_dtype(self) -> str | None:
        """Validated int8-compute knob for this call.

        ``"int8"`` needs the Pallas kernels (the XLA/oracle paths have no
        int8 matmul form) and a strategy that lowers onto them — the
        local path, "ring", or "hybrid".  A config that silently ran the
        quantized model at bf16 would misreport every perf number, so
        mismatches raise rather than degrade (docs/precision.md)."""
        if self.compute_dtype is None:
            return None
        if self.compute_dtype != "int8":
            raise ValueError(
                f"RingAttention: compute_dtype={self.compute_dtype!r}; "
                'supported values are None and "int8"'
            )
        if self.force_regular_attn or not self._use_pallas():
            raise ValueError(
                'compute_dtype="int8" runs on the Pallas kernels only — '
                "set impl=\"pallas\"/use_pallas=True (and drop "
                "force_regular_attn)"
            )
        if (self._ring_size() > 1 and self.use_ring
                and self.sequence_parallel not in ("ring", "hybrid")):
            raise ValueError(
                f'compute_dtype="int8" supports the "ring" and "hybrid" '
                f"strategies (and the local path); got "
                f'sequence_parallel="{self.sequence_parallel}"'
            )
        return "int8"

    def _ring_size(self) -> int:
        """Total sequence-parallel world (over BOTH axes of a factored mesh)."""
        if self.mesh is None:
            return 1
        return seq_world(self.mesh)

    def _ulysses_size(self) -> int:
        if self.mesh is None or not is_factored(self.mesh):
            return 1
        return self.mesh.shape[ULYSSES_AXIS]

    def _layout(self) -> tuple[str, int]:
        """(scheme, factor) for the model-top sequence permutation — the
        shared derivation (``parallel/sharding.py::layout_for``), so this
        layer and ``RingTransformer`` can never disagree."""
        return layout_for(
            self.sequence_parallel, self.striped, self._ring_size(),
            self._ulysses_size(),
        )

    def _check_mesh(self) -> None:
        factored = self.mesh is not None and is_factored(self.mesh)
        if self.sequence_parallel == "hybrid" and not factored:
            raise ValueError(
                'sequence_parallel="hybrid" needs a factored mesh — build '
                "it with create_mesh(ulysses_size=U, ring_size=R)"
            )
        if self.sequence_parallel != "hybrid" and factored:
            raise ValueError(
                f'sequence_parallel="{self.sequence_parallel}" runs on a '
                "plain (data, seq) mesh; the factored (data, ring, ulysses) "
                'mesh is for sequence_parallel="hybrid"'
            )

    def _bidirectional(self, n_local: int) -> bool:
        """Bidirectional streams need an even local shard; warn on the
        silent unidirectional fallback so benchmarks aren't misread."""
        if self.ring_bidirectional and n_local % 2:
            warnings.warn(
                f"ring_bidirectional requested but the per-device sequence "
                f"length ({n_local}) is odd; running the unidirectional ring",
                stacklevel=3,
            )
            return False
        return self.ring_bidirectional

    def _project_qkv(self, x: jax.Array):
        """prenorm + fused qkv -> heads-major (b, h|hk, n, dh), and the
        output gate's logits ``(b, n, h * dh)`` (None without one)."""
        h, kvh, dh = self.heads, self._kv_heads(), self.dim_head
        with jax.named_scope("attn/qkv_proj"):
            normed = self.prenorm(x)
            qkv = self.to_qkv(normed)
        with jax.named_scope("attn/heads"):
            q, k, v = jnp.split(qkv, [h * dh, (h + kvh) * dh], axis=-1)
            b, n, _ = x.shape
            q = q.reshape(b, n, h, dh).transpose(0, 2, 1, 3)
            k = k.reshape(b, n, kvh, dh).transpose(0, 2, 1, 3)
            v = v.reshape(b, n, kvh, dh).transpose(0, 2, 1, 3)
        if self.qk_norm:
            with jax.named_scope("attn/qk_norm"):
                q, k = self.q_norm(q), self.k_norm(k)
        gate = None
        if self.out_gate:
            with jax.named_scope("attn/gate"):
                gate = self.to_gate(normed)
        if self.softmax_scale is not None:
            with jax.named_scope("attn/heads"):
                q = q * jnp.asarray(self.softmax_scale * dh ** 0.5, q.dtype)
        return q, k, v, gate

    def _project_out(self, out: jax.Array, gate: jax.Array | None):
        """heads-major attention output ``(b, h, n, dh)`` -> ``(b, n, dim)``,
        through the output gate where the layer has one."""
        b, _, n, _ = out.shape
        with jax.named_scope("attn/heads"):
            out = out.transpose(0, 2, 1, 3).reshape(b, n, -1)
        if gate is not None:
            with jax.named_scope("attn/gate"):
                out = (out.astype(jnp.float32) * jax.nn.sigmoid(
                    gate.astype(jnp.float32))).astype(out.dtype)
        with jax.named_scope("attn/out_proj"):
            return self.to_out(out)

    def __call__(
        self,
        x: jax.Array,
        mask: jax.Array | None = None,
        segment_ids: jax.Array | None = None,
    ) -> jax.Array:
        """``x: (b, n, dim)`` -> ``(b, n, dim)``.

        When ``auto_shard`` is set, ``x`` is a global (unsharded-layout)
        array: it is padded to the ring size, stripe-permuted if ``striped``,
        and constrained onto the ``(data, seq)`` mesh; the inverse is applied
        to the output (ref ``ring_attention.py:389-403,458-464``).

        ``segment_ids: (b, n)`` int document ids enable packed-sequence
        attention (cross-document attention masked; whole tiles/hops
        skipped where possible — see ``docs/packing.md``).  Padding added
        by ``auto_shard`` gets ``PAD_SEGMENT_ID``, which matches no real
        document.
        """
        check_model_input("RingAttention", x, self.dim)
        ring = self.use_ring and not self.force_regular_attn and self._ring_size() > 1
        assert self.sequence_parallel in ("ring", "zigzag", "ulysses", "hybrid")
        if ring:
            self._check_mesh()
        if self.sequence_parallel == "zigzag":
            assert self._eff_causal(), "zig-zag CP is causal-only (ref zig_zag_attention.py:102-103)"
            assert self._eff_lookback() is None, "lookback not supported with zigzag"

        form = self._mask_form()
        if form is not None:
            if form.needs_segment_ids and segment_ids is None:
                raise ValueError(
                    "RingAttention: the mask includes Segments() — pass "
                    "the runtime segment_ids array"
                )
            if form.doc_starts is not None:
                if segment_ids is not None:
                    raise ValueError(
                        "RingAttention: the mask declares a DocumentMask "
                        "layout AND segment_ids were passed — declare "
                        "one packing"
                    )
                if ring:
                    # sequence-parallel paths realize the declared layout
                    # as runtime ids (padded/permuted/rotated by the
                    # existing proven machinery); the local Pallas path
                    # keeps doc_starts for its trace-time compact grid
                    segment_ids = _doc_runtime_ids(
                        form.doc_starts, x.shape[1], x.shape[0]
                    )

        n_orig = x.shape[1]
        scheme, factor = self._layout()
        if ring and self.auto_shard:
            pad_mult = (
                2 * self._ring_size()
                if self.sequence_parallel == "zigzag"
                else self._ring_size()
            )
            x, mask, n_orig = pad_seq_and_mask(x, mask, pad_mult)
            if segment_ids is not None:
                segment_ids, _ = pad_to_multiple(
                    segment_ids, pad_mult, value=PAD_SEGMENT_ID
                )
            x = layout_permute(x, scheme, factor)
            if mask is not None:
                mask = layout_permute(mask, scheme, factor)
            if segment_ids is not None:
                segment_ids = layout_permute(segment_ids, scheme, factor)
            x = lax.with_sharding_constraint(
                x, NamedSharding(
                    self.mesh, P(data_partition(self.mesh), seq_partition(self.mesh), None)
                )
            )

        q, k, v, gate = self._project_qkv(x)
        b, n, _ = x.shape
        self._certify_mask(n)

        if self._eff_causal():
            mask = None  # ref asserts causal and key-pad mask are exclusive

        if ring:
            with jax.named_scope("attn/kernel_io"):
                out = self._sp_attend(q, k, v, mask, segment_ids)
        else:
            out = self._local_attend(q, k, v, mask, segment_ids)

        out = self._project_out(out, gate)

        if ring and self.auto_shard:
            out = layout_unpermute(out, scheme, factor)
            out = out[:, :n_orig]
        return out

    def _rotate(self, q, k, positions):
        """``q`` and ``k`` rotated to ``positions`` (one per row of their
        sequence axis), or as they are in a layer without positions."""
        if not self.rotary:
            return q, k
        with jax.named_scope("attn/rotary"):
            freqs = rotary_freqs(positions, self.dim_head, self.rotary_theta)
            return apply_rotary(q, freqs), apply_rotary(k, freqs)

    def _local_attend(self, q, k, v, mask, segment_ids=None):
        n = q.shape[2]
        q, k = self._rotate(q, k, jnp.arange(n))
        # a mask-declared packing: doc_starts feed the Pallas compact
        # grid directly; the XLA/oracle paths realize them as runtime ids
        form = self._mask_form()
        doc_starts = (form.doc_starts
                      if form is not None and segment_ids is None else None)
        return self._attend(
            q, k, v, mask, causal=self._eff_causal(),
            segment_ids=segment_ids, doc_starts=doc_starts,
        )

    def _attend(self, q, k, v, mask=None, *, causal, segment_ids=None,
                doc_starts=None):
        """Attention over one device's whole span, on the path the model
        declares: the dense oracle (``force_regular_attn``), the Pallas
        kernel (``_use_pallas()``) or the XLA blockwise scan.  The ONE
        choice for the forward pass and ``prefill`` off the ring, so the
        two cannot drift; rotary is the caller's (``prefill`` keeps the
        rotated ``k`` for the cache)."""
        # what the call runs around its kernel (the kernel's grouped
        # layout, lane padding, masks, the backward's row sums) is named;
        # the kernel goes by its own name and the XLA scan by flash/*
        with jax.named_scope("attn/kernel_io"):
            window = self._eff_lookback()
            doc_ids = (None if doc_starts is None
                       else _doc_runtime_ids(doc_starts, q.shape[2], q.shape[0]))
            if self.force_regular_attn and window is None:
                return default_attention(
                    q, k, v, mask, causal=causal,
                    softclamp_value=self.softclamp_value,
                    segment_ids=segment_ids if doc_ids is None else doc_ids,
                )
            if self._use_pallas():
                return pallas_flash_attention(
                    q, k, v, mask, causal=causal, window=window,
                    softclamp_value=self.softclamp_value,
                    head_chunks=self.pallas_head_chunks,
                    segment_ids=segment_ids, doc_starts=doc_starts,
                    compute_dtype=self._compute_dtype(),
                )
            return flash_attention(
                q, k, v, mask, causal=causal, bucket_size=self.bucket_size,
                window=window, softclamp_value=self.softclamp_value,
                segment_ids=segment_ids if doc_ids is None else doc_ids,
            )

    def _sp_attend(self, q, k, v, mask, segment_ids=None):
        """Dispatch to the configured context-parallel scheme."""
        ring_size = self._ring_size()
        n = q.shape[2]
        mult = 2 * ring_size if self.sequence_parallel == "zigzag" else ring_size
        assert n % mult == 0, (
            f"sequence {n} must divide over {mult} ({self.sequence_parallel}); "
            "use auto_shard=True to pad"
        )
        if self.sequence_parallel == "zigzag":
            return self._zigzag_attend(q, k, v, segment_ids)
        if self.sequence_parallel == "ulysses":
            return self._ulysses_attend(q, k, v, mask, segment_ids)
        if self.sequence_parallel == "hybrid":
            return self._hybrid_attend(q, k, v, mask, segment_ids)
        return self._ring_attend(q, k, v, mask, segment_ids)

    def _seg_spec(self, segment_ids):
        """shard_map spec for an optional (b, n) per-token operand, on the
        plain or factored sequence axes."""
        if segment_ids is None:
            return P()
        return P(data_partition(self.mesh), seq_partition(self.mesh))

    def _ring_leg(self, n_chunk: int):
        """Ring-leg knobs for chunks of length ``n_chunk`` — the whole
        local shard for the pure ring, the post-all-to-all chunk for
        hybrid.  Returns ``(bucket, bidirectional, window,
        max_ring_passes)``; the ONE copy of the bucket-fit and lookback
        hop-skip arithmetic, so the two ring callers cannot drift."""
        # per-hop flash tile: largest divisor of the chunk <= bucket_size
        bucket = min(self.bucket_size, n_chunk)
        while n_chunk % bucket:
            bucket -= 1
        bidirectional = self._bidirectional(n_chunk)
        max_ring_passes = None
        window = None
        lookback = self._eff_lookback()
        if lookback is not None:
            assert self._eff_causal(), (
                "max_lookback_seq_len requires causal attention "
                "(ref ring_flash_attention.py:99)"
            )
            window = lookback
            if not self.striped:
                # contiguous layout: distant hops carry no in-window keys,
                # so cover ceil((window-1)/n_chunk) earlier chunks plus our
                # own (exact — the reference truncates early rows at bucket
                # granularity, ring_flash_attention.py:95-103)
                max_ring_passes = math.ceil((lookback - 1) / n_chunk) + 1
            # striped layout: windows are exact too (per-hop band lower
            # offsets, parallel/ring.py), but striping interleaves tokens
            # so every hop holds some in-window keys — all passes run.
            # Prefer non-striped for windowed attention: the window itself
            # balances causal load and allows hop skipping.
        return bucket, bidirectional, window, max_ring_passes

    def _zigzag_attend(self, q, k, v, segment_ids=None):
        ring_size = self._ring_size()
        n_local = q.shape[2] // ring_size

        def core(q, k, v, seg):
            if self.rotary:
                rank = lax.axis_index(SEQ_AXIS)
                q, k = self._rotate(
                    q, k, zigzag_positions(n_local, rank, ring_size))
            return zigzag_attention(
                q, k, v, SEQ_AXIS,
                bucket_size=self.bucket_size,
                softclamp_value=self.softclamp_value,
                impl="pallas" if self._use_pallas() else "xla",
                segment_ids=seg,
            )

        qspec = P(data_partition(self.mesh), None, SEQ_AXIS, None)
        return compat.shard_map(
            core, mesh=self.mesh,
            in_specs=(qspec, qspec, qspec, self._seg_spec(segment_ids)),
            out_specs=qspec,
            check_vma=not self._use_pallas(),
        )(q, k, v, segment_ids)

    def _ulysses_attend(self, q, k, v, mask, segment_ids=None):
        ring_size = self._ring_size()
        n_local = q.shape[2] // ring_size

        def core(q, k, v, mask, seg):
            if self.rotary:
                rank = lax.axis_index(SEQ_AXIS)
                q, k = self._rotate(q, k, ring_positions(
                    n_local, rank, striped=False, world=ring_size))
            return ulysses_attention(
                q, k, v, SEQ_AXIS,
                causal=self._eff_causal(),
                kv_mask=mask,
                bucket_size=self.bucket_size,
                window=self._eff_lookback(),
                softclamp_value=self.softclamp_value,
                impl="pallas" if self._use_pallas() else "xla",
                segment_ids=seg,
            )

        qspec = P(data_partition(self.mesh), None, SEQ_AXIS, None)
        mspec = P(data_partition(self.mesh), SEQ_AXIS) if mask is not None else P()
        return compat.shard_map(
            core, mesh=self.mesh,
            in_specs=(qspec, qspec, qspec, mspec, self._seg_spec(segment_ids)),
            out_specs=qspec,
            check_vma=not self._use_pallas(),
        )(q, k, v, mask, segment_ids)

    def _hybrid_attend(self, q, k, v, mask, segment_ids=None):
        """Ulysses x Ring 2-D factoring over the (data, ring, ulysses) mesh.

        Rotary runs on the resident (pre-all-to-all) shard with positions
        from the combined rank — the all-to-all only *moves* rotated
        tokens, so the ring leg sees exactly the positions a pure ring of
        ``ring_size`` devices would.  The ring-leg knobs (bucket, window,
        bidirectional streams) are sized against the post-all-to-all chunk
        ``n / ring_size``, which is what the ring actually attends.
        """
        ulysses = self._ulysses_size()
        ring_size = self._ring_size() // ulysses
        n = q.shape[2]
        n_local = n // (ulysses * ring_size)  # resident shard
        n_ring = n // ring_size  # post-all-to-all ring chunk
        bucket, bidirectional, window, max_ring_passes = self._ring_leg(n_ring)

        def core(q, k, v, mask, seg):
            if self.rotary:
                pos = hybrid_positions(
                    n_local,
                    lax.axis_index(ULYSSES_AXIS),
                    lax.axis_index(RING_AXIS),
                    ulysses=ulysses, ring=ring_size, striped=self.striped,
                )
                q_r, k_r = self._rotate(q, k, pos)
            else:
                q_r, k_r = q, k
            return hybrid_attention(
                q_r, k_r, v, mask, ULYSSES_AXIS, RING_AXIS,
                causal=self._eff_causal(), striped=self.striped,
                bucket_size=bucket, max_ring_passes=max_ring_passes,
                window=window, softclamp_value=self.softclamp_value,
                impl=self._kernel_impl(),
                bidirectional=bidirectional,
                dkv_dtype=self.ring_dkv_dtype,
                segment_ids=seg,
                counter_rotate=self.ring_counter_rotate,
                hop_compression=self.ring_hop_compression,
                compute_dtype=self._compute_dtype(),
            )

        qspec = P(data_partition(self.mesh), None, seq_partition(self.mesh), None)
        mspec = self._seg_spec(mask)
        return compat.shard_map(
            core,
            mesh=self.mesh,
            in_specs=(qspec, qspec, qspec, mspec, self._seg_spec(segment_ids)),
            out_specs=qspec,
            check_vma=not self._use_pallas(),
        )(q, k, v, mask, segment_ids)

    def _ring_attend(self, q, k, v, mask, segment_ids=None):
        ring_size = self._ring_size()
        n = q.shape[2]
        n_local = n // ring_size
        bucket, bidirectional, window, max_ring_passes = self._ring_leg(n_local)

        def core(q, k, v, mask, seg):
            rank = lax.axis_index(SEQ_AXIS)
            if self.rotary:
                pos = ring_positions(
                    n_local, rank, striped=self.striped, world=ring_size
                )
                q_r, k_r = self._rotate(q, k, pos)
            else:
                q_r, k_r = q, k
            return ring_flash_attention(
                q_r, k_r, v, mask, SEQ_AXIS,
                self._eff_causal(), self.striped,
                bucket, max_ring_passes, window,
                self.softclamp_value, None,
                self._kernel_impl(),
                bidirectional, self.ring_dkv_dtype,
                segment_ids=seg,
                counter_rotate=self.ring_counter_rotate,
                hop_compression=self.ring_hop_compression,
                compute_dtype=self._compute_dtype(),
            )

        qspec = P(data_partition(self.mesh), None, SEQ_AXIS, None)
        mspec = P(data_partition(self.mesh), SEQ_AXIS) if mask is not None else P()
        return compat.shard_map(
            core,
            mesh=self.mesh,
            in_specs=(qspec, qspec, qspec, mspec, self._seg_spec(segment_ids)),
            out_specs=qspec,
            # pallas_call with device-varying scalars trips jax's vma
            # checker; jax suggests check_vma=False as the workaround
            check_vma=not self._use_pallas(),
        )(q, k, v, mask, segment_ids)

    # ------------------------------------------------------------------
    # Incremental decoding (beyond reference parity)
    # ------------------------------------------------------------------

    def decode_step(
        self,
        x: jax.Array,  # (b, 1, dim) — the new token's activation
        cache_k: jax.Array,  # (b, hk, max_len, dh); sharded over seq if ring
        cache_v: jax.Array,
        pos: jax.Array,  # scalar int32: index the new token occupies
    ) -> tuple[jax.Array, jax.Array, jax.Array]:
        """One token of autoregressive decoding against a KV cache.

        Writes this token's K/V at ``pos`` and attends positions
        ``[0, pos]`` — restricted to the last ``max_lookback_seq_len``
        positions when the layer has a lookback window, matching the
        training-time forward.  With a mesh, the cache is sharded
        contiguously over the ``seq`` axis and the shard partials merge via
        tree attention (``parallel/tree_decode.py``); decode layout is
        always contiguous regardless of how training was striped, since
        positions are explicit.  Returns ``(out (b,1,dim), cache_k, cache_v)``.
        """
        q, k, v, gate = self._project_qkv(x)
        q, k = self._rotate(q, k, jnp.reshape(pos, (1,)))

        ring = self.use_ring and not self.force_regular_attn and self._ring_size() > 1
        # the local cache is a ring buffer: writes land at pos % size and
        # slot validity comes from _buffer_mask.  A full-length cache
        # (size > every pos) reduces exactly to the plain layout, and a
        # window-sized cache (size >= max_lookback_seq_len) stores only the
        # window — O(W) decode memory/bandwidth instead of O(max_len) for
        # lookback layers (RingTransformer.init_cache sizes them so)
        if not ring and self.quantize_cache:
            size = cache_k[0].shape[2]
            with jax.named_scope("attn/cache_write"):
                cache_k, cache_v = self._quantized_write(
                    cache_k, cache_v, k, v, pos % size
                )
            kv = QuantizedKV(*cache_k, *cache_v)
            kv_mask = self._buffer_mask(size, pos, x.shape[0])
            if self._use_pallas():
                out, _ = pallas_flash_decode_q8(
                    q, kv, kv_mask, softclamp_value=self.softclamp_value,
                )
            else:
                k_deq, v_deq = _dequantize(kv, q.dtype)
                out = default_attention(
                    q, k_deq, v_deq, kv_mask,
                    softclamp_value=self.softclamp_value,
                )
        elif not ring:
            size = cache_k.shape[2]
            with jax.named_scope("attn/cache_write"):
                slot = pos % size
                cache_k = lax.dynamic_update_slice_in_dim(cache_k, k.astype(cache_k.dtype), slot, axis=2)
                cache_v = lax.dynamic_update_slice_in_dim(cache_v, v.astype(cache_v.dtype), slot, axis=2)
            kv_mask = self._buffer_mask(size, pos, x.shape[0])
            if self._use_pallas():
                # single-sweep decode kernel: each cache byte read once per
                # kv head, normalized output written in-kernel
                out, _ = pallas_flash_decode(
                    q, cache_k, cache_v, kv_mask,
                    softclamp_value=self.softclamp_value,
                )
            else:
                out = default_attention(
                    q, cache_k, cache_v, kv_mask,
                    softclamp_value=self.softclamp_value,
                )
        else:
            out, cache_k, cache_v = self._ring_decode(q, k, v, cache_k, cache_v, pos)

        return self._project_out(out, gate), cache_k, cache_v

    @staticmethod
    def _quantized_write(cache_k, cache_v, k, v, pos):
        """Quantize this step's K/V rows and write values + scales at
        ``pos``.  Cache entries are ``(values int8, scales f32)`` tuples."""
        kq, ks, vq, vs = quantize_kv_cache(k, v)
        (k_qc, k_sc), (v_qc, v_sc) = cache_k, cache_v

        def wr(c, new, axis):
            return lax.dynamic_update_slice_in_dim(
                c, new.astype(c.dtype), pos, axis=axis
            )

        return (
            (wr(k_qc, kq, 2), wr(k_sc, ks, 2)),
            (wr(v_qc, vq, 2), wr(v_sc, vs, 2)),
        )

    def _decode_mask(self, idx: jax.Array, pos: jax.Array, batch: int) -> jax.Array:
        """Valid-cache-slot mask for a decode step: ``[0, pos]``, windowed to
        the last ``max_lookback_seq_len`` tokens when configured.  ``idx``
        are absolute token positions (the ring path's contiguous shards)."""
        keep = idx <= pos
        lookback = self._eff_lookback()
        if lookback is not None:
            keep = keep & (idx > pos - lookback)
        return jnp.broadcast_to(keep[None, :], (batch, idx.shape[0]))

    def _buffer_mask(self, size: int, pos: jax.Array, batch: int) -> jax.Array:
        """Valid-slot mask for a ring-buffer cache of ``size`` slots.

        Slot ``s`` holds the most recent position ``p_s <= pos`` with
        ``p_s ≡ s (mod size)``; a slot is valid when that position exists
        (``p_s >= 0``) and sits inside the lookback window.  With
        ``size > pos`` this reduces to the plain ``idx <= pos`` mask, so
        the local decode path uses it unconditionally."""
        s = jnp.arange(size)
        p = pos - ((pos - s) % size)
        keep = p >= 0
        lookback = self._eff_lookback()
        if lookback is not None:
            keep = keep & (p > pos - lookback)
        return jnp.broadcast_to(keep[None, :], (batch, size))

    def prefill(
        self,
        x: jax.Array,  # (b, n, dim) — the whole prompt
        cache_k: jax.Array,  # (b, hk, max_len, dh)
        cache_v: jax.Array,
    ) -> tuple[jax.Array, jax.Array, jax.Array]:
        """Process a whole prompt in one causal pass and fill cache[0:n].

        One O(n^2)-FLOPs flash pass instead of n decode steps; the written
        K/V are rotary-applied exactly as ``decode_step`` writes them, so
        decoding can continue from position ``n``.  Off the ring the pass
        takes the forward's own dispatch (``_attend``): the Pallas flash
        kernel over the causal half (a windowed layer: its band) when the
        model declares it (``use_pallas`` / ``impl``: bf16 operands, f32
        accumulators), else the XLA blockwise scan (f32 products over the
        whole square, masked).  With a mesh, the prompt
        is padded onto the ring and attention runs sequence-parallel
        (contiguous layout, like the decode cache) — per-device memory
        scales as n/ring, same as the training forward.  Returns
        ``(out (b,n,dim), cache_k, cache_v)``.
        """
        n = x.shape[1]
        size = (cache_k[0] if self.quantize_cache else cache_k).shape[2]
        if n > size:
            # window-sized ring-buffer cache: only the last `size` rows
            # survive (valid when the cache covers the lookback window —
            # decode steps never look further back than that).  Not an
            # assert: under python -O a silently-truncated global-attention
            # cache would produce wrong logits with no error
            if (self._eff_lookback() is None
                    or size < self._eff_lookback()):
                raise ValueError(
                    f"prefill: prompt ({n}) longer than the cache ({size}) "
                    f"is only valid for a window-sized cache covering "
                    f"max_lookback_seq_len ({self._eff_lookback()})"
                )
        q, k, v, gate = self._project_qkv(x)
        q, k = self._rotate(q, k, jnp.arange(n))

        ring = self.use_ring and not self.force_regular_attn and self._ring_size() > 1
        if ring:
            out = self._ring_prefill_attend(q, k, v)
        else:
            out = self._attend(q, k, v, causal=True)
        with jax.named_scope("attn/cache_write"):
            if n > size:
                # keep the last `size` rows, rolled into ring-buffer slot
                # order: cache[s] = row at position p ≡ s (mod size)
                k_rows = jnp.roll(k[:, :, n - size:], n % size, axis=2)
                v_rows = jnp.roll(v[:, :, n - size:], n % size, axis=2)
            else:
                k_rows, v_rows = k, v  # slots [0, n) are the positions [0, n)
            if self.quantize_cache:
                # attention over the prompt ran on the exact K/V above; only
                # the cache (what later decode steps read) is quantized
                cache_k, cache_v = self._quantized_write(
                    cache_k, cache_v, k_rows, v_rows, 0
                )
            else:
                zeros = (0, 0, 0, 0)
                cache_k = lax.dynamic_update_slice(cache_k, k_rows.astype(cache_k.dtype), zeros)
                cache_v = lax.dynamic_update_slice(cache_v, v_rows.astype(cache_v.dtype), zeros)

        if not ring and self._use_pallas():
            # tie the cache write to its layer: free of it, XLA schedules
            # every layer's write at the program's end and carries all
            # their K/V rows that far, which around the kernel's fixed
            # layouts grows the heap (4.07 for 3.74 GiB of temporaries at
            # 4 x 8,192 tokens x 5 layers: PERF.md section 6, PR 29).
            # Values are untouched
            out, cache_k, cache_v = lax.optimization_barrier(
                (out, cache_k, cache_v))
        return self._project_out(out, gate), cache_k, cache_v

    def _ring_prefill_attend(self, q, k, v):
        """Ring attention over the prompt in contiguous (cache) layout.

        Rotary is already applied (global positions), so the shard_map core
        calls the ring collective directly; right-padding to the ring size
        is invisible under causal masking (pad keys sit after every real
        query) and padded output rows are sliced off.
        """
        if is_factored(self.mesh):
            raise NotImplementedError(
                "ring-sharded prefill/decode runs on a plain (data, seq) "
                "mesh; the factored hybrid mesh is a training/forward "
                "layout — decode with create_mesh(ring_size=...)"
            )
        ring_size = self._ring_size()
        n = q.shape[2]
        pad = (-n) % ring_size
        if pad:
            widths = [(0, 0), (0, 0), (0, pad), (0, 0)]
            q = jnp.pad(q, widths)
            k = jnp.pad(k, widths)
            v = jnp.pad(v, widths)
        n_local = (n + pad) // ring_size
        bucket = max(min(self.bucket_size, n_local), 1)
        while n_local % bucket:
            bucket -= 1

        bidirectional = self._bidirectional(n_local)
        max_ring_passes = None
        window = None
        if self._eff_lookback() is not None:
            window = self._eff_lookback()
            max_ring_passes = math.ceil((window - 1) / n_local) + 1

        def core(q, k, v):
            return ring_flash_attention(
                q, k, v, None, SEQ_AXIS,
                True, False,  # causal, contiguous (non-striped) layout
                bucket, max_ring_passes, window,
                self.softclamp_value, None,
                self._kernel_impl(),
                bidirectional, self.ring_dkv_dtype,
                counter_rotate=self.ring_counter_rotate,
                hop_compression=self.ring_hop_compression,
            )

        qspec = P(data_partition(self.mesh), None, SEQ_AXIS, None)
        out = compat.shard_map(
            core,
            mesh=self.mesh,
            in_specs=(qspec, qspec, qspec),
            out_specs=qspec,
            check_vma=not self._use_pallas(),
        )(q, k, v)
        return out[:, :, :n]

    def _ring_decode(self, q, k, v, cache_k, cache_v, pos):
        if is_factored(self.mesh):
            raise NotImplementedError(
                "ring-sharded decode runs on a plain (data, seq) mesh; the "
                "factored hybrid mesh is a training/forward layout — decode "
                "with create_mesh(ring_size=...)"
            )
        ring_size = self._ring_size()
        quant = self.quantize_cache
        n_local = (cache_k[0] if quant else cache_k).shape[2] // ring_size

        def core(q, k, v, cache_k, cache_v, pos):
            rank = lax.axis_index(SEQ_AXIS)
            owner = pos // n_local
            local_pos = pos % n_local

            def write(c, new):
                with jax.named_scope("attn/cache_write"):
                    return lax.dynamic_update_slice_in_dim(
                        c, new.astype(c.dtype), local_pos, axis=2
                    )

            if quant:
                kq, ks, vq, vs = quantize_kv_cache(k, v)
                cache_k = lax.cond(
                    rank == owner,
                    lambda c: (write(c[0], kq), write(c[1], ks)),
                    lambda c: c, cache_k,
                )
                cache_v = lax.cond(
                    rank == owner,
                    lambda c: (write(c[0], vq), write(c[1], vs)),
                    lambda c: c, cache_v,
                )
            else:
                cache_k = lax.cond(
                    rank == owner, lambda c: write(c, k), lambda c: c, cache_k
                )
                cache_v = lax.cond(
                    rank == owner, lambda c: write(c, v), lambda c: c, cache_v
                )
            idx = rank * n_local + jnp.arange(n_local)
            kv_mask = self._decode_mask(idx, pos, q.shape[0])
            if quant:
                kvq = QuantizedKV(*cache_k, *cache_v)
                # impl="xla" dequantizes inside tree_attn_decode
                out = tree_attn_decode(
                    q, None, None, kv_mask,
                    axis_name=SEQ_AXIS,
                    softclamp_value=self.softclamp_value,
                    impl=None if self._use_pallas() else "xla",
                    kv_quantized=kvq,
                )
            else:
                out = tree_attn_decode(
                    q, cache_k, cache_v, kv_mask,
                    axis_name=SEQ_AXIS,
                    softclamp_value=self.softclamp_value,
                    impl="pallas" if self._use_pallas() else "xla",
                )
            return out, cache_k, cache_v

        cspec = P(data_partition(self.mesh), None, SEQ_AXIS, None)
        sspec = P(data_partition(self.mesh), None, SEQ_AXIS)
        cache_spec = (cspec, sspec) if quant else cspec
        rep = P(data_partition(self.mesh), None, None, None)
        return compat.shard_map(
            core,
            mesh=self.mesh,
            in_specs=(rep, rep, rep, cache_spec, cache_spec, P()),
            out_specs=(rep, cache_spec, cache_spec),
            check_vma=not self._use_pallas(),
        )(q, k, v, cache_k, cache_v, pos)


class LatentAttention(RingAttention):
    """Latent attention (MLA, after the public DeepSeek-V3 block): queries
    through a ``q_latent_dim`` bottleneck with a norm, keys and values
    through one ``kv_latent_dim`` latent a position with a norm, plus
    ``qk_rope_dim`` rotary dimensions a position that all heads share; a
    head's q.k width is ``dim_head = qk_nope_dim + qk_rope_dim`` and its
    value width ``v_dim``.

    One layer, two arithmetic forms.  ``__call__`` and ``prefill`` compute
    the *expanded* form: the latent is projected up to per-head keys and
    values and the layer is ordinary multi-head attention, so every path
    ``RingAttention`` has (the oracle, the XLA scan, the Pallas kernel, the
    sequence-parallel schemes) applies as it stands.  They take one width
    for q, k and v, so ``v`` goes in zero-padded to ``dim_head`` and the
    output's padding is dropped before ``to_out`` (ROADMAP R4 is a kernel
    that takes the two widths).  ``decode_step`` computes the *absorbed*
    form over the latent cache: ``W_UK`` is folded into the query and
    ``W_UV`` applied to the attended latents, so no key or value is ever
    expanded or cached (``ops/pallas_latent.py``).

    The cache, one pair a layer: ``cache_k`` the rotated positional keys
    transposed ``(b, 1, qk_rope_dim, capacity)``, ``cache_v`` the normed
    latents ``(b, 1, capacity, kv_latent_dim)``, which serve as keys and as
    values: ``kv_latent_dim + qk_rope_dim`` values a position, stored once
    (why two arrays: ``ops/pallas_latent.py``).

    The softmax scale ``dim_head ** -0.5 * m * m`` (``m`` the yarn
    ``mscale``) reaches every path by folding ``m * m`` into the queries.
    """

    q_latent_dim: int = 0
    kv_latent_dim: int = 0
    qk_nope_dim: int = 0
    qk_rope_dim: int = 0
    v_dim: int = 0
    rope_scaling: YarnScaling | None = None

    def setup(self):
        if (self.quantize_cache or self.qk_norm or self.out_gate
                or self.softmax_scale is not None):
            raise ValueError(
                "LatentAttention: a latent layer has no int8 cache, per-head "
                "q/k norm, output gate or softmax_scale")
        h = self.heads

        def dense(width):
            return nn.Dense(width, use_bias=False, dtype=self.dtype)

        self.prenorm = RMSNorm(self.dim, self.norm_eps)
        self.to_q_latent = dense(self.q_latent_dim)
        self.q_latent_norm = RMSNorm(self.q_latent_dim, self.norm_eps)
        self.to_q = dense(h * self.dim_head)
        self.to_kv_latent = dense(self.kv_latent_dim + self.qk_rope_dim)
        self.kv_latent_norm = RMSNorm(self.kv_latent_dim, self.norm_eps)
        # per head (k_nope | v), W_UK and W_UV side by side: a bare matrix,
        # because the absorbed form multiplies by its two halves apart and
        # the prefill expands a session at a time inside ``lax.map``
        self.to_kv = self.param(
            "to_kv", nn.initializers.lecun_normal(),
            (self.kv_latent_dim, h * (self.qk_nope_dim + self.v_dim)))
        self.to_out = dense(self.dim)

    def _prenormed(self, x):
        # the pre-norm feeds both bottlenecks: it stands where
        # RingAttention's does, in front of the first products
        with jax.named_scope("attn/qkv_proj"):
            return self.prenorm(x)

    def _query_latents(self, normed):
        with jax.named_scope("attn/latent_q"):
            return self.q_latent_norm(self.to_q_latent(normed))

    def _queries(self, c_q, w=None):
        """``(b, h, n, dim_head)`` from the normed query latents: per head
        (q_nope | q_rope), unrotated, the yarn part of the softmax scale
        folded in.  ``w``: ``to_q``'s matrix, for a caller under
        ``lax.map``, where no variable may be read."""
        with jax.named_scope("attn/latent_q"):
            b, n, _ = c_q.shape
            q = self.to_q(c_q) if w is None else jnp.dot(c_q, w)
            q = q.reshape(b, n, self.heads, self.dim_head).transpose(0, 2, 1, 3)
            m = 1.0 if self.rope_scaling is None else (
                self.rope_scaling.softmax_mscale)
            return q if m == 1.0 else q * jnp.asarray(m * m, q.dtype)

    def _latents(self, normed, positions=None):
        """The normed latents ``(b, n, kv_latent_dim)`` and the positional
        keys ``(b, 1, n, qk_rope_dim)``, rotated to ``positions`` (None: as
        they are, for a caller whose path rotates)."""
        with jax.named_scope("attn/latent_kv"):
            c, k_r = jnp.split(self.to_kv_latent(normed),
                               [self.kv_latent_dim], axis=-1)
            k_r = k_r[:, None]
            if positions is not None:
                k_r = self._rotate_rope(k_r, positions)
            return self.kv_latent_norm(c), k_r

    def _write(self, cache_k, cache_v, c, k_r, at):
        """The caches with the latents ``c`` and the rotated ``k_r`` of
        consecutive positions written from position ``at``."""
        with jax.named_scope("attn/latent_kv"):
            return (
                lax.dynamic_update_slice(
                    cache_k, k_r.swapaxes(2, 3).astype(cache_k.dtype),
                    (0, 0, 0, at)),
                lax.dynamic_update_slice(
                    cache_v, c[:, None].astype(cache_v.dtype), (0, 0, at, 0)))

    def _rotate_rope(self, x, positions):
        """``x: (..., n, width)`` with its last ``qk_rope_dim`` columns
        rotated to ``positions``: the shared ``k_r`` alone, or a whole head
        in one pass, whose ``qk_nope_dim`` columns before them pass as they
        are (cos 1, sin 0)."""
        with jax.named_scope("attn/rotary"):
            freqs = rotary_freqs(positions, self.qk_rope_dim,
                                 self.rotary_theta, self.rope_scaling)
            cos, sin = jnp.cos(freqs), jnp.sin(freqs)
            nope = x.shape[-1] - self.qk_rope_dim
            if nope:
                cos = jnp.pad(cos, [(0, 0), (nope, 0)], constant_values=1.0)
                sin = jnp.pad(sin, [(0, 0), (nope, 0)])
            x = turn(x, cos, sin, self.qk_rope_dim)
            m = 1.0 if self.rope_scaling is None else (
                self.rope_scaling.rotation_mscale)
            if m == 1.0:
                return x
            scale = np.r_[np.ones(nope), np.full(self.qk_rope_dim, m)]  # ra: allow(RA009 a constant of the static widths, never traced)
            return x * jnp.asarray(scale, x.dtype)

    def _rotate(self, q, k, positions):
        """The rotary columns of ``(b, h, n, dim_head)`` queries and
        expanded keys (the last ``qk_rope_dim``) rotated to ``positions``."""
        return (self._rotate_rope(q, positions),
                self._rotate_rope(k, positions))

    def _expand(self, c, k_r):
        """Per-head keys ``(b, h, n, dim_head)`` (k_nope | the shared
        ``k_r``) and values zero-padded to the same width, from the latents
        ``(b, n, kv_latent_dim)`` and ``k_r: (b, 1, n, qk_rope_dim)``.
        (``to_kv`` is an array since ``setup``, not a variable read here, so
        the prefill may call this under ``lax.map``.)"""
        with jax.named_scope("attn/expand"):
            b, n, _ = c.shape
            h = self.heads
            kv = jnp.dot(c, self.to_kv.astype(c.dtype)).reshape(
                b, n, h, self.qk_nope_dim + self.v_dim).transpose(0, 2, 1, 3)
            k_n, v = jnp.split(kv, [self.qk_nope_dim], axis=-1)
            k = jnp.concatenate([k_n, jnp.broadcast_to(
                k_r.astype(k_n.dtype), (b, h, n, self.qk_rope_dim))], axis=-1)
            v = jnp.pad(v, [(0, 0)] * 3 + [(0, self.dim_head - self.v_dim)])
            return k, v

    def _project_qkv(self, x: jax.Array):
        """The forward's expanded q, k and v; the caller's path rotates
        (``_rotate``), so ``k_r`` is expanded unrotated here."""
        normed = self._prenormed(x)
        k, v = self._expand(*self._latents(normed))
        return self._queries(self._query_latents(normed)), k, v, None

    def _project_out(self, out: jax.Array, gate=None):
        with jax.named_scope("attn/heads"):
            out = out[..., :self.v_dim]
        return super()._project_out(out, None)

    def _off_the_ring(self, call: str) -> None:
        if (self.use_ring and not self.force_regular_attn
                and self._ring_size() > 1):
            raise NotImplementedError(
                f"LatentAttention.{call}: a latent cache is not sharded over "
                f"a sequence mesh yet (ROADMAP R5); decode a latent model "
                f"with mesh=None or use_ring=False")

    def prefill(self, x, cache_k, cache_v):
        """The prompt in one causal pass of the expanded form; the cache
        gets the prompt's latents and rotated positional keys at [0, n)."""
        self._off_the_ring("prefill")
        n, size = x.shape[1], cache_v.shape[2]
        if n > size:
            raise ValueError(
                f"prefill: prompt ({n}) longer than the latent cache ({size})")
        normed = self._prenormed(x)
        positions = jnp.arange(n)
        c, k_r = self._latents(normed, positions)
        cache_k, cache_v = self._write(cache_k, cache_v, c, k_r, 0)
        c_q = self._query_latents(normed)
        w_q = self.to_q.variables["params"]["kernel"].astype(c.dtype)

        def one_session(args):
            # (n, q_latent_dim), (n, kv_latent_dim), (1, n, qk_rope_dim)
            c_q, c, k_r = (a[None] for a in args)
            q = self._rotate_rope(self._queries(c_q, w_q), positions)
            k, v = self._expand(c, k_r)
            out = self._attend(q, k, v, causal=True)
            with jax.named_scope("attn/heads"):
                return out[0, ..., :self.v_dim]

        # a session at a time: expanded to every head, 16,384 tokens of q,
        # of k and of v are 1 GB each (192 columns pad to 256 lanes)
        out = lax.map(one_session, (c_q, c, k_r))
        if self._use_pallas():
            # as RingAttention.prefill: tie the cache write to its layer
            out, cache_k, cache_v = lax.optimization_barrier(
                (out, cache_k, cache_v))
        return self._project_out(out), cache_k, cache_v

    def decode_step(self, x, cache_k, cache_v, pos):
        """One token in the absorbed form: this position's latent and
        rotated ``k_r`` are written at ``pos``, then every head attends
        positions ``[0, pos]`` of the latents themselves."""
        self._off_the_ring("decode_step")
        b, h = x.shape[0], self.heads
        dn, dv, dl = self.qk_nope_dim, self.v_dim, self.kv_latent_dim
        normed = self._prenormed(x)
        position = jnp.reshape(pos, (1,))
        c, k_r = self._latents(normed, position)
        cache_k, cache_v = self._write(cache_k, cache_v, c, k_r, pos)
        q = self._queries(self._query_latents(normed))
        q_n, q_r = jnp.split(q[:, :, 0], [dn], axis=-1)
        q_r = self._rotate_rope(q_r[:, :, None], position)[:, :, 0]
        with jax.named_scope("attn/absorb"):
            w = self.to_kv.astype(q_n.dtype)
            w_uk, w_uv = jnp.split(w.reshape(dl, h, dn + dv), [dn], axis=-1)
            scale = self.dim_head ** -0.5
            q_lat = (jnp.einsum("bhn,lhn->bhl", q_n, w_uk,
                                preferred_element_type=jnp.float32)
                     * scale).astype(q_n.dtype)
            q_r = (q_r.astype(jnp.float32) * scale).astype(q_n.dtype)
        kv_mask = self._buffer_mask(cache_v.shape[2], pos, b)
        if not self.is_initializing():
            self.sow(COUNTERS, "latent_cache_bytes_read",
                     kv_mask.sum() * (dl + self.qk_rope_dim)
                     * cache_v.dtype.itemsize,
                     init_fn=lambda: None, reduce_fn=lambda _, new: new)
        if self._use_pallas():
            o_lat = pallas_flash_decode_latent(
                q_lat, q_r, cache_v, cache_k, kv_mask)
        else:
            o_lat = latent_decode_attention(
                q_lat, q_r, cache_v, cache_k, kv_mask).astype(q_n.dtype)
        with jax.named_scope("attn/absorb"):
            out = jnp.einsum("bhl,lhv->bhv", o_lat, w_uv,
                             preferred_element_type=jnp.float32)
            out = out.astype(q_n.dtype).reshape(b, 1, h * dv)
        with jax.named_scope("attn/out_proj"):
            return self.to_out(out), cache_k, cache_v
