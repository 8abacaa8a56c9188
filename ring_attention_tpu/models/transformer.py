"""RingTransformer: end-to-end causal LM over a sharded sequence.

TPU-native equivalent of the reference's ``RingTransformer``
(ref ``ring_attention.py:488-685``): token embedding, depth x
(RingAttention + FeedForward) residual blocks, final RMSNorm + logits, and
autoregressive cross-entropy with label auto-shift and pad-label masking
(ref ``ring_attention.py:599-615``).

Sharding is decided once at the model top (pad -> stripe -> sharding
constraint) and the attention layers run pre-sharded (the reference
similarly passes ``auto_shard_seq=False`` down to layers,
ref ``ring_attention.py:565``).  Per-layer ``max_lookback_seq_len`` gives
local -> global attention over depth (ref ``ring_attention.py:546-561``).

Beyond the reference: an incremental decoding path — ``init_cache`` /
``decode_step`` / ``generate`` — running tree-attention decoding against a
ring-sharded KV cache (the reference only ships the standalone collective,
ref ``tree_attn_decoding.py``).
"""

from __future__ import annotations

import functools
from typing import Any

import flax.linen as nn
from flax.linen.dtypes import promote_dtype
import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..ops.attention import PAD_SEGMENT_ID
from ..parallel.mesh import (
    SEQ_AXIS,
    ULYSSES_AXIS,
    data_partition,
    is_factored,
    seq_partition,
    seq_world,
)
from ..parallel.sharding import (
    layout_for,
    layout_permute,
    layout_unpermute,
    pad_to_multiple,
)
from ..utils.validate import check_tokens_input
from .attention import LatentAttention, RingAttention
from .. import masks as mask_algebra
from .config import ModelConfig
from .layers import FeedForward, GatedFeedForward, RMSNorm
from .moe import RoutedFeedForward
from .remat import REMAT_POLICIES, resolve_remat_policy
from .ssm import Mamba2Mixer

PROBES = "probes"  # the flax collection the stack walker sows into


def _nll_parts(
    logits: jax.Array,  # (..., vocab), any float dtype
    labels: jax.Array,  # (...)
    valid: jax.Array,  # (...) bool
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """``_position_nll`` with the two float32 arrays its gradient is made
    of: ``(nll, logits, logsumexp)``."""
    # not a flax method, so it has no scope of its own: name it for the
    # profiler's layer table (utils/profiling.py STAGES)
    with jax.named_scope("loss/nll"):
        lf = logits.astype(jnp.float32)
        lse = jax.nn.logsumexp(lf, axis=-1)
        chosen = jnp.take_along_axis(
            lf, jnp.where(valid, labels, 0)[..., None], axis=-1
        )[..., 0]
        return jnp.where(valid, lse - chosen, 0.0), lf, lse


def _position_nll(
    logits: jax.Array,  # (..., vocab), any float dtype
    labels: jax.Array,  # (...)
    valid: jax.Array,  # (...) bool
) -> jax.Array:
    """Per-position negative log likelihood, zero where invalid.

    ``nll = logsumexp - chosen logit`` in f32: the same value as
    ``log_softmax`` + gather without materializing a second
    ``(..., vocab)`` f32 array.  THE loss math shared by the dense and
    chunked CE paths — the chunked path's value-identity guarantee
    depends on both calling exactly this."""
    return _nll_parts(logits, labels, valid)[0]


def _head_logits(x_c: jax.Array, kernel: jax.Array, dtype):
    """One chunk's logits as ``nn.Dense(use_bias=False, dtype=dtype)``
    makes them (both operands in the compute dtype, no wider output), and
    the two operands as they entered the product."""
    x_c, w = promote_dtype(x_c, kernel, dtype=dtype)
    logits = lax.dot_general(x_c, w, (((x_c.ndim - 1,), (0,)), ((), ())))
    return logits, x_c, w


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _chunked_nll_sum(
    xs: jax.Array,  # (nc, ..., c, dim) chunked features
    kernel: jax.Array,  # (dim, vocab) the head's parameter
    labels: jax.Array,  # (nc, ..., c)
    valid: jax.Array,  # (nc, ..., c) bool
    dtype,  # the head's compute dtype (None: the operands' own)
) -> jax.Array:
    """Summed nll of every chunk: a scan with one product a chunk.  This
    body is the call that is not differentiated; under ``jax.grad`` the
    rules below run instead and make the gradient in the same scan."""

    def step(total, inp):
        x_c, lab_c, val_c = inp
        logits, _, _ = _head_logits(x_c, kernel, dtype)
        return total + _position_nll(logits, lab_c, val_c).sum(), None

    total, _ = lax.scan(step, jnp.float32(0.0), (xs, labels, valid))
    return total


def _chunked_nll_sum_fwd(xs, kernel, labels, valid, dtype):
    """The loss and, chunk by chunk from the logits it already holds, the
    whole gradient: ``dlogits = (softmax - onehot) * valid`` depends on
    nothing but the chunk, so ``dx`` is a stacked output and ``dW`` a
    float32 carry.  Three vocabulary-sized products a chunk."""
    lead = tuple(range(xs.ndim - 2))  # a chunk's axes but the features'

    def step(carry, inp):
        total, dw = carry
        x_c, lab_c, val_c = inp
        logits, x_c, w = _head_logits(x_c, kernel, dtype)
        nll, lf, lse = _nll_parts(logits, lab_c, val_c)
        with jax.named_scope("loss/grad"):
            onehot = lab_c[..., None] == jnp.arange(lf.shape[-1])
            dlogits = jnp.where(
                val_c[..., None], jnp.exp(lf - lse[..., None]) - onehot, 0.0)
            # rounded where autodiff rounds the cotangent of the logits
            dlogits = dlogits.astype(logits.dtype)
            dx_c = lax.dot_general(
                dlogits, w, (((dlogits.ndim - 1,), (1,)), ((), ())))
            dw = dw + lax.dot_general(
                x_c, dlogits, ((lead, lead), ((), ())),
                preferred_element_type=jnp.float32)
        return (total + nll.sum(), dw), dx_c.astype(xs.dtype)

    init = (jnp.float32(0.0), jnp.zeros(kernel.shape, jnp.float32))
    (total, dw), dx = lax.scan(step, init, (xs, labels, valid))
    return total, (dx, dw.astype(kernel.dtype))


def _chunked_nll_sum_bwd(dtype, residuals, g):
    dx, dw = residuals
    # integer labels and the boolean mask take no cotangent
    return (g * dx).astype(dx.dtype), (g * dw).astype(dw.dtype), None, None


_chunked_nll_sum.defvjp(_chunked_nll_sum_fwd, _chunked_nll_sum_bwd)


class RingTransformer(nn.Module):
    num_tokens: int
    dim: int
    depth: int
    causal: bool = False
    # mask-algebra expression (ring_attention_tpu.masks), forwarded to
    # every attention layer: ``causal=True`` is sugar for
    # ``mask=Causal()``; a tuple selects per layer (mirroring
    # max_lookback_seq_len — e.g. local-window layers below a global
    # one).  Certified at trace time per layer; mutually exclusive with
    # causal=True and max_lookback_seq_len (see RingAttention.mask)
    mask: mask_algebra.Mask | tuple[mask_algebra.Mask | None, ...] | None = None
    heads: int = 8
    dim_head: int = 64
    kv_heads: int | None = None
    bucket_size: int = 512
    striped: bool = False
    use_ring: bool = True
    force_regular_attn: bool = False
    rotary: bool = True
    softclamp_value: float | None = None
    # int -> same lookback every layer; tuple -> per layer (None = global)
    max_lookback_seq_len: int | tuple[int | None, ...] | None = None
    ff_mult: int = 4
    ignore_index: int = -1
    auto_shard: bool = True
    mesh: Mesh | None = None
    use_pallas: bool = False
    # kernel-path selection with graceful degradation, forwarded to every
    # RingAttention layer (see models/attention.py ``impl``): "fused" |
    # "pallas" | "xla" | "auto"; None keeps the explicit use_pallas switch
    impl: str | None = None
    # see RingAttention.pallas_head_chunks (program-size escape hatch)
    pallas_head_chunks: int | None = None
    # see RingAttention.quantize_cache (int8 decode KV cache)
    quantize_cache: bool = False
    # "ring" | "zigzag" | "ulysses" | "hybrid" (Ulysses x Ring factored
    # mesh, create_mesh(ulysses_size=U) — see docs/hybrid_parallelism.md)
    sequence_parallel: str = "ring"
    ring_bidirectional: bool = False  # see RingAttention.ring_bidirectional
    ring_dkv_dtype: str | None = None  # see RingAttention.ring_dkv_dtype
    # see RingAttention.ring_counter_rotate / ring_hop_compression
    ring_counter_rotate: bool = False
    ring_hop_compression: str | None = None
    # see RingAttention.compute_dtype: "int8" runs every layer's forward
    # QK^T/PV on int8 operands (pallas path, ring/hybrid/local), backward
    # bf16 from exact residuals (docs/precision.md)
    compute_dtype: str | None = None
    # rematerialize each block in backward: trades recompute for activation
    # memory — the standard recipe for quarter-million-token training.
    # NOTE: requires the train step to be jit-compiled (jax.checkpoint over
    # shard_map has no eager path)
    remat: bool = False
    # remat refinement: which intermediates each rematted block may KEEP
    # instead of recomputing — a name from models/remat.py REMAT_POLICIES
    # ("save_attn" saves flash_out/flash_lse so the backward skips the
    # O(n^2) ring scan; "save_ffn_inputs" elides the FFN norm recompute;
    # "offload_attn" parks the attn residuals in host memory; see
    # docs/memory.md for the full table).  A tuple selects per layer
    # (mirroring max_lookback_seq_len); None = plain full-block remat.
    remat_policy: str | tuple[str | None, ...] | None = None
    # blockwise feedforward (Ring Attention §3, arXiv 2310.01889): run each
    # FeedForward as a rematted scan over sequence chunks of this size so
    # the (seq, mult*dim) intermediate never exists at full sequence
    # extent — the memory-axis twin of loss_chunk_size (docs/memory.md).
    # Chunks split WITHIN each sequence shard, so the scan adds zero
    # collectives (pinned: analysis/contracts.py "blockwise_ffn" row).
    # None = dense FFN; shard lengths that don't divide are padded.
    ff_chunk_size: int | None = None
    # chunked cross-entropy: compute the loss as a lax.scan over sequence
    # chunks of this size (it makes its own gradient: _chunked_nll_sum),
    # so at most (b, chunk, vocab) logits ever materialize — at a real LM
    # vocab the full logits tensor is the long-context memory wall.  None = single dense logits+CE (fine for
    # small vocab).  The full memory story (why, when, and how this
    # composes with ff_chunk_size / remat_policy / offload) lives in
    # docs/memory.md.
    loss_chunk_size: int | None = None
    dtype: jnp.dtype | None = None
    # the frozen description the stack is built from (models/config.py):
    # set by ``from_config``; None builds the uniform block of the keyword
    # arguments above (``_config``)
    config: ModelConfig | None = None

    @classmethod
    def from_config(cls, config: ModelConfig, **options) -> "RingTransformer":
        """The model ``config`` describes, causal; ``options`` are how it is
        run (mesh, kernels, remat, chunking, dtype), not what it computes."""
        return cls(
            num_tokens=config.num_tokens, dim=config.dim, depth=config.depth,
            heads=config.heads, dim_head=config.dim_head,
            kv_heads=config.kv_heads, causal=True, config=config, **options)

    def _config(self) -> ModelConfig:
        if self.config is not None:
            return self.config
        return ModelConfig.uniform(
            num_tokens=self.num_tokens, dim=self.dim, depth=self.depth,
            heads=self.heads, dim_head=self.dim_head, kv_heads=self.kv_heads,
            ffn_dim=self.dim * self.ff_mult, windows=self._lookbacks(),
            rotary=self.rotary)

    def setup(self):
        # a negative chunk size used to surface as an obscure shape error
        # deep inside pad_to_multiple, and 0 silently disabled chunking via
        # the falsy check in __call__ — validate once, loudly, up front
        if self.loss_chunk_size is not None and self.loss_chunk_size <= 0:
            raise ValueError(
                f"RingTransformer: loss_chunk_size must be None or a "
                f"positive int, got {self.loss_chunk_size!r} (None disables "
                f"chunking; 0 would silently disable it, a negative value "
                f"breaks padding)"
            )
        if self.ff_chunk_size is not None and self.ff_chunk_size <= 0:
            raise ValueError(
                f"RingTransformer: ff_chunk_size must be None or a positive "
                f"int, got {self.ff_chunk_size!r} (None disables the "
                f"blockwise feedforward; any positive size works — shard "
                f"lengths that don't divide are padded)"
            )
        policies = self._remat_policies()
        cfg = self._config()
        self.embed = nn.Embed(self.num_tokens, self.dim, dtype=self.dtype)
        # flax-lifted remat (NOT raw jax.checkpoint: param creation during
        # init is a side effect that would leak tracers out of the
        # checkpointed trace); one lifted class per layer so the policy is
        # per-layer selectable
        ffn = {"gelu": FeedForward, "gated": GatedFeedForward,
               "routed": RoutedFeedForward}
        # a latent model's attention layers, and the widths only they take
        latent = dict(
            q_latent_dim=cfg.q_latent_dim, kv_latent_dim=cfg.kv_latent_dim,
            qk_nope_dim=cfg.qk_nope_dim, qk_rope_dim=cfg.qk_rope_dim,
            v_dim=cfg.v_dim, rope_scaling=cfg.rope_scaling,
        ) if cfg.latent else {}
        attn_classes = [
            Mamba2Mixer if layer.mixer == "mamba"
            else LatentAttention if cfg.latent else RingAttention
            for layer in cfg.layers]
        ff_classes = [ffn[layer.ffn] for layer in cfg.layers]
        if self.remat:
            def rematted(classes):
                return [nn.remat(c, policy=resolve_remat_policy(p))
                        for c, p in zip(classes, policies)]

            attn_classes = rematted(attn_classes)
            ff_classes = rematted(ff_classes)
        self.attn_layers = [
            self._state_space(attn_cls, cfg) if layer.mixer == "mamba"
            else attn_cls(
                dim=self.dim,
                heads=self.heads,
                dim_head=self.dim_head,
                kv_heads=self.kv_heads,
                causal=self.causal,
                striped=self.striped and self._ring_size() > 1,
                bucket_size=self.bucket_size,
                use_ring=self.use_ring,
                force_regular_attn=self.force_regular_attn,
                rotary=layer.rotary,
                rotary_theta=cfg.rotary_theta,
                softclamp_value=self.softclamp_value,
                max_lookback_seq_len=layer.window,
                mask=layer_mask,
                auto_shard=False,  # sharded once at model top
                mesh=self.mesh,
                use_pallas=self.use_pallas,
                impl=self.impl,
                pallas_head_chunks=self.pallas_head_chunks,
                quantize_cache=self.quantize_cache,
                sequence_parallel=self.sequence_parallel,
                ring_bidirectional=self.ring_bidirectional,
                ring_dkv_dtype=self.ring_dkv_dtype,
                ring_counter_rotate=self.ring_counter_rotate,
                ring_hop_compression=self.ring_hop_compression,
                compute_dtype=self.compute_dtype,
                qk_norm=cfg.qk_norm,
                out_gate=cfg.attn_gate,
                norm_eps=cfg.norm_eps,
                dtype=self.dtype,
                softmax_scale=cfg.softmax_scale,
                **latent,
            )
            for attn_cls, layer, layer_mask in zip(
                attn_classes, cfg.layers, self._masks()
            )
        ]
        self.ff_layers = [
            self._feed_forward(ff_cls, layer.ffn, cfg)
            for ff_cls, layer in zip(ff_classes, cfg.layers)
        ]
        # a second norm on each sub-block's output (none: plain pre-norm)
        post = self.depth if cfg.sandwich_norm else 0
        self.post_attn_norms = [
            RMSNorm(self.dim, cfg.norm_eps) for _ in range(post)]
        self.post_ff_norms = [
            RMSNorm(self.dim, cfg.norm_eps) for _ in range(post)]
        self.final_norm = RMSNorm(self.dim, cfg.norm_eps)
        if not cfg.tie_embeddings:  # a tied head is the embedding: _logits
            self.to_logits = nn.Dense(
                self.num_tokens, use_bias=False, dtype=self.dtype)

    def _state_space(self, mixer_cls, cfg: ModelConfig):
        return mixer_cls(
            dim=self.dim, heads=cfg.ssm_heads, head_dim=cfg.ssm_head_dim,
            state=cfg.ssm_state, conv=cfg.ssm_conv, chunk=cfg.ssm_chunk,
            norm_eps=cfg.norm_eps, use_ring=self.use_ring,
            force_regular_attn=self.force_regular_attn, mesh=self.mesh,
            use_pallas=self.use_pallas, impl=self.impl, dtype=self.dtype)

    def _feed_forward(self, ff_cls, kind: str, cfg: ModelConfig):
        if kind == "gelu":
            return ff_cls(
                self.dim, cfg.ffn_dim // self.dim, dtype=self.dtype,
                chunk_size=self.ff_chunk_size,
                seq_shards=self._ring_size(),
                mesh=self.mesh if self.auto_shard else None,
            )
        if kind == "gated":
            return ff_cls(self.dim, cfg.ffn_dim, dtype=self.dtype,
                          norm_eps=cfg.norm_eps)
        return ff_cls(
            self.dim, cfg.expert_dim, num_experts=cfg.num_experts,
            experts_per_token=cfg.experts_per_token,
            experts_held=cfg.experts_held, first_expert=cfg.first_expert,
            shared_dim=cfg.shared_expert_dim, route_scale=cfg.route_scale,
            norm_eps=cfg.norm_eps, dtype=self.dtype,
            expert_groups=cfg.expert_groups,
            groups_per_token=cfg.groups_per_token,
            router_score=cfg.router_score)

    def _ring_size(self) -> int:
        """Total sequence-parallel world (both axes of a factored mesh)."""
        if self.mesh is None or not self.use_ring or self.force_regular_attn:
            return 1
        return seq_world(self.mesh)

    def _embed(self, tokens: jax.Array) -> jax.Array:
        x = self.embed(tokens)
        scale = self._config().embed_scale
        return x if scale == 1.0 else x * jnp.asarray(scale, x.dtype)

    def _logits(self, x: jax.Array) -> jax.Array:
        """The head: its own matrix, or the embedding's transpose where the
        configuration ties them, times ``logit_scale``."""
        cfg = self._config()
        logits = self.embed.attend(x) if cfg.tie_embeddings else (
            self.to_logits(x))
        scale = cfg.logit_scale
        return logits if scale == 1.0 else logits * jnp.asarray(
            scale, logits.dtype)

    def _blocks(self, x: jax.Array, attend) -> jax.Array:
        """The one walk over the stack, for the forward, the prefill and
        the decode step alike: ``attend(i, attn, x)`` is layer ``i``'s
        attention output, however that call reaches it.

        Each layer's attention output (what the kernels and the cache
        made, before any norm) is sown as ``probes/attn_out_<i>``: free
        unless the caller asks, ``apply(..., mutable=["probes"])``."""
        cfg = self._config()
        sandwich = cfg.sandwich_norm

        def residual(y, x):  # a sub-block's output joins the stream
            with jax.named_scope("block/residual"):
                if cfg.residual_scale != 1.0:
                    y = y * jnp.asarray(cfg.residual_scale, y.dtype)
                return y + x

        for i, (attn, ff) in enumerate(zip(self.attn_layers, self.ff_layers)):
            a = attend(i, attn, x)
            if not self.is_initializing():
                self.sow(PROBES, f"attn_out_{i}", a, init_fn=lambda: None,
                         reduce_fn=lambda _, new: new)
            x = residual(self.post_attn_norms[i](a) if sandwich else a, x)
            f = ff(x)
            x = residual(self.post_ff_norms[i](f) if sandwich else f, x)
        return self.final_norm(x)

    def _cached_blocks(self, x, cache, attend):
        """``_blocks`` for the two calls that carry a cache: ``attend(attn,
        x, k, v)`` returns the output and the layer's updated k and v."""
        new_k, new_v = [], []

        def through_cache(i, attn, x):
            a, ck, cv = attend(attn, x, cache["k"][i], cache["v"][i])
            new_k.append(ck)
            new_v.append(cv)
            return a

        x = self._blocks(x, through_cache)
        return x, {"k": new_k, "v": new_v}

    def _ulysses_size(self) -> int:
        if self.mesh is None or not is_factored(self.mesh):
            return 1
        return self.mesh.shape[ULYSSES_AXIS]

    def _layout(self) -> tuple[str, int]:
        """(scheme, factor) of the model-top sequence permutation — the
        shared derivation (``parallel/sharding.py::layout_for``), so the
        model top and every attention layer agree by construction."""
        return layout_for(
            self.sequence_parallel, self.striped, self._ring_size(),
            self._ulysses_size(),
        )

    def _lookbacks(self) -> tuple[int | None, ...]:
        lb = self.max_lookback_seq_len
        if not isinstance(lb, tuple):
            lb = (lb,) * self.depth
        assert len(lb) == self.depth
        return lb

    def _masks(self) -> tuple[mask_algebra.Mask | None, ...]:
        m = self.mask
        if not isinstance(m, tuple):
            m = (m,) * self.depth
        if len(m) != self.depth:
            raise ValueError(
                f"RingTransformer: mask tuple has {len(m)} entries for "
                f"depth {self.depth} (one mask per layer, or a single "
                f"mask for all layers)"
            )
        return m

    def _eff_causal(self) -> bool:
        """Whether every layer's attention is causal — the property the
        pad-mask synthesis and the zig-zag assert actually rely on
        (``causal=True`` or a mask whose kernel form is causal)."""
        if self.mask is None:
            return self.causal
        return all(
            mask_algebra.kernel_form(m).causal if m is not None
            else self.causal
            for m in self._masks()
        )

    def _remat_policies(self) -> tuple[str | None, ...]:
        """Per-layer remat-policy names, validated against the registry
        (models/remat.py) — a ValueError here lists every valid name, where
        the old ``assert`` vanished under ``python -O``."""
        p = self.remat_policy
        if not isinstance(p, tuple):
            p = (p,) * self.depth
        if len(p) != self.depth:
            raise ValueError(
                f"RingTransformer: remat_policy tuple has {len(p)} entries "
                f"for depth {self.depth} (one policy name per layer, or a "
                f"single name for all layers)"
            )
        for name in p:
            if name is not None and name not in REMAT_POLICIES:
                raise ValueError(
                    f"RingTransformer: unknown remat_policy {name!r}; valid "
                    f"policies: {', '.join(sorted(REMAT_POLICIES))} (or "
                    f"None for plain full-block remat)"
                )
        return p

    def __call__(
        self,
        tokens: jax.Array,
        mask: jax.Array | None = None,
        return_loss: bool = False,
        example_mask: jax.Array | None = None,
        segment_ids: jax.Array | None = None,
    ) -> jax.Array:
        """``tokens: (b, n)`` int32 -> logits ``(b, n, num_tokens)`` or scalar loss.

        ``example_mask: (b,)`` marks valid batch rows: the static-shape
        answer to the reference's variable per-rank batch
        (``all_gather_variable_dim``, ref ``distributed.py:58-84``,
        exercised by ``assert_attn.py:81-82``) — ragged data-parallel
        shards are padded to a common batch and the pad rows drop out of
        the loss here.

        ``segment_ids: (b, n)`` int document ids pack multiple documents
        into one sequence: every attention layer masks (and where possible
        skips) cross-document attention, and the loss drops positions
        whose label crosses a document boundary (the first token of each
        packed document is never predicted from the previous document).
        See ``docs/packing.md``.
        """
        check_tokens_input("RingTransformer", tokens)
        segment_same = None
        if return_loss:
            labels = tokens[:, 1:]
            tokens = tokens[:, :-1]
            if segment_ids is not None:
                # label at position i is token i+1: valid only when both
                # sit in the same document (no loss on each doc's first
                # token — it would be "predicted" from the previous doc)
                segment_same = segment_ids[:, 1:] == segment_ids[:, :-1]
                segment_ids = segment_ids[:, :-1]

        ring = self._ring_size()
        n_orig = tokens.shape[1]
        scheme, factor = self._layout()
        zigzag = self.sequence_parallel == "zigzag" and ring > 1
        if zigzag:
            assert self._eff_causal(), "zig-zag CP is causal-only"

        if ring > 1 and self.auto_shard:
            pad_mult = 2 * ring if zigzag else ring
            tokens, _ = pad_to_multiple(tokens, pad_mult)
            padded = tokens.shape[1] != n_orig
            if padded and mask is None and not self._eff_causal():
                # non-causal: real tokens must not attend to the pad slots,
                # so synthesize a key-padding mask (ref ring_attention.py:211-219);
                # causal needs none — pad sits after every real query and the
                # padded output rows are sliced off below.
                mask = jnp.arange(tokens.shape[1])[None, :] < n_orig
                mask = jnp.broadcast_to(mask, tokens.shape)
            tokens = layout_permute(tokens, scheme, factor)
            tokens = lax.with_sharding_constraint(
                tokens, NamedSharding(
                    self.mesh, P(data_partition(self.mesh), seq_partition(self.mesh))
                )
            )
            if mask is not None:
                mask, _ = pad_to_multiple(mask, pad_mult, value=False)
                mask = layout_permute(mask, scheme, factor)
            if segment_ids is not None:
                # pad slots get PAD_SEGMENT_ID: their own "document",
                # attending nothing real (models/attention.py does the
                # same for its per-layer padding)
                segment_ids, _ = pad_to_multiple(segment_ids, pad_mult,
                                                 value=PAD_SEGMENT_ID)
                segment_ids = layout_permute(segment_ids, scheme, factor)

        x = self._embed(tokens)
        if ring > 1 and self.auto_shard:
            x = lax.with_sharding_constraint(
                x, NamedSharding(
                    self.mesh, P(data_partition(self.mesh), seq_partition(self.mesh), None)
                )
            )

        x = self._blocks(x, lambda i, attn, x: attn(x, mask, segment_ids))

        if return_loss and self.loss_chunk_size:
            # the (b, n, vocab) logits never materialize, and under a
            # sequence-parallel mesh the (b, n, dim) features never leave
            # their shards: CE is position-local, so the labels (small,
            # replicated) take the tokens' padding and layout permutation
            # and the projection+CE scans chunks within each shard
            valid = self._valid_labels(labels, example_mask, segment_same)
            shards = 1
            if ring > 1 and self.auto_shard:
                shards = ring
                labels, _ = pad_to_multiple(labels, pad_mult)
                valid, _ = pad_to_multiple(valid, pad_mult, value=False)
                labels = layout_permute(labels, scheme, factor)
                valid = layout_permute(valid, scheme, factor)
            return self._chunked_ce(x, labels, valid, shards)

        logits = self._logits(x)

        if ring > 1 and self.auto_shard:
            logits = layout_unpermute(logits, scheme, factor)
            logits = logits[:, :n_orig]

        if not return_loss:
            return logits

        # Cross-entropy with ignore_index (ref ring_attention.py:664-673)
        valid = self._valid_labels(labels, example_mask, segment_same)
        nll = _position_nll(logits, labels, valid)
        return nll.sum() / jnp.maximum(valid.sum(), 1)

    def _valid_labels(
        self,
        labels: jax.Array,
        example_mask: jax.Array | None,
        segment_same: jax.Array | None = None,
    ) -> jax.Array:
        """Which (b, n) label slots count toward the loss — the ONE place
        the ignore_index / example_mask / packed-boundary rule lives (both
        CE paths use it).  ``segment_same`` marks labels living in the same
        document as the token predicting them."""
        valid = labels != self.ignore_index
        if example_mask is not None:
            valid = valid & example_mask[:, None]
        if segment_same is not None:
            valid = valid & segment_same
        return valid

    def _chunked_ce(
        self,
        x: jax.Array,  # (b, n, dim) final-norm features
        labels: jax.Array,  # (b, n), in the features' layout
        valid: jax.Array,  # (b, n) bool, from _valid_labels, same layout
        shards: int = 1,  # sequence shards the features arrive in
    ) -> jax.Array:
        """Cross-entropy as a scan over sequence chunks that makes its own
        gradient (``_chunked_nll_sum``).

        Peak memory is one chunk's logits ``(b, chunk, vocab)`` per device.
        Undifferentiated, the scan is one product a chunk.  Under
        ``jax.grad`` the same scan also makes ``dx`` (stacked) and the
        head's ``dW`` (a float32 carry) from the logits it holds, so
        nothing is recomputed and the backward only scales both by the
        incoming cotangent: three vocabulary-sized products a chunk.
        Value-identical to the dense path (same f32 lse-minus-chosen per
        position).  A ``jax.custom_vjp``: forward-mode differentiation
        (``jax.jvp``, a Hessian) through the chunked loss is refused; the
        dense path (``loss_chunk_size=None``) takes it.

        Chunks are taken WITHIN each sequence shard, as
        ``FeedForward._chunked`` takes them: ``(b, n, d) -> (nc, b,
        shards, c, d)`` with the shard axis held to the sequence mesh
        axes, so ``loss_chunk_size`` is rows per device per scan step and
        every device scores only its own ``n / shards`` rows.  The scalar
        carry and the head's weight gradient are partial sums the
        partitioner reduces across the mesh."""
        b, n, _ = x.shape
        n_local = n // shards
        # clamp: padding a short sequence UP to the chunk size would make
        # peak memory/compute strictly worse than the dense path
        c = min(self.loss_chunk_size, n_local)

        # off a mesh there is no shard axis: the program is the plain
        # (nc, b, c, ...) chunk scan
        lead = (b, shards) if shards > 1 else (b,)
        k = len(lead)

        def chunks(a, value=0):
            # (b, n, ...) -> (nc, b, shards, c, ...): the shard axis is
            # split out first, so the reshape/transpose stay local to each
            # device; a shard length c does not divide is padded up with
            # valid=False rows
            a = a.reshape(*lead, n_local, *a.shape[2:])
            a, _ = pad_to_multiple(a, c, axis=k, value=value)
            a = a.reshape(*lead, -1, c, *a.shape[k + 1:])
            a = jnp.moveaxis(a, k, 0)
            if shards > 1:
                # without the constraint the partitioner is free to gather
                # the whole sequence onto every device and replicate the scan
                a = lax.with_sharding_constraint(
                    a, NamedSharding(
                        self.mesh,
                        P(None, data_partition(self.mesh),
                          seq_partition(self.mesh), *(None,) * (a.ndim - 3)),
                    )
                )
            return a

        cfg = self._config()
        if cfg.tie_embeddings or cfg.logit_scale != 1.0:
            raise NotImplementedError(
                "RingTransformer: the chunked cross-entropy takes the head's "
                "own matrix; a tied or scaled head trains with "
                "loss_chunk_size=None")
        if self.is_initializing():
            self.to_logits(x[:, :1])  # makes the head's parameter, named as ever
        kernel = self.to_logits.variables["params"]["kernel"]
        total = _chunked_nll_sum(
            chunks(x), kernel, chunks(labels), chunks(valid, value=False),
            self.dtype)
        return total / jnp.maximum(valid.sum(), 1)

    # ------------------------------------------------------------------
    # Incremental decoding
    # ------------------------------------------------------------------

    def init_cache(self, batch: int, max_len: int) -> dict[str, Any]:
        """Fresh KV cache pytree; ``max_len`` must divide over the ring.

        With ``quantize_cache`` each per-layer entry is a
        ``(values int8, scales f32)`` tuple (see
        ``RingAttention.quantize_cache``); otherwise a dense array in the
        model dtype.  A latent layer's pair is its rotated positional keys,
        transposed, and its latents (``LatentAttention``): ``k`` is
        ``(batch, 1, qk_rope_dim, max_len)`` and ``v`` ``(batch, 1, max_len,
        kv_latent_dim)``, ``kv_latent_dim + qk_rope_dim`` values a position,
        and no expanded key or value.  A state-space layer's pair does not
        grow with ``max_len``: ``k`` is the convolution's tail ``(batch, 1,
        ssm_conv - 1, channels)`` and ``v`` its float32 state ``(batch,
        ssm_heads, ssm_head_dim, ssm_state)`` (``Mamba2Mixer``)."""
        ring = self._ring_size()
        assert max_len % max(ring, 1) == 0
        if ring > 1 and self.mesh is not None and is_factored(self.mesh):
            raise NotImplementedError(
                "ring-sharded decode runs on a plain (data, seq) mesh; the "
                "factored hybrid mesh is a training/forward layout — decode "
                "with create_mesh(ring_size=...)"
            )
        kvh = self.kv_heads or self.heads
        dtype = self.dtype or jnp.float32
        cfg = self._config()
        mamba = [layer.mixer == "mamba" for layer in cfg.layers]
        if any(mamba) and (ring > 1 or cfg.latent or self.quantize_cache):
            raise NotImplementedError(
                "init_cache: a state-space layer's state is not sharded over "
                "a sequence mesh yet (ROADMAP R7) and stands beside neither a "
                "latent nor an int8 cache; decode a hybrid model with "
                "mesh=None or use_ring=False")
        if cfg.latent:
            if ring > 1:
                raise NotImplementedError(
                    "init_cache: a latent cache is not sharded over a "
                    "sequence mesh yet (ROADMAP R5); decode a latent model "
                    "with mesh=None or use_ring=False")
            return {
                "k": [jnp.zeros((batch, 1, cfg.qk_rope_dim, max_len), dtype)
                      for _ in cfg.layers],
                "v": [jnp.zeros((batch, 1, max_len, cfg.kv_latent_dim), dtype)
                      for _ in cfg.layers],
            }

        def make_entry(size):
            shape = (batch, kvh, size, self.dim_head)
            if self.quantize_cache:
                entry = (
                    jnp.zeros(shape, jnp.int8),
                    jnp.zeros(shape[:3], jnp.float32),
                )
                if ring > 1:
                    entry = (
                        jax.device_put(entry[0], NamedSharding(
                            self.mesh, P(data_partition(self.mesh), None, SEQ_AXIS, None))),
                        jax.device_put(entry[1], NamedSharding(
                            self.mesh, P(data_partition(self.mesh), None, SEQ_AXIS))),
                    )
                return entry
            entry = jnp.zeros(shape, dtype)
            if ring > 1:
                entry = jax.device_put(entry, NamedSharding(
                    self.mesh, P(data_partition(self.mesh), None, SEQ_AXIS, None)))
            return entry

        # a windowed layer never reads past its window, so off the ring
        # its cache is a ring buffer of that many slots (writes land at
        # pos % size, RingAttention._buffer_mask tells which slots count);
        # the ring-sharded cache keeps absolute positions
        sizes = [
            max_len if layer.window is None or ring > 1
            else min(max_len, layer.window)
            for layer in cfg.layers
        ]
        # a state-space layer's pair is no rows: the convolution's tail and
        # one float32 state, whatever ``max_len`` is (models/ssm.py)
        channels = cfg.ssm_heads * cfg.ssm_head_dim + 2 * cfg.ssm_state
        tail = (batch, 1, cfg.ssm_conv - 1, channels)
        state = (batch, cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state)
        return {
            "k": [jnp.zeros(tail, dtype) if m else make_entry(s)
                  for m, s in zip(mamba, sizes)],
            "v": [jnp.zeros(state, jnp.float32) if m else make_entry(s)
                  for m, s in zip(mamba, sizes)],
        }

    def decode_step(
        self,
        token: jax.Array,  # (b,) int32 — token at position `pos`
        cache: dict[str, Any],
        pos: jax.Array,  # scalar int32
    ) -> tuple[jax.Array, dict[str, Any]]:
        """Next-token logits given the token at ``pos`` and the cache of
        positions ``[0, pos)``.  Returns ``(logits (b, vocab), new_cache)``."""
        x, cache = self._cached_blocks(
            self._embed(token[:, None]), cache,
            lambda attn, x, k, v: attn.decode_step(x, k, v, pos))
        return self._logits(x)[:, 0], cache

    def prefill(
        self,
        tokens: jax.Array,  # (b, n) int32
        cache: dict[str, Any],
    ) -> tuple[jax.Array, dict[str, Any]]:
        """One causal pass over the prompt, filling cache positions [0, n).

        Returns ``(last_logits (b, vocab), cache)`` — n flash-prefilled
        positions instead of n sequential decode steps."""
        x, cache = self._cached_blocks(
            self._embed(tokens), cache,
            lambda attn, x, k, v: attn.prefill(x, k, v))
        # the head reads one row a sequence, the last b rows with positions
        # leading: a slice of the (b, n, d) stream makes XLA:TPU rebuild the
        # residual sum in that shape from every layer's output, all held to
        # the last layer; and positions leading, a sequence-sharded stream
        # gives its last rows without being gathered
        b, n, d = x.shape
        x = jnp.swapaxes(x, 0, 1).reshape(n * b, d)[-b:]
        return self._logits(x), cache

    def generate(
        self,
        prompt: jax.Array,  # (b, n) int32
        max_len: int,
        num_steps: int,
        *,
        temperature: float = 0.0,
        top_k: int | None = None,
        top_p: float | None = None,
        rng: jax.Array | None = None,
    ) -> jax.Array:
        """One prefill pass over the prompt, then emit ``num_steps`` new
        tokens.  Returns ``(b, num_steps)``.

        The decode loop is a single ``nn.scan`` with the KV cache as carry,
        so the jitted program holds ONE decode-step body regardless of
        ``num_steps`` (compile time is O(1) in generation length, not O(n)
        as a Python loop of traced steps would be).

        ``temperature == 0.0`` (default) is greedy argmax; otherwise
        categorical sampling at the given temperature, truncated to the
        ``top_k`` highest-probability tokens and/or the ``top_p`` nucleus
        (smallest probability mass >= top_p), driven by ``rng`` (which must
        then be provided).
        """
        b, n = prompt.shape
        assert n >= 1, "generate needs a non-empty prompt"
        assert num_steps >= 1, "generate needs num_steps >= 1"
        assert n + num_steps - 1 <= max_len, "cache too small for prompt + steps"
        if temperature > 0.0 and rng is None:
            raise ValueError("generate: temperature > 0 needs an rng key")
        if temperature <= 0.0 and (top_k is not None or top_p is not None):
            raise ValueError(
                "generate: top_k/top_p need temperature > 0 (greedy mode "
                "would silently ignore them)"
            )
        if top_p is not None and not 0.0 < top_p <= 1.0:
            raise ValueError(f"generate: top_p must be in (0, 1], got {top_p}")
        if rng is None:  # unused (greedy) but keeps the carry pytree uniform
            rng = jax.random.PRNGKey(0)

        def sample(logits, key):
            if temperature <= 0.0:
                return jnp.argmax(logits, axis=-1).astype(jnp.int32)
            # temperature first: the nucleus must be computed on the SAME
            # distribution that is sampled (the standard ordering)
            logits = logits.astype(jnp.float32) / temperature
            if top_k is not None:
                kth = lax.top_k(logits, top_k)[0][..., -1:]
                logits = jnp.where(logits < kth, -jnp.inf, logits)
            if top_p is not None:
                # nucleus: keep the smallest prefix of descending-prob
                # tokens whose mass reaches top_p (always >= 1 token, since
                # each token's threshold tests the mass *before* it and
                # top_p > 0 is validated above)
                sorted_logits = jnp.sort(logits, axis=-1)[..., ::-1]
                probs = jax.nn.softmax(sorted_logits, axis=-1)
                mass_before = jnp.cumsum(probs, axis=-1) - probs
                cut = jnp.sum(mass_before < top_p, axis=-1, keepdims=True)
                thresh = jnp.take_along_axis(sorted_logits, cut - 1, axis=-1)
                logits = jnp.where(logits < thresh, -jnp.inf, logits)
            return jax.random.categorical(key, logits, axis=-1).astype(
                jnp.int32
            )

        cache = self.init_cache(b, max_len)
        logits, cache = self.prefill(prompt, cache)
        rng, key = jax.random.split(rng)
        tok = sample(logits, key)
        if num_steps == 1:
            return tok[:, None]

        def body(mdl, carry, _):
            tok, cache, pos, rng = carry
            logits, cache = mdl.decode_step(tok, cache, pos)
            rng, key = jax.random.split(rng)
            nxt = sample(logits, key)
            return (nxt, cache, pos + 1, rng), nxt

        scan = nn.scan(
            body,
            variable_broadcast="params",
            split_rngs={"params": False},
            length=num_steps - 1,
        )
        _, rest = scan(self, (tok, cache, jnp.int32(n), rng), None)
        return jnp.concatenate([tok[:, None], rest.T], axis=1)
