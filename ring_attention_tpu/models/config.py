"""A frozen description of a model: what ``RingTransformer`` builds its
stack from.

One ``ModelConfig`` says everything about the block that the arithmetic
depends on: the widths, one ``LayerConfig`` per layer (its attention
window, whether it rotates, which mixer and which feed-forward it has),
the norms, the multipliers and the routed experts.  How the stack is *run*
(mesh, kernels, remat, chunking) stays with ``RingTransformer``'s own
options and is not here.

``RingTransformer.from_config(config, **options)`` is the constructor.
The older keyword constructor (``num_tokens, dim, depth, heads, ...``)
builds the uniform block of ``ModelConfig.uniform`` and walks the same
stack, so a StarCoder2-like model is this with other values.
"""

from __future__ import annotations

import json
from dataclasses import dataclass

from ..ops.rotary import YarnScaling

FFN_KINDS = ("gelu", "gated", "routed")
MIXERS = ("attention", "mamba")
ROUTER_SCORES = ("sigmoid", "softmax")


@dataclass(frozen=True)
class LayerConfig:
    # causal lookback: query i reads keys j with i - j < window; None reads
    # every earlier key (and sizes the layer's decode cache accordingly)
    window: int | None = None
    rotary: bool = True  # False: no positional encoding in this layer
    ffn: str = "gelu"  # one of FFN_KINDS
    # what stands where the attention module stands: "mamba" is a Mamba-2
    # state-space mixer (models/ssm.py), whose cache entry is a state and
    # not rows; ``window`` and ``rotary`` say nothing about such a layer
    mixer: str = "attention"  # one of MIXERS


@dataclass(frozen=True)
class ModelConfig:
    num_tokens: int  # rows of the embedding and the head held here
    dim: int
    heads: int
    dim_head: int
    kv_heads: int
    layers: tuple[LayerConfig, ...]
    ffn_dim: int  # hidden width of the "gelu" and "gated" feed-forwards
    rotary_theta: float = 10000.0
    norm_eps: float = 1e-12
    qk_norm: bool = False  # RMSNorm over dim_head on q and k, before rotary
    attn_gate: bool = False  # sigmoid(x W_g) on the attention output
    # a second RMSNorm on each sub-block's output, before the residual add
    sandwich_norm: bool = False
    embed_scale: float = 1.0
    # "routed" layers: the router scores all ``num_experts`` and this
    # holder computes ``experts_held`` consecutive ones from ``first_expert``
    num_experts: int = 0
    experts_per_token: int = 0
    expert_dim: int = 0
    shared_expert_dim: int = 0  # 0: no shared expert
    route_scale: float = 1.0
    first_expert: int = 0
    experts_held: int = 0
    # group-limited choice: the router's experts in ``expert_groups``
    # consecutive groups, of which a token keeps ``groups_per_token`` (by
    # the sum of each group's two best biased scores) and chooses inside
    expert_groups: int = 1
    groups_per_token: int = 1
    # latent attention (all five or none): queries through a
    # ``q_latent_dim`` bottleneck, keys and values through one
    # ``kv_latent_dim`` latent a position plus ``qk_rope_dim`` rotary
    # dimensions shared by the heads; a head's q.k width is ``qk_nope_dim +
    # qk_rope_dim`` (``dim_head``) and its value width ``v_dim``
    q_latent_dim: int = 0
    kv_latent_dim: int = 0
    qk_nope_dim: int = 0
    qk_rope_dim: int = 0
    v_dim: int = 0
    rope_scaling: YarnScaling | None = None
    # the Mamba-2 mixer of the "mamba" layers (all six or none): ``ssm_heads``
    # heads of ``ssm_head_dim`` channels, each with a ``ssm_head_dim`` x
    # ``ssm_state`` float32 state; B and C shared by the heads of a group;
    # ``ssm_conv`` taps of causal depthwise convolution; the chunked form
    # hands the state on every ``ssm_chunk`` positions
    ssm_heads: int = 0
    ssm_head_dim: int = 0
    ssm_state: int = 0
    ssm_groups: int = 0
    ssm_conv: int = 0
    ssm_chunk: int = 0
    # each sub-block's output is multiplied by it before the residual add,
    # the logits by ``logit_scale``; 1.0: not at all
    residual_scale: float = 1.0
    logit_scale: float = 1.0
    softmax_scale: float | None = None  # None: dim_head ** -0.5
    tie_embeddings: bool = False  # the head is the embedding, transposed
    # "softmax": weights are the softmax over the chosen logits, no bias
    router_score: str = "sigmoid"  # one of ROUTER_SCORES

    def __post_init__(self):
        for i, layer in enumerate(self.layers):
            if layer.ffn not in FFN_KINDS:
                raise ValueError(
                    f"ModelConfig: layer {i} has ffn {layer.ffn!r}; the "
                    f"kinds are {', '.join(FFN_KINDS)}")
            if layer.mixer not in MIXERS:
                raise ValueError(
                    f"ModelConfig: layer {i} has mixer {layer.mixer!r}; the "
                    f"mixers are {', '.join(MIXERS)}")
        if self.router_score not in ROUTER_SCORES:
            raise ValueError(
                f"ModelConfig: router_score {self.router_score!r}; the "
                f"score functions are {', '.join(ROUTER_SCORES)}")
        ssm = (self.ssm_heads, self.ssm_head_dim, self.ssm_state,
               self.ssm_groups, self.ssm_conv, self.ssm_chunk)
        mamba = any(layer.mixer == "mamba" for layer in self.layers)
        if (any(ssm) or mamba) and not (
                all(w > 0 for w in ssm) and self.ssm_groups == 1):
            raise ValueError(
                f"ModelConfig: a Mamba-2 mixer needs all of ssm_heads, "
                f"ssm_head_dim, ssm_state, ssm_groups, ssm_conv and ssm_chunk "
                f"(got {ssm}), and ssm_groups = 1 until a configuration "
                f"needs another")
        if self.heads % self.kv_heads:
            raise ValueError(
                f"ModelConfig: {self.heads} heads do not group over "
                f"{self.kv_heads} kv heads")
        if any(layer.ffn == "routed" for layer in self.layers) and not (
                0 < self.experts_per_token <= self.num_experts
                and 0 < self.experts_held
                and self.first_expert + self.experts_held <= self.num_experts):
            raise ValueError(
                f"ModelConfig: routed layers need 0 < experts_per_token <= "
                f"num_experts and the held experts [{self.first_expert}, "
                f"{self.first_expert + self.experts_held}) inside "
                f"[0, {self.num_experts})")
        if not (0 < self.groups_per_token <= self.expert_groups) or (
                self.expert_groups > 1 and (
                    self.num_experts % self.expert_groups
                    or self.experts_per_token > self.groups_per_token
                    * (self.num_experts // self.expert_groups))):
            raise ValueError(
                f"ModelConfig: {self.num_experts} experts do not make "
                f"{self.expert_groups} equal groups of which a token keeps "
                f"{self.groups_per_token} and chooses "
                f"{self.experts_per_token} experts inside")
        latent = (self.q_latent_dim, self.kv_latent_dim, self.qk_nope_dim,
                  self.qk_rope_dim, self.v_dim)
        if any(latent) and not (
                all(w > 0 for w in latent) and self.qk_rope_dim % 2 == 0
                and self.dim_head == self.qk_nope_dim + self.qk_rope_dim
                and self.kv_heads == self.heads):
            raise ValueError(
                f"ModelConfig: latent attention needs all of q_latent_dim, "
                f"kv_latent_dim, qk_nope_dim, qk_rope_dim (even) and v_dim "
                f"(got {latent}), dim_head = qk_nope_dim + qk_rope_dim and "
                f"kv_heads = heads")

    @property
    def latent(self) -> bool:
        """Whether the attention layers are latent attention."""
        return self.kv_latent_dim > 0

    @property
    def depth(self) -> int:
        return len(self.layers)

    @classmethod
    def uniform(cls, *, num_tokens, dim, depth, heads, dim_head, kv_heads,
                ffn_dim, windows=None, rotary=True):
        """The block the keyword constructor has always built: pre-norm,
        rotary, a non-gated GELU feed-forward, ``windows`` per layer."""
        windows = windows or (None,) * depth
        return cls(
            num_tokens=num_tokens, dim=dim, heads=heads, dim_head=dim_head,
            kv_heads=kv_heads or heads, ffn_dim=ffn_dim,
            layers=tuple(LayerConfig(window=w, rotary=rotary) for w in windows))

    @classmethod
    def from_dict(cls, d: dict) -> "ModelConfig":
        """From a published ``config.json``'s keys, as the files under
        ``benchmarks/configs/`` hold them.  What a family's block is (its
        norms, gates, feed-forward) is not in those keys but in the
        family's published code, so the translation is one function per
        family in ``_FAMILIES`` below (named by ``model_type``, else by
        ``architectures``: "Starcoder2ForCausalLM" is ``starcoder2``), and
        a family that is not there is an error, not some other family's
        block."""
        name = d.get("model_type") or next(
            (a.removesuffix("ForCausalLM").lower()
             for a in d.get("architectures", ())), None)
        family = _FAMILIES.get(name)
        if family is None:
            raise ValueError(
                f"ModelConfig: no translation for the family {name!r} "
                f"(model_type, else architectures); there are "
                f"{', '.join(sorted(_FAMILIES))} (models/config.py)")
        heads = d["num_attention_heads"]
        fields = dict(
            num_tokens=d["vocab_size"], dim=d["hidden_size"], heads=heads,
            dim_head=d.get("head_dim") or d["hidden_size"] // heads,
            kv_heads=d.get("num_key_value_heads") or heads,
            ffn_dim=d["intermediate_size"],
            rotary_theta=float(d.get("rope_theta", 10000.0)))
        return cls(**{**fields, **family(d)})

    @classmethod
    def from_file(cls, path: str) -> "ModelConfig":
        with open(path) as f:
            return cls.from_dict(json.load(f))


def _starcoder2(d: dict) -> dict:
    """The repo's first block, as the StarCoder2 files under
    ``benchmarks/configs/`` run it (their ``changed``): pre-norm RMSNorm at
    the default eps, rotary GQA, a non-gated erf-GELU feed-forward, no
    biases, ``sliding_window`` in every layer or none."""
    layer = LayerConfig(window=d.get("sliding_window"))
    return dict(layers=(layer,) * d["num_hidden_layers"])


def _afmoe(d: dict) -> dict:
    """Gated attention with q/k norms, four norms a layer, rotary on the
    sliding layers only, leading dense layers and then sigmoid-routed
    experts with a shared one (after transformers' ``modeling_afmoe.py``).
    Where the file holds a chip's share of a deployment, ``num_experts``
    counts the experts held here, ``published.num_experts`` the router's
    width and ``first_expert`` the first one held."""
    if not d.get("route_norm", True) or d.get("score_func") != "sigmoid":
        raise ValueError(
            "ModelConfig: the routed layer is written for sigmoid scores "
            "normalised over the chosen experts (route_norm)")
    kinds = d["layer_types"]
    if len(kinds) != d["num_hidden_layers"]:
        raise ValueError(
            f"ModelConfig: {len(kinds)} layer_types for "
            f"{d['num_hidden_layers']} num_hidden_layers")
    layers = tuple(
        LayerConfig(
            window=d["sliding_window"] if sliding else None,
            rotary=sliding,  # full layers carry no positional encoding
            ffn="gated" if i < d["num_dense_layers"] else "routed")
        for i, sliding in enumerate(k == "sliding_attention" for k in kinds))
    return dict(
        layers=layers, norm_eps=d["rms_norm_eps"],
        qk_norm=True, attn_gate=True, sandwich_norm=True,
        embed_scale=d["hidden_size"] ** 0.5 if d.get("mup_enabled") else 1.0,
        num_experts=d.get("published", d)["num_experts"],
        experts_per_token=d["num_experts_per_tok"],
        expert_dim=d["moe_intermediate_size"],
        shared_expert_dim=d["moe_intermediate_size"] * d["num_shared_experts"],
        route_scale=d["route_scale"],
        first_expert=d.get("first_expert", 0),
        experts_held=d["num_experts"])


def _dots_vlm(d: dict) -> dict:
    """The language model of ``dots.vlm1`` is the DeepSeek-V3 block (the
    config names its keys one for one; after the public
    ``modeling_deepseek.py``): latent attention with a norm on each
    bottleneck and yarn-scaled rotary dimensions shared by the heads, plain
    pre-norm residuals, ``first_k_dense_replace`` leading gated-SiLU layers
    and then sigmoid-routed experts with a shared one, chosen inside the
    ``topk_group`` best of ``n_group`` groups.  The vision tower and the
    multi-token-prediction module are not built (ROADMAP, Reach).  Where
    the file holds a chip's share, ``n_routed_experts`` counts the experts
    held here, ``published.n_routed_experts`` the router's width and
    ``first_expert`` the first one held."""
    latent = ("q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
              "qk_rope_head_dim", "v_head_dim")
    missing = [k for k in latent if not d.get(k)]
    if missing:
        raise ValueError(
            f"ModelConfig: a dots_vlm file gives every latent width; "
            f"missing {', '.join(missing)}")
    if (d.get("scoring_func") != "sigmoid" or d.get("topk_method") != "noaux_tc"
            or not d.get("norm_topk_prob", True)
            or d.get("moe_layer_freq", 1) != 1):
        raise ValueError(
            "ModelConfig: the routed layer is written for sigmoid scores, "
            "the bias-corrected group-limited choice (noaux_tc), weights "
            "normalised over the chosen experts (norm_topk_prob) and a "
            "routed layer in every layer after the dense ones")
    depth, dense = d["num_hidden_layers"], d["first_k_dense_replace"]
    return dict(
        layers=tuple(LayerConfig(ffn="gated" if i < dense else "routed")
                     for i in range(depth)),
        dim_head=d["qk_nope_head_dim"] + d["qk_rope_head_dim"],
        kv_heads=d["num_attention_heads"],
        norm_eps=d["rms_norm_eps"],
        q_latent_dim=d["q_lora_rank"], kv_latent_dim=d["kv_lora_rank"],
        qk_nope_dim=d["qk_nope_head_dim"], qk_rope_dim=d["qk_rope_head_dim"],
        v_dim=d["v_head_dim"],
        rope_scaling=YarnScaling.from_dict(d.get("rope_scaling")),
        num_experts=d.get("published", d)["n_routed_experts"],
        experts_per_token=d["num_experts_per_tok"],
        expert_dim=d["moe_intermediate_size"],
        shared_expert_dim=d["moe_intermediate_size"] * d["n_shared_experts"],
        route_scale=d["routed_scaling_factor"],
        first_expert=d.get("first_expert", 0),
        experts_held=d["n_routed_experts"],
        expert_groups=d["n_group"], groups_per_token=d["topk_group"])


def _granitemoehybrid(d: dict) -> dict:
    """Granite 4.0-H (after transformers' ``modeling_granitemoehybrid.py``,
    whose mixer is Bamba's Mamba-2 layer): ``layer_types`` says which layers
    are Mamba-2 mixers and which grouped-query attention without positional
    encoding and with the softmax scale ``attention_multiplier``; every
    layer's feed-forward is softmax-routed experts plus a shared one, both
    gated SiLU; plain pre-norm residuals whose sub-block outputs are
    multiplied by ``residual_multiplier``; the embedding by
    ``embedding_multiplier``; the tied head's logits divided by
    ``logits_scaling``.  Where the file holds a chip's share,
    ``num_local_experts`` counts the experts held here,
    ``published.num_local_experts`` the router's width and ``first_expert``
    the first one held."""
    kinds = d["layer_types"]
    if len(kinds) != d["num_hidden_layers"] or set(kinds) - set(MIXERS):
        raise ValueError(
            f"ModelConfig: {len(kinds)} layer_types ({sorted(set(kinds))}) "
            f"for {d['num_hidden_layers']} num_hidden_layers of "
            f"{', '.join(MIXERS)}")
    if (d.get("position_embedding_type") != "nope" or d.get("mamba_proj_bias")
            or not d.get("mamba_conv_bias") or d.get("attention_bias")
            or d.get("normalization_function", "rmsnorm") != "rmsnorm"
            or d.get("hidden_act") != "silu"
            or d["mamba_expand"] * d["hidden_size"]
            != d["mamba_n_heads"] * d["mamba_d_head"]):
        raise ValueError(
            "ModelConfig: the granitemoehybrid block is written for no "
            "positional encoding (nope), RMSNorm, SiLU, a convolution with a "
            "bias, projections without, and mamba_expand x hidden_size = "
            "mamba_n_heads x mamba_d_head")
    return dict(
        layers=tuple(LayerConfig(rotary=False, ffn="routed", mixer=kind)
                     for kind in kinds),
        norm_eps=d["rms_norm_eps"],
        embed_scale=float(d["embedding_multiplier"]),
        residual_scale=float(d["residual_multiplier"]),
        logit_scale=1.0 / d["logits_scaling"],
        softmax_scale=float(d["attention_multiplier"]),
        tie_embeddings=bool(d["tie_word_embeddings"]),
        ssm_heads=d["mamba_n_heads"], ssm_head_dim=d["mamba_d_head"],
        ssm_state=d["mamba_d_state"], ssm_groups=d["mamba_n_groups"],
        ssm_conv=d["mamba_d_conv"], ssm_chunk=d["mamba_chunk_size"],
        router_score="softmax",
        num_experts=d.get("published", d)["num_local_experts"],
        experts_per_token=d["num_experts_per_tok"],
        expert_dim=d["intermediate_size"],
        shared_expert_dim=d["shared_intermediate_size"],
        first_expert=d.get("first_expert", 0),
        experts_held=d["num_local_experts"])


_FAMILIES = {"starcoder2": _starcoder2, "afmoe": _afmoe,
             "dots_vlm": _dots_vlm, "granitemoehybrid": _granitemoehybrid}
