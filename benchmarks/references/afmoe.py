"""The plain reference of the ``afmoe`` block (Trinity-Large-Preview), and
the comparison that decides ``correct`` for its cells.

float32 ``jax.numpy`` under ``default_matmul_precision("highest")``: no
kernels, no cache, no batching, nothing imported from the program.  The
equations, for one sequence ``t`` (ISSUE 28, after the public
``transformers`` implementation of ``afmoe``)::

    h = Embed[t] * sqrt(d)                                   (mup_enabled)
    per layer of type sliding | full:
      a = RMSNorm_in(h)
      q = a Wq   k = a Wk   v = a Wv   g = a Wg
      q = RMSNorm_q(q), k = RMSNorm_k(k)     over each head's head_dim
      sliding: q, k = RoPE(q, k) (half rotation);  full: no positions
      o = softmax(q k^T / sqrt(head_dim) + mask) v
          grouped; mask causal, and on sliding layers i - j < window
      h = h + RMSNorm_post_attn((o * sigmoid(g)) Wo)
      m = RMSNorm_pre_mlp(h)
      dense (index < num_dense_layers): f = Wdown(silu(Wgate m) * (Wup m))
      routed: s = sigmoid(m Wr);  S = top-k(s + b), b for the choice only
              w_e = route_scale * s_e / (sum_{e in S} s_e + 1e-20)
              f = Shared(m) + sum_{e in S, e held} w_e Expert_e(m)
      h = h + RMSNorm_post_mlp(f)
    logits = RMSNorm_final(h) W_head

It is given the same share of the deployment as the program: the experts
``[first_expert, first_expert + num_experts)`` of ``published.num_experts``
(what the absent ones would add is left out, and that partial result goes
on), and the slice of the vocabulary the embedding and the head hold.

It reads the parameters out of the program's own tree (flax names) and
casts one layer, and inside a routed layer one expert, at a time: the
cell's 4.3 G parameters in float32 would not fit beside it.  Every expert
is computed for every token and weighted by ``w_e`` (zero where the token
did not choose it): plain, and at the check's few thousand tokens cheap.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

QUERY_BLOCK = 1024

# Limits, system against reference: the configuration file's ``limits``
# (with ``limits_why``: the readings they were set from), not constants
# here, so a second afmoe configuration brings its own.  What each is for:
#
# ``logits_rel_l2``: bfloat16 rounding moves every logit a little, as in
# benchmarks/reference.py.
#
# ``attn_rel_l2``: the logits see a sub-block only through its post-norm,
# whose weight can be small (a tenth in trinity-large-preview), and most of
# a logit is then the embedding's own path.  So the attention outputs are
# compared too, undiluted: each layer's ``(o * sigmoid(g)) Wo`` before its
# norm, read from the program's ``probes`` collection, the largest relative
# L2 of any layer.  It sees how the kernels read the caches.  With random
# weights it sees what the caches hold only in the first layer: further up
# every position's value rows share a common part (the blown-up average the
# layer below added), the outputs are mostly that part, and independent
# rounding of the rows averages away under it.
#
# ``cache_rel_l2``: so what the caches hold is compared row by row: the k
# rows (after their norm and rotation) and the v rows the program left in
# every layer's cache at the compared positions, the largest relative L2 of
# any layer's k or v.
#
# ``routing_margin``, ``min_positions``: a rounded router score turns a
# top-k choice that is near a tie, and the position where that happens gets
# another expert's output, which is no rounding (one such row doubles the
# whole comparison's relative L2).  The reference is never handed the
# program's routing.  It knows where its own choice is near a tie, though:
# a position at which, in some routed layer, the weakest expert chosen and
# the strongest one passed over are closer than ``routing_margin`` (in
# score + bias) and one of the two is held here, is left out of both
# comparisons.  ``min_positions`` of them have to be left.


def _f32(tree):
    return jax.tree.map(lambda w: w.astype(jnp.float32), tree)


def _rmsnorm(x, gamma, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * gamma


def _rotary(x, theta):
    """x: (heads, n, d); half-rotation (NeoX) rotary at positions 0..n-1."""
    n, d = x.shape[-2:]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.arange(n, dtype=jnp.float32)[:, None] * inv[None, :]
    ang = jnp.concatenate([ang, ang], axis=-1)
    x1, x2 = jnp.split(x, 2, axis=-1)
    return x * jnp.cos(ang) + jnp.concatenate([-x2, x1], -1) * jnp.sin(ang)


def _attention(q, k, v, window):
    """q: (h, n, d), k and v: (hk, n, d); causal, and ``i - j < window``
    where there is one; query head i reads kv head i // (h / hk); a block
    of queries at a time."""
    h, n, d = q.shape
    hk = k.shape[0]
    q = q.reshape(hk, h // hk, n, d) * d ** -0.5
    cols = jnp.arange(n)[None, :]
    out = []
    for lo in range(0, n, QUERY_BLOCK):
        hi = min(lo + QUERY_BLOCK, n)
        rows = jnp.arange(lo, hi)[:, None]
        keep = cols <= rows
        if window is not None:
            keep = keep & (rows - cols < window)
        s = jnp.einsum("kgid,kjd->kgij", q[:, :, lo:hi], k)
        s = jnp.where(keep, s, -jnp.inf)
        out.append(jnp.einsum("kgij,kjd->kgid", jax.nn.softmax(s, -1), v))
    return jnp.concatenate(out, axis=2).reshape(h, n, d)


def _gated(x, p):
    gate, up = x @ p["gate"]["kernel"], x @ p["up"]["kernel"]
    return (jax.nn.silu(gate) * up) @ p["down"]["kernel"]


def _routed(m, p, config):
    """The routed layer's output, the tokens each held expert got, and each
    token's margin: how far the weakest chosen expert is from the strongest
    one passed over, where one of the two is held here (else infinite)."""
    k = config["num_experts_per_tok"]
    first, held = config.get("first_expert", 0), config["num_experts"]
    scores = jax.nn.sigmoid(m @ p["router"].astype(jnp.float32))
    bias = p["expert_bias"].astype(jnp.float32)
    ranked, order = lax.top_k(scores + bias, k + 1)
    chosen = order[:, :k]
    near = order[:, k - 1:] - first  # the last one in, the first one out
    margin = jnp.where(((near >= 0) & (near < held)).any(-1),
                       ranked[:, k - 1] - ranked[:, k], jnp.inf)
    top = jnp.take_along_axis(scores, chosen, axis=-1)
    weights = config["route_scale"] * top / (
        top.sum(-1, keepdims=True) + 1e-20)
    # (tokens, held): a token's weight for each expert held here
    local = chosen - first
    per_expert = jnp.sum(
        jnp.where(local[:, :, None] == jnp.arange(held), weights[:, :, None],
                  0.0), axis=1)
    chose = (local[:, :, None] == jnp.arange(held)).any(1)  # (tokens, held)
    width = p["experts_down"].shape[1]

    def one_expert(e, acc):
        gate_up = p["experts_gate_up"][e].astype(jnp.float32)
        down = p["experts_down"][e].astype(jnp.float32)
        h = m @ gate_up
        y = (jax.nn.silu(h[:, :width]) * h[:, width:]) @ down
        return acc + per_expert[:, e, None] * y

    out = lax.fori_loop(0, held, one_expert, jnp.zeros_like(m))
    if config.get("num_shared_experts"):
        out = out + _gated(m, _f32(p["shared"]))
    return out, chose, margin


def forward(params, tokens, config, last: int | None = None):
    """``(logits, inside)`` of one sequence ``(n,)``: logits ``(n or last,
    vocab slice)``; ``inside["counts"]``, per routed layer the tokens each
    held expert received ``(routed layers, held)``; and for the positions
    kept, ``inside["chose"]``, whether each chose each held expert
    ``(routed layers, n or last, held)``, ``inside["margin"]``, each one's
    smallest margin over the routed layers ``(n or last,)``, and
    ``inside["attn"]``, every layer's attention output before its norm
    ``(layers, n or last, hidden)``, and ``inside["kv"]``, every layer's k
    (normed, rotated) and v rows ``(layers, 2, kv heads, n or last,
    head_dim)``."""
    p = params["params"]
    h, hk = config["num_attention_heads"], config["num_key_value_heads"]
    d, eps = config["head_dim"], config["rms_norm_eps"]
    n = tokens.shape[0]
    kept = slice(None) if last is None else slice(-last, None)
    chose, margins, attn, kv = [], [], [], []
    with jax.default_matmul_precision("highest"):
        x = p["embed"]["embedding"][tokens].astype(jnp.float32)
        if config.get("mup_enabled"):
            x = x * config["hidden_size"] ** 0.5
        for i, kind in enumerate(config["layer_types"]):
            sliding = kind == "sliding_attention"
            a = _f32(p[f"attn_layers_{i}"])
            normed = _rmsnorm(x, a["prenorm"]["gamma"], eps)
            qkv = normed @ a["to_qkv"]["kernel"]
            q, k, v = jnp.split(qkv, [h * d, (h + hk) * d], axis=-1)
            q = _rmsnorm(q.reshape(n, h, d), a["q_norm"]["gamma"], eps)
            k = _rmsnorm(k.reshape(n, hk, d), a["k_norm"]["gamma"], eps)
            q, k = q.transpose(1, 0, 2), k.transpose(1, 0, 2)
            v = v.reshape(n, hk, d).transpose(1, 0, 2)
            if sliding:
                q = _rotary(q, config["rope_theta"])
                k = _rotary(k, config["rope_theta"])
            kv.append(jnp.stack([k[:, kept], v[:, kept]]))
            o = _attention(q, k, v,
                           config["sliding_window"] if sliding else None)
            o = o.transpose(1, 0, 2).reshape(n, h * d)
            o = o * jax.nn.sigmoid(normed @ a["to_gate"]["kernel"])
            o = o @ a["to_out"]["kernel"]
            attn.append(o[kept])
            post = p[f"post_attn_norms_{i}"]["gamma"].astype(jnp.float32)
            x = x + _rmsnorm(o, post, eps)

            f = p[f"ff_layers_{i}"]
            m = _rmsnorm(x, f["norm"]["gamma"].astype(jnp.float32), eps)
            if i < config["num_dense_layers"]:
                y = _gated(m, _f32({k_: f[k_] for k_ in ("gate", "up", "down")}))
            else:
                y, c, margin = _routed(m, f, config)
                chose.append(c)
                margins.append(margin)
            post = p[f"post_ff_norms_{i}"]["gamma"].astype(jnp.float32)
            x = x + _rmsnorm(y, post, eps)
        x = _rmsnorm(x, p["final_norm"]["gamma"].astype(jnp.float32), eps)
        if last is not None:
            x = x[-last:]
        out = x @ p["to_logits"]["kernel"].astype(jnp.float32)
    chose, margin = jnp.stack(chose), jnp.stack(margins).min(0)
    return out, {"counts": chose.sum(1), "chose": chose[:, kept],
                 "margin": margin[kept], "attn": jnp.stack(attn),
                 "kv": jnp.stack(kv)}


def logits(params, tokens, config, last: int | None = None):
    return forward(params, tokens, config, last)[0]


def rel_l2(got, want):
    got, want = got.astype(jnp.float32), want.astype(jnp.float32)
    return jnp.linalg.norm(got - want) / jnp.linalg.norm(want)


def verdict(got: dict, want, inside, limits) -> dict:
    """The comparison that decides ``correct`` (NaN compares false), with
    the numbers it compared.  ``got``: the program's ``logits`` and, shaped
    as ``inside``'s, ``attn`` and ``kv`` (None where the program gave none:
    not correct); ``want``, ``inside``: ``forward``'s, at the same
    positions.  Positions whose routing is near a tie are left out."""
    keep = inside["margin"] >= limits["routing_margin"]
    kept = int(keep.sum())

    def rel(mine, its):  # over the kept positions, the last axis but one
        if mine is None or not kept:
            return float("nan")
        return float(rel_l2(mine[..., keep, :], its[..., keep, :]))

    logits = rel(got["logits"], want)
    layers = range(inside["attn"].shape[0])
    attn = [rel(got["attn"][i], inside["attn"][i])
            for i in layers] if got["attn"] is not None else [float("nan")]
    cache = [[rel(got["kv"][i, j], inside["kv"][i, j]) for j in (0, 1)]
             for i in layers] if got["kv"] is not None else [[float("nan")]]
    # (numpy's max, not Python's: a NaN among the layers must win)
    worst_attn, worst_cache = float(np.max(attn)), float(np.max(cache))
    return {"ok": bool(kept >= limits["min_positions"]
                       and logits <= limits["logits_rel_l2"]
                       and worst_attn <= limits["attn_rel_l2"]
                       and worst_cache <= limits["cache_rel_l2"]),
            "logits_rel_l2": logits, "attn_rel_l2": worst_attn,
            "cache_rel_l2": worst_cache, "positions_compared": kept,
            "attn_rel_l2_by_layer": attn, "cache_rel_l2_by_layer": cache,
            "logits_rel_l2_all_positions": float(rel_l2(got["logits"], want)),
            "margins": [round(float(x), 5) for x in inside["margin"]]}
