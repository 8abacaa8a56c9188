"""The plain reference of the ``dots_vlm`` language model (dots.vlm1.inst:
the DeepSeek-V3 block), and the comparison that decides ``correct`` for its
cells.

float32 ``jax.numpy`` under ``default_matmul_precision("highest")``: the
expanded form only, no cache, no kernels, no batching, nothing imported from
the program.  The equations, for one sequence ``t`` (ISSUE 32, after the
public ``modeling_deepseek.py``; eps 1e-6 everywhere)::

    h = Embed[t]
    per layer l:
      a    = RMSNorm_in(h)
      c_q  = RMSNorm_q(a W_DQ)                                  q_lora_rank
      q    = c_q W_UQ          -> heads x (q_n | q_r)
      c_kv | k_r = a W_DKV ;  c_kv = RMSNorm_kv(c_kv) ;  k_r one a position
      q_r, k_r = RoPE_yarn(q_r, k_r, position)
      k_n | v  = c_kv W_UKV    -> heads x (qk_nope | v)
      o    = softmax(s (q_n.k_n + q_r.k_r) + causal mask) v
             s = (qk_nope + qk_rope)^-0.5 m^2,  m = 0.1 mscale_all_dim ln(factor) + 1
      h    = h + o W_O
      x    = RMSNorm_post(h)
      l < first_k_dense_replace:  f = W_down(silu(W_gate x) * (W_up x))
      else:  sc = sigmoid(x W_r) ;  sc' = sc + b        b moves the choice only
             n_group groups of consecutive experts; a group's rank is the sum
             of its two largest sc'; the topk_group best groups are kept
             S = the num_experts_per_tok largest sc' among the kept groups
             w_e = routed_scaling_factor sc_e / (sum_{e in S} sc_e + 1e-20)
             f = Shared(x) + sum_{e in S, e held} w_e Expert_e(x)
      h    = h + f
    logits = RMSNorm_final(h) W_head

    RoPE_yarn over r = qk_rope dims, i = 0 .. r/2 - 1 (half rotation):
      extra_i = theta^(-2i/r) ;  inter_i = extra_i / factor
      low, high = floor, ceil of r ln(original / (beta 2 pi)) / (2 ln theta)
                  at beta = beta_fast, beta_slow, clipped to [0, r/2 - 1]
      ramp_i = clip((i - low) / (high - low), 0, 1)
      inv_freq_i = inter_i ramp_i + extra_i (1 - ramp_i)
      cos and sin times mscale(factor, mscale) / mscale(factor, mscale_all_dim)

It is given the same share of the deployment as the program: the experts
``[first_expert, first_expert + n_routed_experts)`` of
``published.n_routed_experts`` (what the absent ones would add is left out,
and that partial result goes on), and the slice of the vocabulary the
embedding and the head hold.

It reads the parameters out of the program's own tree (flax names) and
casts one matrix, and inside a routed layer one expert, at a time: the
cell's 4.57 G parameters in float32 would not fit beside it.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from .afmoe import _f32, _gated, _rmsnorm, rel_l2

QUERY_BLOCK = 512

# Limits, system against reference: the configuration file's ``limits`` with
# ``limits_why``.  ``logits_rel_l2`` as in references/afmoe.py;
# ``attn_rel_l2`` and ``cache_rel_l2`` one number a layer (a plain pre-norm
# stack carries bfloat16 rounding from layer to layer, so a limit that fits
# the last layer would be blind in the first, where a cache kept in a lower
# precision shows most); ``cache_rel_l2`` compares what a latent cache holds,
# per layer the rotated positional keys and the normed latents the program
# left at the compared positions.
#
# Routing.  With random weights the rounding of four layers moves a router
# score by some 0.006, as much as the scores near the choice's edge lie
# apart, so a margin alone cannot say where the two sides chose alike.  The
# program's counters can: each decoded position of the check's one session
# is one token, so its ``tokens_per_expert`` is its choice among the experts
# held here, which is all of the choice that reaches this holder's output
# (the absent experts add nothing here, and what another choice among them
# does to the sum the weights are divided by is far under the rounding).  A
# layer's rows at a position are compared where the two sides chose the same
# held experts for that token in every routed layer below; a position of the
# prompt, whose own choice the counters do not give, where the reference's
# margin (``margin_of``: how far a held expert chosen is from the strongest
# eligible one passed over and a held one passed over from the weakest
# chosen; how far a dropped group with held experts is from the weakest group
# kept; and, where such a group is kept, how far the weakest group kept is
# from the strongest dropped, since another group beside it changes which of
# its experts make the top k) is at least ``routing_margin`` in every routed
# layer below.  A token's choice that first differs (above
# that layer it is another token to both sides) where the reference's margin
# is at least ``routing_margin`` is no rounding: not correct.  The
# reference is never handed the program's choices; only the comparison is.
# ``min_positions`` of the logits' positions have to be left.


def _mscale(factor, mscale):
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def _yarn_inv_freq(r, theta, y):
    i = jnp.arange(r // 2, dtype=jnp.float32)
    extra = theta ** (-2.0 * i / r)
    if y is None:
        return extra

    def turns_at(beta):
        return (r * math.log(y["original_max_position_embeddings"]
                             / (beta * 2 * math.pi)) / (2 * math.log(theta)))

    low = min(max(math.floor(turns_at(y["beta_fast"])), 0), r // 2 - 1)
    high = min(max(math.ceil(turns_at(y["beta_slow"])), 0), r // 2 - 1)
    ramp = jnp.clip((i - low) / (high - low if high > low else 0.001), 0, 1)
    return extra / y["factor"] * ramp + extra * (1 - ramp)


def _rotary(x, theta, y):
    """x: (..., n, r); half-rotation rotary at positions 0..n-1 with the
    yarn frequencies."""
    n, r = x.shape[-2:]
    ang = jnp.arange(n, dtype=jnp.float32)[:, None] * _yarn_inv_freq(
        r, theta, y)[None, :]
    ang = jnp.concatenate([ang, ang], axis=-1)
    gain = 1.0 if y is None else (
        _mscale(y["factor"], y["mscale"])
        / _mscale(y["factor"], y["mscale_all_dim"]))
    x1, x2 = jnp.split(x, 2, axis=-1)
    return (x * jnp.cos(ang)
            + jnp.concatenate([-x2, x1], -1) * jnp.sin(ang)) * gain


def _attention(q, k, v, scale):
    """q, k: (h, n, d); v: (h, n, dv); causal; a block of queries at a
    time."""
    n = q.shape[1]
    cols = jnp.arange(n)[None, :]
    out = []
    for lo in range(0, n, QUERY_BLOCK):
        hi = min(lo + QUERY_BLOCK, n)
        s = jnp.einsum("hid,hjd->hij", q[:, lo:hi], k) * scale
        s = jnp.where(cols <= jnp.arange(lo, hi)[:, None], s, -jnp.inf)
        out.append(jnp.einsum("hij,hjd->hid", jax.nn.softmax(s, -1), v))
    return jnp.concatenate(out, axis=1)


def choose(scores, bias, config):
    """The group-limited choice: ``(chosen (tokens, k), (ranked, eligible),
    groups)``, ``ranked`` the k + 1 best eligible biased scores and
    ``eligible`` every expert's biased score, -inf in a dropped group (both
    for the margin), and ``groups`` the groups' ranks ``(tokens, n_group)``."""
    k, groups = config["num_experts_per_tok"], config["n_group"]
    biased = scores + bias
    tokens, experts = biased.shape
    by_group = biased.reshape(tokens, groups, experts // groups)
    rank = lax.top_k(by_group, min(2, experts // groups))[0].sum(-1)
    kept = lax.top_k(rank, config["topk_group"])[1]
    keep = (kept[:, :, None] == jnp.arange(groups)).any(1)
    eligible = jnp.where(keep[:, :, None], by_group, -jnp.inf).reshape(
        tokens, experts)
    ranked, order = lax.top_k(eligible, k + 1)
    return order[:, :k], (ranked, eligible), rank


def margin_of(ranked, eligible, rank, config):
    """Each token's margin ``(tokens,)``: how far the reference's scores are
    from a choice with other held experts in it.  A held expert chosen
    leaves when it falls under the strongest eligible expert passed over, one
    passed over enters when it rises over the weakest chosen: the least of
    those distances over the held experts of kept groups.  A dropped group
    with held experts is kept when its rank rises over the weakest kept
    group's; where one is kept, any other set of kept groups changes which
    of its experts make the top k, and the nearest other set is as far as
    the weakest group kept is from the strongest dropped."""
    k, groups = config["num_experts_per_tok"], config["n_group"]
    first, held = config.get("first_expert", 0), config["n_routed_experts"]
    last_in, first_out = ranked[:, k - 1, None], ranked[:, k, None]
    mine = eligible[:, first:first + held]
    margin = jnp.where(mine >= last_in, mine - first_out,
                       last_in - mine).min(-1)
    if config["topk_group"] < groups:
        best = lax.top_k(rank, config["topk_group"] + 1)[0]
        last_in, first_out = best[:, -2, None], best[:, -1, None]
        size = eligible.shape[1] // groups
        mine = rank[:, first // size:(first + held - 1) // size + 1]
        margin = jnp.minimum(margin, jnp.where(
            mine >= last_in, last_in - first_out, last_in - mine).min(-1))
    return margin


def _routed(m, p, config):
    """The routed layer's output, whether each token chose each held expert,
    each token's margin (``margin_of``) and the layer's pairs by group."""
    groups = config["n_group"]
    first, held = config.get("first_expert", 0), config["n_routed_experts"]
    scores = jax.nn.sigmoid(m @ p["router"].astype(jnp.float32))
    chosen, (ranked, eligible), rank = choose(
        scores, p["expert_bias"].astype(jnp.float32), config)
    margin = margin_of(ranked, eligible, rank, config)
    top = jnp.take_along_axis(scores, chosen, axis=-1)
    weights = config["routed_scaling_factor"] * top / (
        top.sum(-1, keepdims=True) + 1e-20)
    local = chosen - first
    per_expert = jnp.sum(
        jnp.where(local[:, :, None] == jnp.arange(held), weights[:, :, None],
                  0.0), axis=1)
    chose = (local[:, :, None] == jnp.arange(held)).any(1)  # (tokens, held)
    by_group = jnp.sum(
        (chosen // (scores.shape[1] // groups)).reshape(-1)[:, None]
        == jnp.arange(groups), axis=0)
    width = p["experts_down"].shape[1]

    def one_expert(e, acc):
        gate_up = p["experts_gate_up"][e].astype(jnp.float32)
        down = p["experts_down"][e].astype(jnp.float32)
        h = m @ gate_up
        y = (jax.nn.silu(h[:, :width]) * h[:, width:]) @ down
        return acc + per_expert[:, e, None] * y

    out = lax.fori_loop(0, held, one_expert, jnp.zeros_like(m))
    if config.get("n_shared_experts"):
        out = out + _gated(m, _f32(p["shared"]))
    return out, chose, margin, by_group


def forward(params, tokens, config, last: int | None = None):
    """``(logits, inside)`` of one sequence ``(n,)``: logits ``(n or last,
    vocab slice)``; ``inside["counts"]``, per routed layer the tokens each
    held expert received ``(routed layers, held)``, ``inside["groups"]``,
    per routed layer every holder's pairs by group ``(routed layers,
    n_group)``, ``inside["routed_layers"]``, their indices in the stack; and
    for the positions kept ``inside["chose"]``, whether each chose each held
    expert ``(routed layers, n or last, held)``, ``inside["margins"]`` ``(routed layers, n or last)`` and
    their least ``inside["margin"]``, ``inside["attn"]``, every layer's
    attention output ``(layers, n or last, hidden)``, and ``inside["kv"]``,
    per layer the pair a latent cache holds: the rotated positional keys
    ``(1, n or last, qk_rope)`` and the normed latents ``(1, n or last,
    kv_lora_rank)``."""
    p = params["params"]
    h, eps = config["num_attention_heads"], config["rms_norm_eps"]
    dn, dr = config["qk_nope_head_dim"], config["qk_rope_head_dim"]
    dv, dl = config["v_head_dim"], config["kv_lora_rank"]
    theta, yarn = float(config["rope_theta"]), config.get("rope_scaling")
    m_all = 1.0 if yarn is None else _mscale(yarn["factor"],
                                             yarn["mscale_all_dim"])
    scale = (dn + dr) ** -0.5 * m_all * m_all
    n = tokens.shape[0]
    kept = slice(None) if last is None else slice(-last, None)
    chose, margins, groups, attn, kv = [], [], [], [], []

    def w(leaf):
        return leaf.astype(jnp.float32)

    with jax.default_matmul_precision("highest"):
        x = w(p["embed"]["embedding"][tokens])
        for i in range(config["num_hidden_layers"]):
            a = p[f"attn_layers_{i}"]
            normed = _rmsnorm(x, w(a["prenorm"]["gamma"]), eps)
            c_q = _rmsnorm(normed @ w(a["to_q_latent"]["kernel"]),
                           w(a["q_latent_norm"]["gamma"]), eps)
            q = (c_q @ w(a["to_q"]["kernel"])).reshape(n, h, dn + dr)
            q = q.transpose(1, 0, 2)
            c_kv, k_r = jnp.split(normed @ w(a["to_kv_latent"]["kernel"]),
                                  [dl], axis=-1)
            c_kv = _rmsnorm(c_kv, w(a["kv_latent_norm"]["gamma"]), eps)
            k_r = _rotary(k_r[None], theta, yarn)  # (1, n, dr)
            q = jnp.concatenate(
                [q[..., :dn], _rotary(q[..., dn:], theta, yarn)], axis=-1)
            kv.append((k_r[:, kept], c_kv[None, kept]))
            k_v = (c_kv @ w(a["to_kv"])).reshape(n, h, dn + dv)
            k_v = k_v.transpose(1, 0, 2)
            k = jnp.concatenate(
                [k_v[..., :dn], jnp.broadcast_to(k_r, (h, n, dr))], axis=-1)
            o = _attention(q, k, k_v[..., dn:], scale)
            o = o.transpose(1, 0, 2).reshape(n, h * dv) @ w(
                a["to_out"]["kernel"])
            attn.append(o[kept])
            x = x + o

            f = p[f"ff_layers_{i}"]
            m = _rmsnorm(x, w(f["norm"]["gamma"]), eps)
            if i < config["first_k_dense_replace"]:
                # a matrix at a time: the three are 1.6 GB in float32
                hidden = jax.nn.silu(m @ w(f["gate"]["kernel"])) * (
                    m @ w(f["up"]["kernel"]))
                y = hidden @ w(f["down"]["kernel"])
            else:
                y, c, margin, by_group = _routed(m, f, config)
                chose.append(c)
                margins.append(margin)
                groups.append(by_group)
            x = x + y
        x = _rmsnorm(x, w(p["final_norm"]["gamma"]), eps)
        if last is not None:
            x = x[-last:]
        out = x @ w(p["to_logits"]["kernel"])
    chose, margin = jnp.stack(chose), jnp.stack(margins).min(0)
    by_group = jnp.stack(groups)  # (routed layers, n_group)
    first = config["first_k_dense_replace"]
    return out, {"counts": chose.sum(1), "chose": chose[:, kept],
                 "margin": margin[kept], "margins": jnp.stack(margins)[:, kept],
                 "groups": by_group,
                 "routed_layers": list(range(first, first + len(margins))),
                 "attn": jnp.stack(attn), "kv": kv}


def logits(params, tokens, config, last: int | None = None):
    return forward(params, tokens, config, last)[0]


def routed_alike(routing, inside, far) -> tuple:
    """``(alike, widest)``: ``alike[r, p]``, whether routed layer ``r``
    chose for position ``p`` what the program chose, known for the last
    positions (``routing``: the program's ``chose``, shaped as ``inside``'s
    at those positions; None: none known) and taken from the
    reference's margin before them; ``widest``, the largest margin at which
    a known position's choice first differs (0.0: none does).  Only the
    first routed layer that differs counts: above it the token is another
    token to both sides, and its routing differs at any margin."""
    margins = np.asarray(inside["margins"])
    alike = margins >= far
    if routing is None:
        return alike, 0.0
    known = np.asarray(routing["chose"]).shape[1]
    same = (np.asarray(routing["chose"])
            == np.asarray(inside["chose"])[:, -known:]).all(-1)
    first = ~same & (np.cumsum(~same, axis=0) == 1)
    widest = float(np.max(np.where(first, margins[:, -known:], 0.0),
                          initial=0.0))
    alike[:, -known:] = same
    return alike, widest


def verdict(got: dict, want, inside, limits) -> dict:
    """The comparison that decides ``correct`` (NaN compares false), with
    the numbers it compared.  ``got``: the program's ``logits`` and, shaped
    as ``inside``'s, ``attn`` and ``kv`` (None where the program gave none:
    not correct) and ``routing`` (``routed_alike``); ``want``, ``inside``:
    ``forward``'s, at the same positions.  A layer's rows are compared at
    the positions routed alike in every routed layer below it."""
    alike, widest = routed_alike(got.get("routing"), inside,
                                 limits["routing_margin"])
    fault = widest >= limits["routing_margin"]
    depth = inside["attn"].shape[0]
    # keep[l]: the positions whose input to layer l saw the same experts
    below = np.asarray(inside["routed_layers"])
    keep = [alike[below < layer].all(0) for layer in range(depth + 1)]

    def rel(mine, its, where):  # the last axis but one holds the positions
        if mine is None or not where.any():
            return float("nan")
        return float(rel_l2(mine[..., where, :], its[..., where, :]))

    def limit(name, layer):
        value = limits[name]
        return value[layer] if isinstance(value, (list, tuple)) else value

    logits = rel(got["logits"], want, keep[depth])
    attn = [rel(got["attn"][i], inside["attn"][i], keep[i])
            for i in range(depth)] if got["attn"] is not None else [
        float("nan")] * depth
    cache = [max(rel(got["kv"][i][j], inside["kv"][i][j], keep[i])
                 for j in (0, 1)) if got["kv"] is not None else float("nan")
             for i in range(depth)]
    # (not a <= b for each: a NaN must fail)
    within = all(attn[i] <= limit("attn_rel_l2", i)
                 and cache[i] <= limit("cache_rel_l2", i)
                 for i in range(depth))
    kept = int(keep[depth].sum())
    return {"ok": bool(not fault and kept >= limits["min_positions"]
                       and logits <= limits["logits_rel_l2"] and within),
            "logits_rel_l2": logits, "attn_rel_l2": float(np.max(attn)),
            "cache_rel_l2": float(np.max(cache)), "positions_compared": kept,
            "positions_compared_by_layer": [int(k.sum()) for k in keep[:-1]],
            "attn_rel_l2_by_layer": attn, "cache_rel_l2_by_layer": cache,
            "logits_rel_l2_all_positions": float(rel_l2(got["logits"], want)),
            "routed_differently_beyond_margin": fault,
            "widest_margin_routed_differently": widest,
            "margins": [round(float(x), 5) for x in inside["margin"]],
            "reference_pairs_per_group": np.asarray(inside["groups"]).tolist()}
