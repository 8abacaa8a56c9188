"""The plain reference of the ``granitemoehybrid`` block (granite-4.0-h-small:
Mamba-2 mixers, one attention layer in ten, softmax-routed experts), and the
comparison that decides ``correct`` for its cells.

float32 ``jax.numpy`` under ``default_matmul_precision("highest")``: the
recurrence position by position (a ``lax.scan`` over ``S_t``, so that the
program's chunked form is compared with another form and not with itself),
no cache, no kernels, no batching, nothing imported from the program.  The
equations, for one sequence ``t`` (ISSUE 34, after transformers'
``modeling_granitemoehybrid.py``, whose mixer is Bamba's Mamba-2 layer, and
arXiv 2405.21060; eps ``rms_norm_eps`` everywhere)::

    h = embedding_multiplier * Embed[t]
    per layer l:
      a = RMSNorm_in(h)
      layer_types[l] == "mamba":
        z | u | d = a W_in                    inner | inner + 2 state | heads
        u_t = silu(sum_{j<taps} w_conv[j] * u_{t-taps+1+j} + b_conv)
              depthwise, causal, zeros before position 0
        x | B | C = u                         heads x d_head | state | state
        dt_t = softplus(d_t + dt_bias) ;  A = -exp(A_log)
        S_t[h] = exp(dt_t[h] A[h]) S_{t-1}[h] + dt_t[h] x_t[h] (x) B_t
        y_t[h] = S_t[h] C_t + Dskip[h] x_t[h]                 S_{-1} = 0
        m = RMSNorm_inner(y * silu(z)) W_out        the gate, then the norm
      layer_types[l] == "attention":
        q, k, v = a W_q, a W_k, a W_v         no positional encoding (nope)
        m = softmax(attention_multiplier q.k + causal mask) v W_o
      h = h + residual_multiplier * m
      p = RMSNorm_post(h)
      r = p W_r ;  T = the num_experts_per_tok largest r
      w_e = exp(r_e) / sum_{e' in T} exp(r_e')             no bias, no scale
      f = Shared(p) + sum_{e in T, e held} w_e Expert_e(p)  both gated SiLU
      h = h + residual_multiplier * f
    logits = RMSNorm_final(h) Embed^T / logits_scaling      the tied head

It is given the same share of the deployment as the program: the experts
``[first_expert, first_expert + num_local_experts)`` of
``published.num_local_experts`` (what the absent ones would add is left out,
and that partial result goes on), and the slice of the vocabulary the
embedding holds.

It reads the parameters out of the program's own tree (flax names) and
casts one layer, and inside a routed layer one expert, at a time: the
cell's 4.76 G parameters in float32 would not fit beside it.
"""

from __future__ import annotations

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
# the last layer would be blind in the first, where a state kept in a lower
# precision shows most).  ``attn_rel_l2`` compares each layer's mixer output
# (a Mamba-2 layer's ``m`` as the attention layer's) before the residual's
# multiplier.  ``cache_rel_l2`` compares what the caches hold: an attention
# layer's k and v rows at the compared positions; a Mamba-2 layer's
# convolution tail and its state after the last position, whole.  A state
# has no positions to leave out, and the last positions weigh most in it:
# one of them routed otherwise below the layer moved a state by 6 to 15%
# and a tail by 5 to 14% on the chip (PERF.md section 6, PR 34).  So a
# layer's tail is compared where the positions it holds were routed alike
# in every layer below, its state where every compared position was (what
# other experts did to an earlier prompt position is in the limit).  The
# first layer stands under every router and is always compared, exactly.  A layer's limit is one
# number for its pair or a pair of them, (tail, state): the tail carries
# the rounding of its bfloat16 rows, and a state kept in a lower precision
# would hide under a limit that fits the tail.  It nearly hides anyway: the
# products that make a state take bfloat16 operands, whose rounding does not
# average away in a sum of terms of either sign, so a sound state reads 0.5%
# and sixteen roundings of a bfloat16 state bring it to 0.6%.  So the
# precision of what the cache holds is also read directly:
# ``state_bfloat16_share`` limits the share of a state's values that
# bfloat16 represents exactly, which is all of them for a state kept (or
# handed on) in bfloat16 and one in 65,536 for a float32 one.
#
# Routing, as references/dots_vlm.py sets out: with random weights the
# rounding of a few layers moves a router's logit as far as the logits at
# the choice's edge lie apart, and with 36 of 72 experts held nearly every
# token has a held expert near it.  Each decoded position of the check's one
# session is one token, so the program's ``tokens_per_expert`` is its choice
# among the held experts: a layer's rows at such a position are compared
# where the two sides chose the same held experts in every routed layer
# below.  A position of the prompt, whose own choice the counters do not
# give, where the reference's margin (``margin_of``: in the router's
# logits, the least distance of any held expert from the other side of the
# choice's edge) is at least ``routing_margin`` in every routed layer below.
# A token's choice that first differs (above that layer it is another token
# to both sides) where the margin is at least ``routing_margin`` is no
# rounding: not correct.  The reference is never handed the program's
# choices; only the comparison is.  ``min_positions`` of the logits'
# positions have to be left.


def _attention(q, k, v, scale):
    """q: (h, n, d), k and v: (hk, n, d); causal; query head i reads kv head
    i // (h / hk); a block of queries at a time."""
    h, n, d = q.shape
    hk = k.shape[0]
    q = q.reshape(hk, h // hk, n, d) * scale
    cols = jnp.arange(n)[None, :]
    out = []
    for lo in range(0, n, QUERY_BLOCK):
        hi = min(lo + QUERY_BLOCK, n)
        s = jnp.einsum("kgid,kjd->kgij", q[:, :, lo:hi], k)
        s = jnp.where(cols <= jnp.arange(lo, hi)[:, None], s, -jnp.inf)
        out.append(jnp.einsum("kgij,kjd->kgid", jax.nn.softmax(s, -1), v))
    return jnp.concatenate(out, axis=2).reshape(h, n, d)


def recurrence(x, b, c, dt, a, d):
    """The selective state-space recurrence position by position: ``x: (n,
    heads, d_head)``, ``b, c: (n, state)``, ``dt: (n, heads)``, ``a, d:
    (heads,)``.  Returns ``(y (n, heads, d_head), S_{n-1} (heads, d_head,
    state))``."""
    def step(s, inputs):
        x_t, b_t, c_t, dt_t = inputs
        s = (jnp.exp(dt_t * a)[:, None, None] * s
             + (dt_t[:, None] * x_t)[:, :, None] * b_t[None, None, :])
        return s, s @ c_t + d[:, None] * x_t

    s0 = jnp.zeros((*x.shape[1:], b.shape[-1]), jnp.float32)
    s, y = lax.scan(step, s0, (x, b, c, dt))
    return y, s


def _mamba(normed, p, config):
    """A Mamba-2 mixer's output ``(n, hidden)``, the last ``taps - 1`` rows
    of ``u`` before the convolution and the state after the last position."""
    heads, d_head = config["mamba_n_heads"], config["mamba_d_head"]
    state, taps = config["mamba_d_state"], config["mamba_d_conv"]
    inner, n = heads * d_head, normed.shape[0]
    zud = normed @ p["in_proj"]
    z, u, d = jnp.split(zud, [inner, zud.shape[-1] - heads], axis=-1)
    rows = jnp.concatenate([jnp.zeros((taps - 1, u.shape[-1])), u])
    conv = jax.nn.silu(p["conv_bias"] + sum(
        p["conv_kernel"][j] * rows[j:j + n] for j in range(taps)))
    x, b, c = jnp.split(conv, [inner, inner + state], axis=-1)
    dt = jax.nn.softplus(d + p["dt_bias"])
    y, s = recurrence(x.reshape(n, heads, d_head), b, c, dt,
                      -jnp.exp(p["A_log"]), p["D"])
    gated = y.reshape(n, inner) * jax.nn.silu(z)
    out = _rmsnorm(gated, p["gate_norm"]["gamma"],
                   config["rms_norm_eps"]) @ p["out_proj"]
    return out, rows[n:], s


def margin_of(logits, k, first, held):
    """Each token's margin ``(tokens,)``: how far the router's logits are
    from a choice with other held experts in it.  A held expert chosen
    leaves when it falls under the strongest expert passed over, one passed
    over enters when it rises over the weakest chosen: the least of those
    distances over the held experts."""
    ranked = lax.top_k(logits, k + 1)[0]
    last_in, first_out = ranked[:, k - 1, None], ranked[:, k, None]
    mine = logits[:, first:first + held]
    return jnp.where(mine >= last_in, mine - first_out,
                     last_in - mine).min(-1)


def _routed(m, p, config):
    """The routed layer's output, whether each token chose each held expert
    and each token's margin (``margin_of``)."""
    k = config["num_experts_per_tok"]
    first, held = config.get("first_expert", 0), config["num_local_experts"]
    logits = m @ p["router"].astype(jnp.float32)
    top, chosen = lax.top_k(logits, k)
    weights = jax.nn.softmax(top, axis=-1)  # over the chosen logits
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
    out = out + _gated(m, _f32(p["shared"]))
    return out, chose, margin_of(logits, k, first, held)


def forward(params, tokens, config, last: int | None = None):
    """``(logits, inside)`` of one sequence ``(n,)``: logits ``(n or last,
    vocab slice)``; ``inside["counts"]``, per layer the tokens each held
    expert received ``(layers, held)``, ``inside["routed_layers"]``, their
    indices in the stack (all); and for the positions kept
    ``inside["chose"]``, whether each chose each held expert ``(layers, n or
    last, held)``, ``inside["margins"]`` ``(layers, n or last)`` and their
    least ``inside["margin"]``, ``inside["attn"]``, every layer's mixer
    output ``(layers, n or last, hidden)``, and ``inside["kv"]``, per layer
    what its cache holds: an attention layer's k and v rows ``(kv heads, n
    or last, head_dim)`` each, a Mamba-2 layer's convolution tail ``(taps -
    1, channels)`` and its state after the last position ``(heads, d_head,
    state)``."""
    p = params["params"]
    h, hk = config["num_attention_heads"], config["num_key_value_heads"]
    d, eps = config["hidden_size"] // h, config["rms_norm_eps"]
    residual = config["residual_multiplier"]
    n = tokens.shape[0]
    kept = slice(None) if last is None else slice(-last, None)
    chose, margins, attn, kv = [], [], [], []
    with jax.default_matmul_precision("highest"):
        x = config["embedding_multiplier"] * p["embed"]["embedding"][
            tokens].astype(jnp.float32)
        for i, kind in enumerate(config["layer_types"]):
            a = _f32(p[f"attn_layers_{i}"])
            normed = _rmsnorm(x, a["prenorm"]["gamma"], eps)
            if kind == "mamba":
                o, tail, state = _mamba(normed, a, config)
                kv.append((tail, state))
            else:
                qkv = normed @ a["to_qkv"]["kernel"]
                q, k, v = (t.reshape(n, -1, d).transpose(1, 0, 2)
                           for t in jnp.split(
                               qkv, [h * d, (h + hk) * d], axis=-1))
                kv.append((k[:, kept], v[:, kept]))
                o = _attention(q, k, v, config["attention_multiplier"])
                o = o.transpose(1, 0, 2).reshape(n, h * d) @ a["to_out"][
                    "kernel"]
            attn.append(o[kept])
            x = x + residual * o

            f = p[f"ff_layers_{i}"]
            m = _rmsnorm(x, f["norm"]["gamma"].astype(jnp.float32), eps)
            y, c, margin = _routed(m, f, config)
            chose.append(c)
            margins.append(margin)
            x = x + residual * y
        x = _rmsnorm(x, p["final_norm"]["gamma"].astype(jnp.float32), eps)
        if last is not None:
            x = x[-last:]
        head = (p["embed"]["embedding"].T if config["tie_word_embeddings"]
                else p["to_logits"]["kernel"])
        out = x @ head.astype(jnp.float32) / config["logits_scaling"]
    chose, margins = jnp.stack(chose), jnp.stack(margins)
    return out, {"counts": chose.sum(1), "chose": chose[:, kept],
                 "margin": margins.min(0)[kept], "margins": margins[:, kept],
                 "routed_layers": list(range(len(config["layer_types"]))),
                 "mamba_layers": [i for i, kind in enumerate(
                     config["layer_types"]) if kind == "mamba"],
                 "attn": jnp.stack(attn), "kv": kv}


def logits(params, tokens, config, last: int | None = None):
    return forward(params, tokens, config, last)[0]


def routed_alike(routing, inside, far) -> tuple:
    """``(alike, widest)``: ``alike[r, p]``, whether routed layer ``r``
    chose for position ``p`` what the program chose, known for the last
    positions (``routing``: the program's ``chose``, shaped as ``inside``'s
    at those positions; None: none known) and taken from the reference's
    margin before them; ``widest``, the largest margin at which a known
    position's choice first differs (0.0: none does).  Only the first
    routed layer that differs counts: above it the token is another token
    to both sides, and its routing differs at any margin."""
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


def abs_or_nan(x: float) -> float:
    """Orders a NaN above every number: the worst reading of a pair."""
    return float("inf") if x != x else x


def verdict(got: dict, want, inside, limits) -> dict:
    """The comparison that decides ``correct`` (NaN compares false), with
    the numbers it compared.  ``got``: the program's ``logits`` and, shaped
    as ``inside``'s, ``attn`` and ``kv`` (None where the program gave none:
    not correct) and ``routing`` (``routed_alike``); ``want``, ``inside``:
    ``forward``'s, at the same positions.  A layer's rows are compared at
    the positions routed alike in every routed layer below it; a Mamba-2
    layer's tail whole where its positions are among them, its state whole
    where every compared position is (None in the numbers where not: the
    first layer, under every router, always is)."""
    alike, widest = routed_alike(got.get("routing"), inside,
                                 limits["routing_margin"])
    fault = widest >= limits["routing_margin"]
    depth = inside["attn"].shape[0]
    # keep[l]: the positions whose input to layer l saw the same experts
    below = np.asarray(inside["routed_layers"])
    keep = [alike[below < layer].all(0) for layer in range(depth + 1)]
    mamba = set(np.asarray(inside["mamba_layers"]).tolist())

    def rel(mine, its, where=None):  # the last axis but one: the positions
        if mine is None:
            return float("nan")
        if where is None:
            return float(rel_l2(mine, its))
        if not where.any():
            return float("nan")
        return float(rel_l2(mine[..., where, :], its[..., where, :]))

    def limit(name, layer):
        value = limits[name]
        return value[layer] if isinstance(value, (list, tuple)) else value

    logits = rel(got["logits"], want, keep[depth])
    attn = [rel(got["attn"][i], inside["attn"][i], keep[i])
            for i in range(depth)] if got["attn"] is not None else [
        float("nan")] * depth
    def pair(i):  # (k rows, v rows), or (tail, state): None where left out
        if got["kv"] is None:
            return [float("nan")] * 2
        mine, its = got["kv"][i], inside["kv"][i]
        if i not in mamba:
            return [rel(mine[j], its[j], keep[i]) for j in (0, 1)]
        # a tail is the last taps - 1 positions' rows; a state holds every
        # position, the last ones most: each whole, where those positions'
        # inputs saw the same experts
        return [rel(mine[0], its[0]) if keep[i][-its[0].shape[0]:].all()
                else None,
                rel(mine[1], its[1]) if keep[i].all() else None]

    pairs = [pair(i) for i in range(depth)]
    cache = [max((x for x in p if x is not None), key=abs_or_nan,
                 default=None) for p in pairs]

    def cache_within(layer):  # one limit for the pair, or one for each
        value = limit("cache_rel_l2", layer)
        value = value if isinstance(value, (list, tuple)) else (value, value)
        return all(x <= v for x, v in zip(pairs[layer], value)
                   if x is not None)

    # the share of a state's values that bfloat16 holds exactly: one in
    # 65,536 of a float32 state's, all of a state kept in bfloat16
    narrow = [float(np.mean(np.asarray(
        got["kv"][i][1] == got["kv"][i][1].astype(jnp.bfloat16)
    ))) if got["kv"] is not None and i in mamba else 0.0 for i in range(depth)]
    # (not a <= b for each: a NaN must fail)
    within = all(attn[i] <= limit("attn_rel_l2", i) and cache_within(i)
                 and narrow[i] <= limits["state_bfloat16_share"]
                 for i in range(depth))
    kept = int(keep[depth].sum())
    return {"ok": bool(not fault and kept >= limits["min_positions"]
                       and logits <= limits["logits_rel_l2"] and within),
            "logits_rel_l2": logits, "attn_rel_l2": float(np.max(attn)),
            "cache_rel_l2": max((x for x in cache if x is not None),
                                key=abs_or_nan, default=None),
            "positions_compared": kept,
            "positions_compared_by_layer": [int(k.sum()) for k in keep[:-1]],
            "attn_rel_l2_by_layer": attn, "cache_rel_l2_by_layer": cache,
            "cache_rel_l2_pairs_by_layer": pairs,
            "state_bfloat16_share": float(np.max(narrow)),
            "logits_rel_l2_all_positions": float(rel_l2(got["logits"], want)),
            "routed_differently_beyond_margin": fault,
            "widest_margin_routed_differently": widest,
            "margins": [round(float(x), 5) for x in inside["margin"]]}
