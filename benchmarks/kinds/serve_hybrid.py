"""Traffic driver "serve_hybrid": ``serve_sessions`` for a model most of
whose layers are Mamba-2 mixers, with a recurrent state where an attention
layer has rows.  Workload file, configuration file, window and the timed
programs are ``serve_sessions``'s, which is imported and not repeated; two
things differ.

*The weights.*  ``serve_sessions._weights`` draws every tensor normal at
``fan_in ** -0.5`` from ``leaf.shape[-2]``, and has no rule for a mixer's
vectors and convolution.  They are drawn here by the Mamba-2 reference
initialisation (the configuration file's ``assumed``): ``A_log = log U[1,
16]``, ``dt_bias`` the inverse softplus of a step drawn log-uniform in
[0.001, 0.1], the skip ``D`` one (all three float32, 384 values a layer),
the convolution's taps and bias uniform in +-0.5 (bfloat16).  It matters to
the check and not only to taste: with a normal ``dt_bias`` every state
forgets within a few positions, and a state kept in a lower precision would
pass.  The mixer's two matrices and two norms go by ``serve_sessions``'s
rule.  ``weights.embedding_gain`` multiplies the embedding as drawn (the
configuration file says why: with the tied head and the embedding's
multiplier, random weights at ``hidden_size ** -0.5`` make every session
repeat its first token, and a run then routes four fixed tokens).

*What the caches hold, and the check.*  ``init_cache`` gives a Mamba-2 layer
a pair that is no rows: the convolution's tail ``(sessions, 1, taps - 1,
channels)`` and one float32 state ``(sessions, heads, d_head, state)``,
whatever the capacity.  ``_cache_rows`` reads an attention layer's k and v
rows at the compared positions and a Mamba-2 layer's tail and state as they
stand after the last decoded position, which is how the reference's
``inside["kv"]`` is shaped.  (``serve_sessions._fill`` takes the tree as it
stands: a state's and a tail's axis 2 is not the capacity, so both are
filled whole with unit normal values, and the timed prefill, which starts
every session from a zero state, overwrites them: the states the decode
starts from are the prompt's, a state has no positions to fill.)  As in
``serve_latent``, each decoded position's own routing (the program's
counters) is handed to the comparison, not to the reference.  The check's
line also gets ``ssm``: the bytes the steps' counters say the states moved
(``ssm_state_bytes``: read and written) beside what the shapes say, and the
prefill's ``ssm_chunks`` and ``ssm_padded_positions``.
"""

from __future__ import annotations

import importlib
import types
import zlib
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np

from . import random_tokens, serve_sessions as base
from .serve_sessions import (  # noqa: F401
    COUNTERS, PROBES, RingTransformer, _attn_rows, _by_layer,
    _last_rows, window)

_drawn_there = base._weights  # before ``setup`` puts this file's in its place
# a mixer's leaves that serve_sessions._weights has no rule for
DRAWN_HERE = ("A_log", "dt_bias", "D", "conv_kernel", "conv_bias")


def shape(ctx) -> dict:
    c = ctx.config
    kinds = c["layer_types"]
    return {**base.shape(ctx),
            "ssm_layers": kinds.count("mamba"),
            "attention_layers": kinds.count("attention"),
            "ssm_heads": c["mamba_n_heads"], "ssm_head_dim": c["mamba_d_head"],
            "ssm_state": c["mamba_d_state"],
            "prompt_tokens": ctx.workload["prompt_tokens"]}


def _draw(name: str, key, leaf):
    """One of ``DRAWN_HERE``: the Mamba-2 reference initialisation."""
    k = jax.random.fold_in(key, zlib.crc32(name.encode()) & 0x7FFFFFFF)
    if name.endswith("['A_log']"):
        return jnp.log(jax.random.uniform(k, leaf.shape, jnp.float32, 1., 16.))
    if name.endswith("['dt_bias']"):
        dt = jnp.exp(jax.random.uniform(
            k, leaf.shape, jnp.float32, np.log(0.001), np.log(0.1)))
        return dt + jnp.log(-jnp.expm1(-dt))  # softplus's inverse
    if name.endswith("['D']"):
        return jnp.ones(leaf.shape, jnp.float32)
    return jax.random.uniform(k, leaf.shape, jnp.float32, -0.5, 0.5).astype(
        jnp.bfloat16)


def _weights(model, key, spec: dict):
    """``serve_sessions._weights`` for every leaf it has a rule for, and
    the mixers' vectors and convolutions by ``_draw``."""
    def mine(path) -> bool:
        return jax.tree_util.keystr(path).endswith(
            tuple(f"['{leaf}']" for leaf in DRAWN_HERE))

    def without(tree):  # the tree less the leaves drawn here
        flat = jax.tree_util.tree_flatten_with_path(tree)[0]
        out: dict = {}
        for path, leaf in flat:
            if not mine(path):
                node = out
                for part in path[:-1]:
                    node = node.setdefault(part.key, {})
                node[path[-1].key] = leaf
        return out

    rest = _drawn_there(types.SimpleNamespace(
        init=lambda k, tokens: without(model.init(k, tokens))), key, spec)
    shapes = jax.eval_shape(
        model.init, key, jnp.zeros((1, 128), jnp.int32))["params"]
    for path, leaf in jax.tree_util.tree_flatten_with_path(shapes)[0]:
        if mine(path):
            node = rest["params"]
            for part in path[:-1]:
                node = node[part.key]
            node[path[-1].key] = _draw(jax.tree_util.keystr(path), key, leaf)
    gain = spec.get("embedding_gain", 1.0)
    if gain != 1.0:
        embed = rest["params"]["embed"]
        embed["embedding"] = (embed["embedding"] * gain).astype(jnp.bfloat16)
    return rest


def _cache_rows(cache, positions) -> list:
    """Per layer a pair, float32: an attention layer's k and v rows ``(kv
    heads, positions, head_dim)`` at ``positions``; a Mamba-2 layer's
    convolution tail ``(taps - 1, channels)`` and state ``(heads, d_head,
    state)`` as the one session's cache holds them now (the pair's shapes
    differ; an attention layer's do not)."""
    def rows(entry):
        return entry[0][:, positions % entry.shape[2]].astype(jnp.float32)

    return [(rows(k), rows(v)) if k.shape == v.shape
            else (k[0, 0].astype(jnp.float32), v[0].astype(jnp.float32))
            for k, v in zip(cache["k"], cache["v"])]


def _routed(counters) -> list:
    """One call's routed layers' counters, in the stack's order."""
    return [c for c in _by_layer(counters.get(COUNTERS, {}))
            if "tokens_per_expert" in c]


def _ssm(counters, name: str) -> int:
    """A Mamba-2 counter of one call, summed over the layers that sow it."""
    return int(sum(int(layer[name])
                   for layer in counters.get(COUNTERS, {}).values()
                   if name in layer))


def _check(ctx, model, params, key) -> dict:
    """``serve_sessions._check`` over both kinds of cache: one session,
    prefill, then decoding token by token; logits, every layer's mixer output
    and what every layer's cache holds against the reference's full forward
    at the same positions, each decoded position's own routing (from the
    program's counters) handed to the comparison, not to the reference."""
    cfg, c = ctx.config, ctx.workload["check"]
    ref = importlib.import_module(
        f"..references.{cfg['reference']}", __package__)
    n, m = c["prompt_tokens"], c["decode_tokens"]
    tokens = random_tokens(key, (1, n + m), cfg["vocab_size"])
    given = np.asarray(tokens)  # sliced on the host: no program per slice
    cache = model.apply({}, 1, c["cache_capacity"],
                        method=RingTransformer.init_cache)
    prefill = jax.jit(lambda p, t, c: _last_rows(model.apply(
        p, t, c, method=RingTransformer.prefill,
        mutable=[COUNTERS, PROBES])), donate_argnums=2)
    decode = jax.jit(lambda p, t, c, i: _last_rows(model.apply(
        p, t, c, i, method=RingTransformer.decode_step,
        mutable=[COUNTERS, PROBES])), donate_argnums=2)
    (logits, cache), sown = prefill(params, given[:, :n], cache)
    got, attn, calls = [logits], [_attn_rows(sown)], [sown]
    for i in range(n, n + m):
        (logits, cache), sown = decode(params, given[:, i], cache, np.int32(i))
        got.append(logits)
        attn.append(_attn_rows(sown))
        calls.append(sown)
    want, inside = jax.jit(
        lambda p, t: ref.forward(p, t[0], cfg, last=m + 1))(params, tokens)

    # (calls, routed layers, held): a step's counts are its one token's choice
    held = np.stack([np.stack([np.asarray(layer["tokens_per_expert"])
                               for layer in _routed(call)])
                     for call in calls])
    got = {"logits": jnp.concatenate(got),
           "attn": jnp.stack([jnp.stack(a) for a in attn], 1),
           "kv": _cache_rows(cache, np.arange(n - 1, n + m)),
           "routing": {"chose": held[1:].transpose(1, 0, 2) > 0}}
    out = {**ref.verdict(got, want, inside, cfg["limits"]),
           "positions": m + 1}
    ref_counts = np.asarray(inside["counts"])
    ref_steps = np.asarray(inside["chose"])[:, -m:].transpose(1, 0, 2)
    out["routing"] = {
        "pairs_on_held": int(held.sum()),
        "reference_pairs_on_held": int(ref_counts.sum()),
        # each pair the two route differently moves two counts by one
        "pairs_that_differ_at_least": int(
            np.abs(held.sum(0) - ref_counts).sum() // 2),
        "decoded_positions_routed_differently": [
            i for i, (mine, its) in enumerate(zip(held[1:] > 0, ref_steps))
            if (mine != its).any()],
        "prefill_held_share": [float(layer["held_share"])
                               for layer in _routed(calls[0])],
        "experts_touched_per_decode_step": [
            int((step > 0).sum()) for step in held[1:]],
    }
    layers = cfg["layer_types"].count("mamba")
    state = cfg["mamba_n_heads"] * cfg["mamba_d_head"] * cfg["mamba_d_state"] * 4
    chunk = cfg["mamba_chunk_size"]
    out["ssm"] = {
        "state_bytes_per_step": [_ssm(call, "ssm_state_bytes")
                                 for call in calls[1:]],
        # every Mamba-2 layer reads and writes the one session's state
        "state_bytes_by_shape_per_step": [2 * layers * state] * m,
        "prefill_chunks": _ssm(calls[0], "ssm_chunks"),
        "prefill_chunks_by_shape": layers * -(-n // chunk),
        "prefill_padded_positions": _ssm(calls[0], "ssm_padded_positions"),
        "prefill_padded_positions_by_shape": layers * (-n % chunk),
    }
    return out


def setup(ctx):
    with mock.patch.object(base, "_check", _check), \
            mock.patch.object(base, "_weights", _weights):
        return base.setup(ctx)
