"""Traffic driver "serve_latent": ``serve_sessions`` for a model whose
attention layers keep a latent cache.  Workload file, configuration file,
window, weights and the timed programs are ``serve_sessions``'s, which is
imported and not repeated; the correctness check is this file's, because
two things of it differ.

*What the caches hold.*  ``init_cache`` gives a latent layer a pair that is
not k rows and v rows of one shape: the rotated positional keys, transposed,
``(sessions, 1, qk_rope, capacity)`` and the normed latents ``(sessions, 1,
capacity, kv_lora_rank)``.  ``_cache_rows`` reads both at the compared
positions, per layer ``((1, positions, qk_rope), (1, positions,
kv_lora_rank))``, which is how the reference's ``inside["kv"]`` is shaped.
(``serve_sessions._fill`` fits as it stands: the latents' capacity is on
axis 2 and positions [prompt, decode_start) are filled; the positional
keys' array is filled whole, the prompt's part overwritten by the prefill
and what lies past the decoded position masked.)

*The program's own counts go into the comparison.*  Each decoded position
of the one session is one token, so a step's ``tokens_per_expert`` is that
token's choice among the held experts; the reference's ``verdict`` compares
a position where the two sides chose alike
(``references/dots_vlm.py`` says why a margin alone does not do here).  The
check's line also gets ``latent``: the bytes the steps' counters say the
latent layers read (``latent_cache_bytes_read``) beside what the shapes
say, and the pairs by group beside the reference's.
"""

from __future__ import annotations

import importlib
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np

from . import random_tokens, serve_sessions as base
from .serve_sessions import (  # noqa: F401
    COUNTERS, PROBES, RingTransformer, _attn_rows, _by_layer, _last_rows,
    shape, window)


def _cache_rows(cache, positions) -> list:
    """Per layer the rotated positional keys ``(1, positions, qk_rope)`` and
    the latents ``(1, positions, kv_lora_rank)`` the program left in the one
    session's cache, float32."""
    return [(k[0][:, :, positions].swapaxes(1, 2).astype(jnp.float32),
             v[0][:, positions].astype(jnp.float32))
            for k, v in zip(cache["k"], cache["v"])]


def _routed(counters) -> list:
    """One call's routed layers' counters, in the stack's order."""
    return [c for c in _by_layer(counters.get(COUNTERS, {}))
            if "tokens_per_expert" in c]


def _check(ctx, model, params, key) -> dict:
    """``serve_sessions._check`` over a latent cache: one session, prefill,
    then decoding token by token; logits, every layer's attention output and
    what every layer's cache holds against the reference's full forward at
    the same positions, each decoded position's own routing (from the
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

    def per_call(name, width):  # (calls, routed layers, width) of a counter
        # (a program whose router has no groups sows none: zeros, which no
        # reference with groups agrees with)
        return np.stack([np.stack([np.asarray(layer.get(name, np.zeros(
            width, np.int32))) for layer in _routed(call)])
                         for call in calls])

    held = per_call("tokens_per_expert", cfg["n_routed_experts"])
    groups = per_call("pairs_per_group", cfg["n_group"])
    got = {"logits": jnp.concatenate(got),
           "attn": jnp.stack([jnp.stack(a) for a in attn], 1),
           "kv": _cache_rows(cache, np.arange(n - 1, n + m)),
           # a step's counts are its one token's choice
           "routing": {"chose": held[1:].transpose(1, 0, 2) > 0}}
    out = {**ref.verdict(got, want, inside, cfg["limits"]),
           "positions": m + 1}
    ref_counts = np.asarray(inside["counts"])
    ref_steps = np.asarray(inside["chose"])[:, -m:].transpose(1, 0, 2)
    row = (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]) * 2  # bfloat16
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
    out["latent"] = {
        "cache_bytes_read_per_step": [
            int(sum(int(layer["latent_cache_bytes_read"])
                    for layer in call[COUNTERS].values()
                    if "latent_cache_bytes_read" in layer))
            for call in calls[1:]],
        # every latent layer reads positions [0, p] of the one session
        "cache_bytes_by_shape_per_step": [
            cfg["num_hidden_layers"] * (n + i + 1) * row for i in range(m)],
        "pairs_per_group": groups.sum(0).tolist(),
    }
    return out


def setup(ctx):
    with mock.patch.object(base, "_check", _check):
        return base.setup(ctx)
