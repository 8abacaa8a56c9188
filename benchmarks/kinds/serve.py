"""Traffic driver "serve": one long session on one chip.  The window is
the prefill of a new prompt and the fetch of its first token, then one
jitted decode step per token from deep in the cache, each token fetched to
the host before the next step (a served token is one the client holds).

It calls what ``examples/generate.py`` calls: ``RingTransformer``'s
``init_cache``, ``prefill`` and ``decode_step``, compiled ahead of time
with the cache donated.  Greedy sampling and the position counter ride in
the jitted step, so a token costs one dispatch and one fetch.  The server
holds bfloat16 weights, cast once in set-up.  A token of -1 marks
non-finite logits.

Workload file: ``cache_capacity``; ``prompt_tokens`` (prefilled at
positions [0, prompt)); ``decode_start`` (positions [prompt, decode_start)
are filled in set-up with unit normal keys and values: the turns the
session has already served); ``max_tokens``; ``model`` (``RingTransformer``
options of this cell); ``warmup_tokens``; ``check`` (prompt, decoded tokens
and cache capacity of the correctness session); ``trace.decode_seconds``.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ring_attention_tpu.models import RingTransformer

from .. import reference
from ..reduce import percentile
from . import model_args, model_shape, random_tokens, seed_key, span


def shape(ctx) -> dict:
    return {**model_shape(ctx),
            "decode_start": ctx.workload["decode_start"]}


def _greedy(logits):
    token = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return jnp.where(jnp.isfinite(logits).all(axis=-1), token, -1)


def setup(ctx):
    cfg, wl = ctx.config, ctx.workload
    model = RingTransformer(**model_args(cfg), mesh=None, use_ring=False,
                            **wl["model"])
    k_init, k_prompt, k_fill, k_check = jax.random.split(
        seed_key(ctx.seed), 4)
    prompt_n, start = wl["prompt_tokens"], wl["decode_start"]

    params = jax.jit(lambda k: jax.tree.map(
        lambda w: w.astype(jnp.bfloat16),
        model.init(k, jnp.zeros((1, 128), jnp.int32))))(k_init)
    prompt = random_tokens(k_prompt, (1, prompt_n), cfg["vocab_size"])

    def session(key):
        # init_cache reads the model's sizes, not its weights
        cache = model.apply({}, 1, wl["cache_capacity"],
                            method=RingTransformer.init_cache)
        leaves, tree = jax.tree.flatten(cache)
        for i, leaf in enumerate(leaves):
            served = jax.random.normal(
                jax.random.fold_in(key, i),
                (*leaf.shape[:2], start - prompt_n, leaf.shape[3]),
                leaf.dtype)
            leaves[i] = lax.dynamic_update_slice(
                leaf, served, (0, 0, prompt_n, 0))
        return jax.tree.unflatten(tree, leaves)

    cache = jax.jit(session)(k_fill)
    jax.block_until_ready((params, prompt, cache))
    ctx.part("weights_and_state")

    def prefill_fn(p, tokens, cache):
        logits, cache = model.apply(p, tokens, cache,
                                    method=RingTransformer.prefill)
        return _greedy(logits), cache

    def decode_fn(p, token, cache, pos):
        logits, cache = model.apply(p, token, cache, pos,
                                    method=RingTransformer.decode_step)
        return _greedy(logits), cache, pos + 1

    first_pos = jnp.int32(start)
    prefill = jax.jit(prefill_fn, donate_argnums=2).lower(
        params, prompt, cache).compile()
    decode = jax.jit(decode_fn, donate_argnums=2).lower(
        params, jnp.zeros((1,), jnp.int32), cache, first_pos).compile()
    ctx.part("compile_or_load")

    check = _check(ctx, model, params, k_check)
    ctx.part("check")

    state = {"prefill": prefill, "decode": decode, "params": params,
             "prompt": prompt, "cache": cache, "first_pos": first_pos,
             "check": check}
    _session(state, wl["warmup_tokens"])
    ctx.part("warmup")
    return state


def _check(ctx, model, params, key) -> dict:
    """Prefill then decoding through a small cache, logits against the
    reference's full forward at the same positions (tokens are given, not
    sampled: with random weights the largest logit turns on rounding)."""
    cfg, c = ctx.config, ctx.workload["check"]
    n, m = c["prompt_tokens"], c["decode_tokens"]
    tokens = random_tokens(key, (1, n + m), cfg["vocab_size"])
    given = np.asarray(tokens)  # sliced on the host: no program per slice
    cache = model.apply({}, 1, c["cache_capacity"],
                        method=RingTransformer.init_cache)
    prefill = jax.jit(lambda p, t, c: model.apply(
        p, t, c, method=RingTransformer.prefill), donate_argnums=2)
    decode = jax.jit(lambda p, t, c, i: model.apply(
        p, t, c, i, method=RingTransformer.decode_step), donate_argnums=2)
    logits, cache = prefill(params, given[:, :n], cache)
    got = [logits]
    for i in range(n, n + m):
        logits, cache = decode(params, given[:, i], cache, np.int32(i))
        got.append(logits)
    rel = float(jax.jit(lambda p, t, got: reference.rel_l2(
        jnp.concatenate(got),
        reference.logits(p, t[0], cfg, last=m + 1)))(params, tokens, got))
    return {"ok": reference.verdict(rel), "logits_rel_l2": rel,
            "positions": m + 1}


def _session(state, max_tokens, seconds=None, from_first_token=False):
    """Prefill and first token, then decode until ``max_tokens`` are served
    or ``seconds`` have passed, counted from the start or, for a traced
    run, from the first token.  Leaves the cache in ``state`` for the next
    call, which overwrites the same positions."""
    params, decode = state["params"], state["decode"]
    start = time.perf_counter()
    with span("bench/prefill"):
        token, cache = state["prefill"](params, state["prompt"],
                                        state["cache"])
        tokens = [int(np.asarray(token)[0])]
    stamps = [time.perf_counter()]
    zero = stamps[0] if from_first_token else start
    pos = state["first_pos"]
    while len(tokens) <= max_tokens and (
            seconds is None or stamps[-1] - zero < seconds):
        with span("bench/token"):
            token, cache, pos = decode(params, token, cache, pos)
        with span("bench/fetch"):
            tokens.append(int(np.asarray(token)[0]))
        stamps.append(time.perf_counter())
    state["cache"] = cache
    gaps_ms = [1e3 * (b - a) for a, b in zip(stamps, stamps[1:])]
    return tokens, stamps[0] - start, gaps_ms


def window(ctx, state):
    wl = ctx.workload
    if ctx.trace:
        tokens, prefill_s, gaps_ms = _session(
            state, wl["max_tokens"], wl["trace"]["decode_seconds"],
            from_first_token=True)
    else:
        tokens, prefill_s, gaps_ms = _session(
            state, wl["max_tokens"], ctx.seconds)
    failed = sum(t < 0 for t in tokens)
    return {
        "attempted": len(tokens),
        "failed": failed,
        "finite": failed == 0,
        "end_to_end": {"prefill_s": prefill_s,
                       "decode_gap_ms_p95": percentile(gaps_ms, 95)},
        "units": {"token": len(gaps_ms)},
        "series": {"decode_gap_ms": gaps_ms},
        "log": {"tokens": len(tokens), "first_tokens": tokens[:8],
                "prefill_s": prefill_s,
                "decode_gap_ms_p50": percentile(gaps_ms, 50),
                "decode_gap_ms_p95": percentile(gaps_ms, 95),
                "decode_gap_ms_max": max(gaps_ms)},
    }
