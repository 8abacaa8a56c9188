"""Traffic driver "serve_sessions": several long sessions on one chip,
decoded together.  The window is the prefill of every session's new prompt
in one call and the fetch of their first tokens, then one jitted decode
step per position from deep in the caches, a token for each session, all of
them fetched to the host before the next step (a served token is one the
client holds).

The model is whatever the configuration file describes: it is built by
``RingTransformer.from_config(ModelConfig.from_dict(config))`` and checked
against ``references/<config["reference"]>.py``, so a later serving
configuration is a configuration file, a reference and a workload file.  It
calls what ``examples/generate.py`` calls: ``init_cache`` (a windowed layer
gets a ring buffer of its window, a full layer ``cache_capacity`` slots),
``prefill`` and ``decode_step``, compiled ahead of time with the cache
donated.  Greedy sampling and the position counter ride in the jitted step,
so a step costs one dispatch and one fetch.  The server holds bfloat16
weights.  A token of -1 marks non-finite logits.

Workload file: ``sessions`` (decoded together, all at the same position);
``cache_capacity`` (positions a session); ``prompt_tokens`` (a session,
prefilled at positions [0, prompt)); ``decode_start`` (set-up fills the
full layers' positions [prompt, decode_start) and every slot of the ring
buffers with unit normal keys and values: the turns already served);
``max_steps``; ``warmup_steps``; ``model`` (``RingTransformer`` options of
this cell: how it runs, not what it computes); ``check`` (``prompt_tokens``,
``decode_tokens`` and ``cache_capacity`` of the one-session correctness
check: ``prefill`` + ``decode_step`` against the reference's full forward
at the same positions, in the logits, in every layer's attention output
(the program's ``probes``) and in the k and v rows left in every layer's
cache); ``trace.decode_seconds``.  The check runs the program's own calls
at one session and its own capacity; of the timed programs (``sessions``
rows, ``cache_capacity`` slots) it sees only that their tokens are finite.

Configuration file, beside the published keys: ``reference`` (module under
``references/``: ``forward(params, tokens, config, last) -> (logits,
inside)`` and ``verdict(got, want, inside, limits) -> {"ok": ...,
numbers}``), ``limits`` (that comparison's, set from chip readings that
``limits_why`` gives) and ``weights``: ``expert_bias_std`` (the routers'
selection bias is drawn normal at this scale, so that the choice and the
weights differ as they do in a trained checkpoint) and ``norm_gamma`` (a
fragment of a norm's path in the program's parameter tree -> its weight,
where it is not one: the configuration file says why).

Weights are drawn here and not by ``model.init``: that makes float32
tensors, 17 GB of them for the first configuration served.  Norm weights
are one but for ``norm_gamma``'s, ``expert_bias`` as above, every other
tensor normal at ``fan_in ** -0.5`` (the embedding at ``dim ** -0.5``), in
bfloat16.
"""

from __future__ import annotations

import importlib
import re
import time
import zlib

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

try:
    from ring_attention_tpu.models import ModelConfig, RingTransformer
except ImportError as e:  # a checkout from before the configuration seam
    raise SystemExit(
        f"serve_sessions: this checkout's program cannot build a model from "
        f"a configuration file ({e})")

from ..reduce import percentile
from . import random_tokens, seed_key, span

COUNTERS = "counters"  # the program's flax collection of routing counts
PROBES = "probes"  # and of each layer's attention output (attn_out_<i>)


def shape(ctx) -> dict:
    c, wl = ctx.config, ctx.workload
    layers = ModelConfig.from_dict(c).layers
    return {
        "hidden": c["hidden_size"], "ffn": c["intermediate_size"],
        "heads": c["num_attention_heads"],
        "kv_heads": c["num_key_value_heads"],
        "dim_head": c.get("head_dim")
        or c["hidden_size"] // c["num_attention_heads"],
        "vocab": c["vocab_size"], "depth": len(layers), "chips": ctx.chips,
        "decode_start": wl["decode_start"], "sessions": wl["sessions"],
        "full_layers": sum(layer.window is None for layer in layers),
        "sliding_layers": sum(layer.window is not None for layer in layers),
        "window": c.get("sliding_window"),
    }


def _greedy(logits):
    token = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return jnp.where(jnp.isfinite(logits).all(axis=-1), token, -1)


def _weights(model, key, spec: dict):
    """The model's parameter tree in bfloat16, drawn leaf by leaf; ``spec``
    is the configuration file's ``weights``."""
    shapes = jax.eval_shape(
        model.init, key, jnp.zeros((1, 128), jnp.int32))["params"]

    def draw(path, leaf):
        name = jax.tree_util.keystr(path)
        # (a str's hash() is salted per process; a checksum is not)
        k = jax.random.fold_in(key, zlib.crc32(name.encode()) & 0x7FFFFFFF)
        if "gamma" in name:
            gain = next((g for part, g in spec.get("norm_gamma", {}).items()
                         if part in name), 1.0)
            return jnp.full(leaf.shape, gain, jnp.bfloat16)
        if "expert_bias" in name:
            return spec.get("expert_bias_std", 0.0) * jax.random.normal(
                k, leaf.shape, jnp.float32)
        fan_in = leaf.shape[-1 if "embedding" in name else -2]
        return jax.random.normal(k, leaf.shape, jnp.bfloat16) * fan_in ** -0.5

    return {"params": jax.tree_util.tree_map_with_path(draw, shapes)}


def _fill(model, sessions, capacity, lo, hi, key):
    """A cache whose full layers hold unit normal keys and values at
    positions [lo, hi) and whose ring buffers hold them in every slot."""
    cache = model.apply({}, sessions, capacity,
                        method=RingTransformer.init_cache)
    leaves, tree = jax.tree.flatten(cache)
    for i, leaf in enumerate(leaves):
        first, n = (lo, hi - lo) if leaf.shape[2] == capacity else (
            0, leaf.shape[2])
        served = jax.random.normal(
            jax.random.fold_in(key, i),
            (*leaf.shape[:2], n, leaf.shape[3]), leaf.dtype)
        leaves[i] = lax.dynamic_update_slice(leaf, served, (0, 0, first, 0))
    return jax.tree.unflatten(tree, leaves)


def setup(ctx):
    cfg, wl = ctx.config, ctx.workload
    model = RingTransformer.from_config(
        ModelConfig.from_dict(cfg), mesh=None, use_ring=False,
        dtype=jnp.bfloat16, **wl["model"])
    k_init, k_prompt, k_fill, k_check = jax.random.split(
        seed_key(ctx.seed), 4)
    sessions, prompt_n = wl["sessions"], wl["prompt_tokens"]
    start = wl["decode_start"]

    params = jax.jit(lambda k: _weights(
        model, k, cfg.get("weights", {})))(k_init)
    prompt = random_tokens(k_prompt, (sessions, prompt_n), cfg["vocab_size"])
    cache = jax.jit(lambda k: _fill(
        model, sessions, wl["cache_capacity"], prompt_n, start, k))(k_fill)
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
        params, jnp.zeros((sessions,), jnp.int32), cache, first_pos).compile()
    ctx.part("compile_or_load")

    check = _check(ctx, model, params, k_check)
    ctx.part("check")

    state = {"prefill": prefill, "decode": decode, "params": params,
             "prompt": prompt, "cache": cache, "first_pos": first_pos,
             "check": check}
    _session(state, wl["warmup_steps"])
    ctx.part("warmup")
    return state


def _expert_counts(counters) -> np.ndarray:
    """(routed layers, held experts) from one call's counters, the layers
    in the stack's order; (0, 0) for a model that routes nothing."""
    rows = [np.asarray(c["tokens_per_expert"])
            for c in _by_layer(counters.get(COUNTERS, {}))]
    return np.stack(rows) if rows else np.zeros((0, 0), np.int64)


def _by_layer(collection: dict) -> list:
    """A collection's entries in the stack's order (names end in the
    layer's index)."""
    return [v for _, v in sorted(
        collection.items(),
        key=lambda kv: int(re.search(r"\d+$", kv[0]).group()))]


def _last_rows(out):
    """A call's result with its probes cut to the last position inside the
    jit (the prefill's are ``(1, prompt, hidden)`` a layer otherwise)."""
    result, sown = out
    return result, {**sown, PROBES: jax.tree.map(
        lambda a: a[:, -1:], sown.get(PROBES, {}))}


def _attn_rows(sown) -> list:
    """(layers, hidden): each layer's attention output at the call's last
    position of the one session; [] where the program has no probes."""
    return [a[0, 0] for a in _by_layer(sown[PROBES])]


def _cache_rows(cache, positions) -> jax.Array:
    """(layers, 2, kv heads, positions, head_dim) float32: the k and v rows
    the program left in the one session's caches at ``positions`` (a ring
    buffer keeps a position at slot ``position % size``).  An int8 cache's
    entry is a (values, scales) pair (``init_cache``): multiplied out."""
    def rows(entry):
        if isinstance(entry, (tuple, list)):
            values, scales = entry
            entry = values.astype(jnp.float32) * scales[..., None]
        return entry[0][:, positions % entry.shape[2]].astype(jnp.float32)

    return jnp.stack([jnp.stack([rows(k), rows(v)])
                      for k, v in zip(cache["k"], cache["v"])])


def _check(ctx, model, params, key) -> dict:
    """One session: prefill, then decoding through both kinds of cache;
    logits, every layer's attention output and the rows left in every
    layer's cache, against the reference's full forward at the same
    positions (tokens are given, not sampled: with random weights the
    largest logit turns on rounding).  The program's routing counts are
    read from its counters and set beside the reference's own; the
    reference is never given the program's choices."""
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
    (logits, cache), counters = prefill(params, given[:, :n], cache)
    got, counts = [logits], _expert_counts(counters)
    attn = [_attn_rows(counters)]  # each position's (layers, hidden)
    prefill_share = [float(v["held_share"])
                     for v in counters.get(COUNTERS, {}).values()]
    steps = []  # each decoded position's own (routed layers, held) choices
    for i in range(n, n + m):
        (logits, cache), counters = decode(
            params, given[:, i], cache, np.int32(i))
        got.append(logits)
        attn.append(_attn_rows(counters))
        steps.append(_expert_counts(counters))
    want, routing = jax.jit(
        lambda p, t: ref.forward(p, t[0], cfg, last=m + 1))(params, tokens)
    got = {"logits": jnp.concatenate(got),
           "attn": jnp.stack([jnp.stack(a) for a in attn], 1)
           if attn[0] else None,
           "kv": _cache_rows(cache, np.arange(n - 1, n + m))}
    out = {**ref.verdict(got, want, routing, cfg["limits"]),
           "positions": m + 1}
    if counts.size:
        counts = counts + sum(steps)
        ref_counts = np.asarray(routing["counts"])
        ref_steps = np.asarray(routing["chose"])[:, -m:].transpose(1, 0, 2)
        out["routing"] = {
            "pairs_on_held": int(counts.sum()),
            "reference_pairs_on_held": int(ref_counts.sum()),
            # each pair the two route differently moves two counts by one
            "pairs_that_differ_at_least": int(
                np.abs(counts - ref_counts).sum() // 2),
            # a decoded position is one token: its counts are its choices
            "decoded_positions_routed_differently": [
                i for i, (mine, its) in enumerate(zip(steps, ref_steps))
                if (mine != its).any()],
            "prefill_held_share": prefill_share,
            "experts_touched_per_decode_step": [
                int((step > 0).sum()) for step in steps],
        }
    return out


def _session(state, max_steps, seconds=None, from_first_token=False):
    """Prefill and the first tokens, then decode until ``max_steps`` steps
    are served or ``seconds`` have passed, counted from the start or, for a
    traced run, from the first tokens.  Leaves the cache in ``state`` for
    the next call, which overwrites the same positions."""
    params, decode = state["params"], state["decode"]
    start = time.perf_counter()
    with span("bench/prefill"):
        token, cache = state["prefill"](params, state["prompt"],
                                        state["cache"])
        tokens = [np.asarray(token)]
    stamps = [time.perf_counter()]
    zero = stamps[0] if from_first_token else start
    pos = state["first_pos"]
    while len(tokens) <= max_steps and (
            seconds is None or stamps[-1] - zero < seconds):
        with span("bench/token"):
            token, cache, pos = decode(params, token, cache, pos)
        with span("bench/fetch"):
            tokens.append(np.asarray(token))
        stamps.append(time.perf_counter())
    state["cache"] = cache
    gaps_ms = [1e3 * (b - a) for a, b in zip(stamps, stamps[1:])]
    return np.stack(tokens), stamps[0] - start, gaps_ms


def window(ctx, state):
    wl = ctx.workload
    if ctx.trace:
        tokens, prefill_s, gaps_ms = _session(
            state, wl["max_steps"], wl["trace"]["decode_seconds"],
            from_first_token=True)
    else:
        tokens, prefill_s, gaps_ms = _session(
            state, wl["max_steps"], ctx.seconds)
    failed = int((tokens < 0).any(axis=1).sum())  # steps, as attempted is
    return {
        "attempted": len(tokens),
        "failed": failed,
        "finite": failed == 0,
        "end_to_end": {"prefill_s": prefill_s,
                       "decode_gap_ms_p95": percentile(gaps_ms, 95)},
        "units": {"token": len(gaps_ms)},
        "series": {"decode_gap_ms": gaps_ms},
        "log": {"steps": len(tokens), "sessions": int(tokens.shape[1]),
                "first_tokens": tokens[:2].tolist(),
                "prefill_s": prefill_s,
                "decode_gap_ms_p50": percentile(gaps_ms, 50),
                "decode_gap_ms_p95": percentile(gaps_ms, 95),
                "decode_gap_ms_max": max(gaps_ms)},
    }
