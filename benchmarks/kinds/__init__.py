"""Traffic drivers, one module per ``kind`` a workload file may name.

A driver is three functions.  ``setup(ctx)`` builds the system under test
from the seed on the device, compiles or loads the cell's programs, checks
them against the plain reference and warms them up, calling
``ctx.part(name)`` as each part of the set-up ends; it returns whatever
``window`` needs.  ``window(ctx, state)`` is the timed loop and returns a
dict: ``attempted``, ``failed``, ``finite``, ``end_to_end`` (metric name ->
value; a traced run's are of its short window and feed the ``mfu``
reducer), ``units`` (how many steps or tokens the window held, for the
per-unit reducers), ``series`` (host-clock samples a reducer may read) and
``log`` (printed on an earlier line).  ``shape(ctx)`` gives the sizes the
arithmetic in ``reduce.py`` works from.

What the two drivers share is here.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def seed_key(seed: int) -> jax.Array:
    """A key from any whole number up to 2**62: the low 31 bits seed it and
    the rest are folded in, so no seed overflows a signed 32-bit int."""
    return jax.random.fold_in(
        jax.random.PRNGKey(seed & 0x7FFFFFFF), (seed >> 31) & 0x7FFFFFFF)


def model_args(config: dict) -> dict:
    """``RingTransformer`` arguments from a configuration file's published
    keys; a cell's own model options come from its workload file."""
    heads = config["num_attention_heads"]
    return dict(
        num_tokens=config["vocab_size"],
        dim=config["hidden_size"],
        depth=config["num_hidden_layers"],
        heads=heads,
        dim_head=config["hidden_size"] // heads,
        kv_heads=config["num_key_value_heads"],
        ff_mult=config["intermediate_size"] // config["hidden_size"],
        causal=True,
        rotary=True,
        dtype=jnp.bfloat16,
    )


def model_shape(ctx) -> dict:
    """The sizes every cell has, for ``reduce.py``'s arithmetic."""
    c = ctx.config
    return {
        "hidden": c["hidden_size"],
        "ffn": c["intermediate_size"],
        "heads": c["num_attention_heads"],
        "kv_heads": c["num_key_value_heads"],
        "dim_head": c["hidden_size"] // c["num_attention_heads"],
        "vocab": c["vocab_size"],
        "depth": c["num_hidden_layers"],
        "chips": ctx.chips,
    }


def span(name: str):
    """A host span in the profiler's own trace (free when nothing traces);
    ``reduce.py`` reads the ones that start with ``bench/``."""
    return jax.profiler.TraceAnnotation(name)


def random_tokens(key, shape, vocab: int, sharding=None) -> jax.Array:
    """Uniform token ids made on the device."""
    return jax.jit(
        lambda k: jax.random.randint(k, shape, 0, vocab, jnp.int32),
        out_shardings=sharding)(key)
