"""Traffic driver "train": one optimizer step after another on fresh
batches, on one chip or as a sequence-parallel ring over several.

It calls what ``examples/train.py`` calls: ``RingTransformer``,
``create_mesh``, ``make_train_step`` (donated parameters and AdamW state,
compiled ahead of time).  There is no input pipeline in the program, so
the batches are uniform random token ids made on the device before the
window.  Each row carries one token more than the cell's length, so that
after the label shift the model sees exactly that length.

Workload file: ``batch`` rows of ``tokens_per_row`` tokens, ``batches``
distinct batches (reused in order if the window outlasts them),
``learning_rate``, ``model`` (``RingTransformer`` options of this cell),
``check_tokens`` (length of the correctness sequence) and ``trace.steps``
(how many steps a traced run holds).
"""

from __future__ import annotations

import math
import time

import jax
import jax.numpy as jnp
import optax
from jax.sharding import NamedSharding, PartitionSpec

from ring_attention_tpu.models import RingTransformer
from ring_attention_tpu.parallel import create_mesh
from ring_attention_tpu.utils.train import make_train_step

from .. import reference
from . import model_args, model_shape, random_tokens, seed_key, span


def shape(ctx) -> dict:
    return {**model_shape(ctx), "batch": ctx.workload["batch"],
            "seq": ctx.workload["tokens_per_row"]}


def setup(ctx):
    cfg, wl = ctx.config, ctx.workload
    mesh = (create_mesh(ring_size=ctx.chips, devices=jax.devices()[:ctx.chips])
            if ctx.chips > 1 else None)
    # replicated over the ring, as examples/train.py places them; a row of
    # length + 1 does not divide over the ring, the model shards what is
    # left after the label shift itself
    everywhere = NamedSharding(mesh, PartitionSpec()) if mesh else None
    model = RingTransformer(**model_args(cfg), mesh=mesh,
                            use_ring=mesh is not None, **wl["model"])
    optimizer = optax.adamw(wl["learning_rate"])
    k_init, k_data, k_check = jax.random.split(seed_key(ctx.seed), 3)

    def make_state(key):
        # parameter shapes do not depend on the length: one tile a shard
        tokens = jnp.zeros((1, 128 * ctx.chips), jnp.int32)
        params = model.init(key, tokens)
        return params, optimizer.init(params)

    params, opt_state = jax.jit(make_state, out_shardings=everywhere)(k_init)
    rows, length = wl["batch"], wl["tokens_per_row"]
    one_batch = jax.jit(
        lambda k, i: jax.random.randint(
            jax.random.fold_in(k, i), (rows, length + 1), 0,
            cfg["vocab_size"], jnp.int32),
        out_shardings=everywhere)
    batches = [one_batch(k_data, i) for i in range(wl["batches"])]
    jax.block_until_ready((params, opt_state, batches))
    ctx.part("weights_and_state")

    step = make_train_step(
        lambda p, t: model.apply(p, t, return_loss=True), optimizer,
        jit_donate=True,
    ).lower(params, opt_state, batches[0]).compile()
    ctx.part("compile_or_load")

    check = _check(ctx, model, params, k_check, everywhere)
    ctx.part("check")

    params, opt_state, loss = step(params, opt_state, batches[0])
    check["warmup_loss"] = float(jax.block_until_ready(loss))
    ctx.part("warmup")
    return {"step": step, "params": params, "opt_state": opt_state,
            "batches": batches, "check": check}


def _check(ctx, model, params, key, everywhere) -> dict:
    """One seeded sequence through the same model object and mesh as the
    step, against the plain reference: the logits of every position, and
    the loss (which alone passes through the chunked cross-entropy)."""
    cfg = ctx.config
    tokens = random_tokens(key, (1, ctx.workload["check_tokens"] + 1),
                           cfg["vocab_size"], everywhere)

    @jax.jit
    def compare(p, t):
        got_loss = model.apply(p, t, return_loss=True)
        got = model.apply(p, t[:, :-1])[0]
        want = reference.logits(p, t[0, :-1], cfg)
        want_loss = reference.loss(want, t[0, 1:])
        return (reference.rel_l2(got, want), got_loss, want_loss,
                jnp.abs(got_loss - want_loss) / want_loss)

    rel, got_loss, want_loss, loss_rel = (
        float(x) for x in compare(params, tokens))
    return {"ok": reference.verdict(rel, loss_rel), "logits_rel_l2": rel,
            "loss": got_loss, "reference_loss": want_loss,
            "loss_rel": loss_rel}


def window(ctx, state):
    wl = ctx.workload
    step, batches = state["step"], state["batches"]
    params, opt_state = state["params"], state["opt_state"]
    traced_steps = wl["trace"]["steps"] if ctx.trace else None
    losses = []
    start = now = time.perf_counter()
    while (len(losses) < traced_steps if traced_steps
           else now - start < ctx.seconds):
        with span("bench/step"):
            params, opt_state, loss = step(
                params, opt_state, batches[len(losses) % len(batches)])
            jax.block_until_ready(loss)
        with span("bench/fetch"):
            losses.append(float(loss))
        now = time.perf_counter()
    rate = wl["batch"] * wl["tokens_per_row"] * len(losses) / (now - start)
    finite = [math.isfinite(x) for x in losses]
    return {
        "attempted": len(losses),
        "failed": finite.count(False),
        "finite": all(finite),
        "end_to_end": {"train_tokens_per_s": rate},
        "units": {"step": len(losses)},
        "series": {},
        "log": {"losses": [f"{x:.6g}" for x in losses],
                "window_s": now - start},
    }
