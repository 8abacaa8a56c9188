"""A routed-expert feed-forward that is told which experts it holds.

``RoutedFeedForward`` is one holder's part of an expert-parallel layer:
the router scores all ``num_experts`` published experts with a sigmoid,
picks ``experts_per_token`` of them (a per-expert bias moves the choice
and never the weights; with ``expert_groups`` > 1 only inside the
``groups_per_token`` groups of consecutive experts whose two best biased
scores sum highest), normalises the chosen scores over all of the
chosen, and this holder computes the ``experts_held`` consecutive experts
from ``first_expert`` for the (token, expert) pairs that fell on them,
added to the shared expert's output.  With ``router_score="softmax"`` the
scores are the softmax of the router's logits and there is no bias:
normalised over the chosen, the weights are the softmax over the chosen
logits.  What the absent experts would have added is left out: on one chip
the layer runs without its exchange, and nothing stands in for the other
holders.

No pair is dropped and there is no capacity factor.  The pairs are sorted
by expert and go through ``lax.ragged_dot`` (on the TPU XLA lowers it to
a grouped matrix product that visits only the groups that have rows, so
an expert no token chose reads no weights; measured against the other
forms in CHANGES.md, PR 28) in passes of at most ``PASS_ROWS`` rows: a
prefill whose pairs fall an eighth on this holder takes one pass, and the
worst case, every pair here, takes more passes and not more memory.  A
holder that expects more pairs than a pass takes (half of 72 experts held
and ten a token: 163,840 of a 32,768-token prefill's) takes its tokens a
block at a time, each block one pass (``_blocks``).

A pass combines by token: each of a token's ``experts_per_token`` slots
that holds a pair of this pass gathers the pair's row of the second
product's output, and the token's sum is the selected ``weight x row``s
in float32.  The rows of that output past the pass's last pair are not
the product's to define and are never read: a held pair's place is below
them by construction, so the slots' own ``mine`` is the whole condition,
and nothing masks the product's output (such a mask read and wrote all
``PASS_ROWS`` rows a pass, though half of them or fewer hold pairs;
PERF.md section 6, PR 35).
"""

from __future__ import annotations

import functools

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax

from .layers import GatedFeedForward, RMSNorm

# rows of sorted (token, expert) pairs one pass of the expert products
# takes: the workspace is PASS_ROWS x (dim + 3 x expert_dim) values
PASS_ROWS = 32768

COUNTERS = "counters"  # the flax collection the layer sows its counts into


class RoutedFeedForward(nn.Module):
    dim: int
    expert_dim: int
    num_experts: int  # the router's width: every published expert
    experts_per_token: int
    experts_held: int
    first_expert: int = 0
    shared_dim: int = 0  # 0: no shared expert
    route_scale: float = 1.0
    norm_eps: float = 1e-12
    dtype: jnp.dtype | None = None
    expert_groups: int = 1  # 1: the choice is over all experts
    groups_per_token: int = 1
    router_score: str = "sigmoid"  # or "softmax": no selection bias

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        b, n, d = x.shape
        held, f = self.experts_held, self.expert_dim
        m = RMSNorm(d, self.norm_eps, name="norm")(x).reshape(b * n, d)
        init = nn.initializers.lecun_normal(in_axis=-2, out_axis=-1,
                                            batch_axis=(0,))
        router = self.param("router", nn.initializers.lecun_normal(),
                            (d, self.num_experts))
        softmax = self.router_score == "softmax"
        bias = 0.0 if softmax else self.param(
            "expert_bias", nn.initializers.zeros, (self.num_experts,))
        gate_up = self.param("experts_gate_up", init, (held, d, 2 * f))
        down = self.param("experts_down", init, (held, f, d))
        dtype = self.dtype or m.dtype

        with jax.named_scope("moe/router"):
            # float32 at full precision: it is 256 columns wide, and a
            # rounded score turns a choice that is near a tie
            logits = jnp.dot(
                m.astype(jnp.float32), router.astype(jnp.float32),
                precision=lax.Precision.HIGHEST)
            scores = (jax.nn.softmax(logits, axis=-1) if softmax
                      else jax.nn.sigmoid(logits))
            _, chosen = lax.top_k(
                self._eligible(scores if softmax else scores + bias),
                self.experts_per_token)
            top = jnp.take_along_axis(scores, chosen, axis=-1)
            weights = self.route_scale * top / (
                top.sum(-1, keepdims=True) + 1e-20)
        blocks = self._blocks(b * n)
        routed = self._routed if blocks == 1 else functools.partial(
            self._routed_in_blocks, blocks)
        out = routed(m.astype(dtype), chosen, weights,
                     gate_up.astype(dtype), down.astype(dtype))
        if self.shared_dim:
            with jax.named_scope("moe/shared"):
                out = out + GatedFeedForward(
                    d, self.shared_dim, dtype=self.dtype, prenorm=False,
                    name="shared")(m)
        return out.astype(x.dtype).reshape(b, n, d)

    def _eligible(self, biased):
        """The biased scores ``(tokens, experts)`` a token may choose from:
        all of them, or with groups only those of its best groups, a group
        ranked by the sum of its two largest (the others at -inf)."""
        groups = self.expert_groups
        if groups == 1:
            return biased
        with jax.named_scope("moe/groups"):
            tokens, experts = biased.shape
            by_group = biased.reshape(tokens, groups, experts // groups)
            rank = lax.top_k(by_group, min(2, experts // groups))[0].sum(-1)
            _, kept = lax.top_k(rank, self.groups_per_token)
            keep = (kept[:, :, None] == jnp.arange(groups)).any(1)
            return jnp.where(keep[:, :, None], by_group, -jnp.inf).reshape(
                tokens, experts)

    def _count(self, chosen, counts, n_here):
        """Sows a call's counters: free unless the caller asks,
        ``apply(..., mutable=["counters"])``."""
        pairs = chosen.size
        counters = [("tokens_per_expert", counts),
                    ("held_share", n_here / pairs),
                    ("experts_touched", jnp.sum(counts > 0)),
                    # the rows the combine needs of the products' output:
                    # one a held pair, of `pairs` slots
                    ("combine_rows_copied", n_here)]
        if self.expert_groups > 1:  # every holder's pairs, by group
            group = chosen.reshape(pairs) // (
                self.num_experts // self.expert_groups)
            counters.append(("pairs_per_group", jnp.sum(
                group[:, None] == jnp.arange(self.expert_groups), axis=0,
                dtype=jnp.int32)))
        for name, value in counters:
            if not self.is_initializing():
                self.sow(COUNTERS, name, value, init_fn=lambda: None,
                         reduce_fn=lambda _, new: new)

    def _blocks(self, tokens: int) -> int:
        """How many blocks the routed part takes a call's tokens in: one
        (all of them, in passes) unless this holder expects more pairs than
        a pass takes; then the fewest, a power of two that divides the
        tokens, whose expected pairs leave a fifth of a pass to spare."""
        expected = (tokens * self.experts_per_token * self.experts_held
                    / self.num_experts)
        blocks = 1
        while (expected > 0.8 * PASS_ROWS * blocks
               and tokens % (2 * blocks) == 0):
            blocks *= 2
        return blocks

    def _routed_in_blocks(self, blocks, m, chosen, weights, gate_up, down):
        """``_routed`` a block of tokens at a time.  A pass's combine
        gathers ``experts_per_token`` rows for every token of its call
        whichever pass holds them (XLA's shapes are static: an absent
        slot copies a row too, some 60 ns a row on a v5e): with half the
        experts held and ten a token the whole call in five or six passes
        spent two thirds of a 32,768-token prefill there, and a seed's
        routing decided which (PERF.md section 6, PR 34 and PR 35).  A
        block's pairs fit one pass, so every row is gathered once."""
        tokens, d = m.shape
        k, held = self.experts_per_token, self.experts_held
        local = chosen.reshape(-1) - self.first_expert
        counts = jnp.sum(
            jnp.where((local >= 0) & (local < held), local, held)[:, None]
            == jnp.arange(held), axis=0, dtype=jnp.int32)
        self._count(chosen, counts, counts.sum())
        out = lax.map(
            lambda block: self._routed(*block, gate_up, down, count=False),
            (m.reshape(blocks, -1, d), chosen.reshape(blocks, -1, k),
             weights.reshape(blocks, -1, k)))
        return out.reshape(tokens, d)

    def _routed(self, m, chosen, weights, gate_up, down, count=True):
        """Sum over the held experts a token chose of ``weight x expert(m)``,
        float32 ``(tokens, dim)``; ``count``: sow this call's counters."""
        tokens, d = m.shape
        k, held, f = self.experts_per_token, self.experts_held, self.expert_dim
        pairs = tokens * k
        rows = min(pairs, PASS_ROWS)
        passes = -(-pairs // rows)

        with jax.named_scope("moe/dispatch"):
            local = chosen - self.first_expert
            here = (local >= 0) & (local < held)
            # pairs sorted by held expert, the absent experts' pairs last
            key = jnp.where(here, local, held).reshape(pairs)
            order = jnp.argsort(key, stable=True).astype(jnp.int32)
            place = jnp.argsort(order).astype(jnp.int32).reshape(tokens, k)
            counts = jnp.sum(key[:, None] == jnp.arange(held), axis=0,
                             dtype=jnp.int32)
            ends = jnp.cumsum(counts)
            starts, n_here = ends - counts, ends[-1]
            order = jnp.pad(order, (0, passes * rows - pairs))
        if count:
            self._count(chosen, counts, n_here)

        def one_pass(lo):
            with jax.named_scope("moe/dispatch"):
                source = lax.dynamic_slice_in_dim(order, lo, rows) // k
                xs = jnp.take(m, source, axis=0)
                sizes = (jnp.clip(ends, lo, lo + rows)
                         - jnp.clip(starts, lo, lo + rows))
            with jax.named_scope("moe/experts"):
                h = lax.ragged_dot(xs, gate_up, sizes)
                y = lax.ragged_dot(nn.silu(h[:, :f]) * h[:, f:], down, sizes)
            with jax.named_scope("moe/combine"):
                # rows past the last pair are not the product's to define
                # and may hold NaN: a held pair's place is below n_here, so
                # `mine` is the whole condition, and it selects (a product
                # with a zero weight would let an absent slot's row through)
                at = place - lo
                mine = here & (at >= 0) & (at < rows)
                at = jnp.clip(at, 0, rows - 1)
                return sum(
                    jnp.where(mine[:, j, None], weights[:, j, None]
                              * jnp.take(y, at[:, j], axis=0), 0.0)
                    for j in range(k))

        if passes == 1:
            return one_pass(0)

        def body(carry):
            lo, acc = carry
            y = one_pass(lo)
            with jax.named_scope("moe/combine_sum"):
                return lo + rows, acc + y

        _, out = lax.while_loop(
            lambda carry: carry[0] < n_here, body,
            (jnp.int32(0), jnp.zeros((tokens, d), jnp.float32)))
        return out
