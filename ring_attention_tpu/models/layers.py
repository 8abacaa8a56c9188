"""Transformer building blocks: RMSNorm and (blockwise) FeedForward.

TPU-native equivalents of the reference's ``RMSNorm``
(ref ``ring_attention.py:470-477``: ``F.normalize(x) * sqrt(dim) * gamma``)
and ``FeedForward`` (ref ``ring_attention.py:479-486``: prenorm -> Dense(mult*dim)
-> GELU -> Dense(dim)).  Norm statistics are computed in float32 regardless
of activation dtype, then cast back — the standard TPU mixed-precision
recipe.

Beyond the reference: ``FeedForward(chunk_size=...)`` is the *blockwise
feedforward* half of Ring Attention (arXiv 2310.01889 §3 — the paper pairs
blockwise attention with a blockwise FFN precisely so activation memory,
not compute, stops being the context-length ceiling).  The
prenorm -> Dense -> GELU -> Dense block runs as a rematted ``lax.scan``
over sequence chunks, so the ``(seq, mult*dim)`` intermediate only ever
exists at chunk extent — forward AND backward (the per-chunk remat makes
the grad pass recompute one chunk at a time).  Chunks are taken per
sequence *shard* (``seq_shards``), so under a sequence-parallel mesh every
scan step keeps all devices busy and the scan adds ZERO collectives
(pinned by the ``blockwise_ffn`` row of ``analysis/contracts.py``).
See ``docs/memory.md``.
"""

from __future__ import annotations

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


class RMSNorm(nn.Module):
    dim: int
    eps: float = 1e-12

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        gamma = self.param("gamma", nn.initializers.ones, (self.dim,))
        xf = x.astype(jnp.float32)
        rms = jnp.sqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + self.eps)
        return ((xf / rms) * gamma).astype(x.dtype)


class GatedFeedForward(nn.Module):
    """``down(silu(gate(h)) * up(h))`` with ``h = RMSNorm(x)``, no biases:
    the dense feed-forward of the gated-SiLU families and, with
    ``prenorm=False`` (the caller has normalised), a shared expert."""

    dim: int
    hidden: int
    dtype: jnp.dtype | None = None
    norm_eps: float = 1e-12
    prenorm: bool = True

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        if self.prenorm:
            x = RMSNorm(self.dim, self.norm_eps, name="norm")(x)

        def dense(width, name):
            return nn.Dense(width, use_bias=False, dtype=self.dtype, name=name)

        h = nn.silu(dense(self.hidden, "gate")(x)) * dense(self.hidden, "up")(x)
        return dense(self.dim, "down")(h)


class FeedForward(nn.Module):
    dim: int
    mult: int = 4
    dtype: jnp.dtype | None = None
    # blockwise feedforward: run the block as a rematted scan over sequence
    # chunks of this size so at most (b, chunk, mult*dim) of the
    # intermediate exists at once.  None = dense single-shot block; short
    # sequences (<= chunk per shard) and shapes that cannot split
    # shard-aligned (decode steps) fall back to the dense block, which is
    # value-identical
    chunk_size: int | None = None
    # sequence-shard count of the incoming layout: chunks are taken WITHIN
    # each shard so no scan slice crosses a device boundary
    seq_shards: int = 1
    mesh: Mesh | None = None

    def setup(self):
        # explicit names pin the param tree to the original @nn.compact
        # auto-naming, so checkpoints and shared-params parity predate the
        # chunked path
        self.norm = RMSNorm(self.dim, name="RMSNorm_0")
        self.proj_in = nn.Dense(
            self.dim * self.mult, use_bias=False, dtype=self.dtype,
            name="Dense_0",
        )
        self.proj_out = nn.Dense(
            self.dim, use_bias=False, dtype=self.dtype, name="Dense_1",
        )

    def _block(self, x: jax.Array) -> jax.Array:
        # "ffn_in" is the remat-policy name for the post-norm input (see
        # models/remat.py save_ffn_inputs); the mult*dim intermediate is
        # deliberately unnamed — no policy may keep it
        h = checkpoint_name(self.norm(x), "ffn_in")
        # exact (erf) gelu: the reference's nn.GELU() default
        # (ref ring_attention.py:484); the tanh approximation would be the
        # one avoidable numeric divergence in cross-framework parity
        h = nn.gelu(self.proj_in(h), approximate=False)
        return self.proj_out(h)

    def __call__(self, x: jax.Array) -> jax.Array:
        n = x.shape[1]
        shards = max(self.seq_shards, 1)
        c = self.chunk_size
        if c is not None and c > 0 and n % shards == 0:
            c = min(c, n // shards)
            if 0 < c < n // shards:
                return self._chunked(x, c, shards)
        return self._block(x)

    def _chunked(self, x: jax.Array, c: int, shards: int) -> jax.Array:
        """The blockwise path: (b, n, d) -> (nc, b, shards, c, d) chunks
        scanned through a rematted block.

        The shard axis is split out FIRST so chunk i of the scan is the
        concatenation of every device's chunk i — each step keeps the full
        sequence-parallel world busy on its own c positions, and the
        reshape/transpose stay local to each device (position-local math
        needs no cross-shard data).  A shard length that does not divide
        by ``c`` is padded up and the pad rows sliced back off (the FFN is
        position-local, so pad outputs are garbage nobody reads)."""
        b, n, d = x.shape
        n_local = n // shards
        pad = (-n_local) % c
        xs = x.reshape(b, shards, n_local, d)
        if pad:
            xs = jnp.pad(xs, ((0, 0), (0, 0), (0, pad), (0, 0)))
        nc = (n_local + pad) // c
        xs = xs.reshape(b, shards, nc, c, d).transpose(2, 0, 1, 3, 4)
        if self.mesh is not None and shards > 1:
            # keep the shard axis on the sequence mesh axes through the
            # scan: without the constraint the partitioner is free to
            # gather the whole sequence onto every device
            from ..parallel.mesh import data_partition, seq_partition

            xs = lax.with_sharding_constraint(
                xs, NamedSharding(
                    self.mesh,
                    P(None, data_partition(self.mesh),
                      seq_partition(self.mesh), None, None),
                )
            )

        def body(mdl, carry, x_c):
            return carry, mdl._block(x_c)

        scan = nn.scan(
            nn.remat(body, prevent_cse=False),
            variable_broadcast="params",
            split_rngs={"params": False},
            in_axes=0,
            out_axes=0,
        )
        _, ys = scan(self, None, xs)
        out = ys.transpose(1, 2, 0, 3, 4).reshape(b, shards, nc * c, d)
        if pad:
            out = out[:, :, :n_local]
        out = out.reshape(b, n, d)
        if self.mesh is not None and shards > 1:
            from ..parallel.mesh import data_partition, seq_partition

            out = lax.with_sharding_constraint(
                out, NamedSharding(
                    self.mesh, P(data_partition(self.mesh),
                                 seq_partition(self.mesh), None)
                )
            )
        return out
