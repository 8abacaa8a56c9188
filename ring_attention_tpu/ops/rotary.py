"""Shard-aware rotary position embeddings.

TPU-native analogue of ``RingRotaryEmbedding`` / ``apply_rotary_pos_emb``
(ref ``ring_attention.py:102-172``).  The reference's key subtlety is that
positions must reflect how the sequence was sharded:

  - plain ring sharding: rank ``r`` holds the contiguous slice
    ``[r * n_local, (r + 1) * n_local)`` (ref ``ring_attention.py:153-155``)
  - striped sharding: rank ``r`` holds every ``world``-th token starting at
    ``r``, i.e. global position of local index ``i`` is ``i * world + r``
    (ref ``ring_attention.py:142-151``; we stripe at token granularity, the
    reference's ``buckets=1`` fused-kernel case)

Here those are pure position computations: the model computes per-shard
positions (optionally inside ``shard_map`` using ``lax.axis_index``) and
feeds them to ``rotary_freqs`` -> ``apply_rotary``.  Rotary math is always
float32 (the reference forces fp32 via autocast-off, ref
``ring_attention.py:128,167``).

The half rotation ``(a, b) -> (-b, a)`` is a product by a fixed signed
permutation, not a split, a negation and a concatenation: XLA:TPU made
those separate passes over half-width buffers padded to 128 lanes (8 to 20
times the rotation's bytes floor, PERF.md s5, PR 37), where a product's
elementwise epilogue is one pass.  Every column of the permutation holds
one +-1, so the product is exact: a bfloat16 row goes into the MXU as it
is, and a wider one at the highest precision.  The backward is the same
product turning the cotangent by ``-theta`` (``turn``'s ``custom_vjp``), so
the gradient is the split form's bit for bit in bfloat16 too.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax


def ring_positions(n_local: int, rank: jax.Array | int, *, striped: bool, world: int) -> jax.Array:
    """Global token positions for one sequence shard.

    ``rank`` may be a traced scalar (e.g. ``lax.axis_index``) so the same
    compiled program serves every mesh position.
    """
    i = jnp.arange(n_local)
    if striped:
        return i * world + rank
    return i + rank * n_local


def hybrid_positions(
    n_local: int,
    ulysses_rank: jax.Array | int,
    ring_rank: jax.Array | int,
    *,
    ulysses: int,
    ring: int,
    striped: bool,
) -> jax.Array:
    """Global token positions for one shard of a factored ``seq = ulysses
    x ring`` layout (``parallel/hybrid.py``).

    The sequence dimension shards ring-major / ulysses-minor: ring rank
    ``r`` owns chunk ``r`` of ``ring`` chunks and ulysses rank ``u`` owns
    subchunk ``u`` within it, so local index ``i`` sits at in-chunk index
    ``u * n_local + i`` — equivalently, combined rank ``r * ulysses + u``
    of a ``ring * ulysses``-way contiguous sharding.  Striping (for the
    causal ring's load balance) interleaves at the OUTER ring degree only:
    in-chunk index ``j`` of ring rank ``r`` is global token ``j * ring +
    r``, exactly the layout ``stripe_permute(x, ring)`` + factored
    sharding produces.
    """
    j = ulysses_rank * n_local + jnp.arange(n_local)
    if striped:
        return j * ring + ring_rank
    return ring_rank * (ulysses * n_local) + j


@dataclass(frozen=True)
class YarnScaling:
    """A published config's ``rope_scaling`` of type ``yarn`` (after the
    public DeepSeek-V3 implementation): frequencies that turn more than
    ``beta_fast`` times over the original context keep their value, those
    that turn fewer than ``beta_slow`` times are divided by ``factor``, and
    a linear ramp blends the ones between."""

    factor: float
    original_max_position: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0

    @classmethod
    def from_dict(cls, d: dict | None) -> "YarnScaling | None":
        """``None`` for a null ``rope_scaling``; any type but yarn, or a
        yarn without its two required keys, is a one-line error."""
        if d is None:
            return None
        kind = d.get("type", d.get("rope_type"))
        missing = {"factor", "original_max_position_embeddings"} - set(d)
        if kind != "yarn" or missing:
            raise ValueError(
                f"rope_scaling: the rotary embedding scales by type 'yarn' "
                f"with factor and original_max_position_embeddings; got "
                f"type {kind!r}, missing {sorted(missing)}")
        return cls(
            factor=float(d["factor"]),
            original_max_position=int(d["original_max_position_embeddings"]),
            beta_fast=float(d.get("beta_fast", 32)),
            beta_slow=float(d.get("beta_slow", 1)),
            mscale=float(d.get("mscale", 1)),
            mscale_all_dim=float(d.get("mscale_all_dim", 0)))

    @staticmethod
    def _mscale(factor: float, mscale: float) -> float:
        return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0

    @property
    def softmax_mscale(self) -> float:
        """``m``: the softmax scale of a yarn model is ``d ** -0.5 * m * m``."""
        return self._mscale(self.factor, self.mscale_all_dim)

    @property
    def rotation_mscale(self) -> float:
        """What cos and sin are multiplied by (1 where the two agree)."""
        return (self._mscale(self.factor, self.mscale)
                / self._mscale(self.factor, self.mscale_all_dim))

    def inv_freq(self, dim: int, theta: float) -> jax.Array:
        extra = 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))

        def turns_at(beta):  # the index whose frequency turns beta times
            return (dim * math.log(self.original_max_position
                                   / (beta * 2 * math.pi))
                    / (2 * math.log(theta)))

        low = min(max(math.floor(turns_at(self.beta_fast)), 0), dim // 2 - 1)
        high = min(max(math.ceil(turns_at(self.beta_slow)), 0), dim // 2 - 1)
        span = high - low if high > low else 0.001
        ramp = jnp.clip(
            (jnp.arange(dim // 2, dtype=jnp.float32) - low) / span, 0.0, 1.0)
        return extra / self.factor * ramp + extra * (1.0 - ramp)


def rotary_freqs(positions: jax.Array, dim: int, theta: float = 10000.0,
                 scaling: YarnScaling | None = None) -> jax.Array:
    """Angles ``(n, dim)`` for NeoX-style (half-rotation) rotary embedding,
    the two halves of a row alike; ``scaling`` blends the frequencies as
    yarn does."""
    if scaling is None:
        inv_freq = 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))
    else:
        inv_freq = scaling.inv_freq(dim, theta)
    return positions.astype(jnp.float32)[:, None] * jnp.tile(inv_freq, 2)


def _half_turn(width: int, d: int, dtype) -> jax.Array:
    """The ``d x d`` signed permutation that takes a row whose last
    ``width`` columns are the halves ``(a, b)`` to ``(-b, a)`` there, and
    every column before them to zero."""
    r = np.zeros((d, d), np.float32)  # ra: allow(RA009 a constant of the static width, never traced)
    h = width // 2
    lo = np.arange(d - width, d - h)  # ra: allow(RA009 a constant of the static width, never traced)
    r[lo + h, lo] = -1.0
    r[lo, lo + h] = 1.0
    return jnp.asarray(r, dtype)


@partial(jax.custom_vjp, nondiff_argnums=(3,))
def turn(x: jax.Array, cos: jax.Array, sin: jax.Array, width: int) -> jax.Array:
    """``x * cos + x @ R * sin`` in float32, cast back to ``x``'s type, where
    ``R`` turns the last ``width`` columns of a row and takes any before them
    to zero: tables of 1 and 0 there pass those columns as they are (a
    latent head's non-positional part)."""
    turned = jnp.dot(
        x, _half_turn(width, x.shape[-1], x.dtype),
        precision=lax.Precision.HIGHEST if x.dtype.itemsize > 2 else None,
        preferred_element_type=jnp.float32)
    return (x.astype(jnp.float32) * cos + turned * sin).astype(x.dtype)


def _turn_fwd(x, cos, sin, width):
    return turn(x, cos, sin, width), (cos, sin)


def _turn_bwd(width, tables, g):
    """The cotangent turned by ``-theta``: ``R``'s transpose is ``-R`` and
    a row's two angle halves are alike, so ``g * cos - g @ R * sin`` is the
    split form's gradient term for term, summed in float32 as it was (under
    autodiff the two terms would each be rounded to ``g``'s type first)."""
    cos, sin = tables
    return turn(g, cos, -sin, width), None, None


turn.defvjp(_turn_fwd, _turn_bwd)


def apply_rotary(x: jax.Array, freqs: jax.Array) -> jax.Array:
    """Apply rotary embedding.  ``x: (..., n, d)``, ``freqs: (n, d)``."""
    return turn(x, jnp.cos(freqs), jnp.sin(freqs), x.shape[-1])
