"""The rotation as a product by a signed permutation (ISSUE 38) computes
what rotate_half's split, negation and concatenation computed.

``split_form`` below is the parent's ``apply_rotary`` and the parent's
latent ``_rotated_columns``, kept here as the reference.  Against it, for
every rotary site's shape (whole 128-wide heads; the 64 rotary columns of a
192-wide latent head, with and without the yarn ``rotation_mscale``; one
decode position; contiguous, striped and zigzag positions):

- the forward is equal element for element, in bfloat16 and in float32;
- the gradient is equal too, in bfloat16 and in float32: the rotation's
  backward is its own (``custom_vjp``), the cotangent turned by ``-theta``
  with its two terms summed in float32 as the split form's autodiff sums
  them (autodiff of the product would round each term to bfloat16 first);
- no operation of the lowered rotation, forward or backward, slices or
  concatenates: the half rotation is the product's.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ring_attention_tpu.models.attention import LatentAttention
from ring_attention_tpu.ops.rotary import (
    YarnScaling,
    apply_rotary,
    ring_positions,
    rotary_freqs,
)
from ring_attention_tpu.parallel.zigzag import zigzag_positions

NOPE, ROPE = 128, 64
YARN = YarnScaling(factor=40.0, original_max_position=8, mscale=1.0,
                   mscale_all_dim=0.707)


def split_form(x, freqs):
    """The parent's ``apply_rotary``: rotate_half by halves."""
    xf = x.astype(jnp.float32)
    x1, x2 = jnp.split(xf, 2, axis=-1)
    half = jnp.concatenate([-x2, x1], axis=-1)
    return (xf * jnp.cos(freqs) + half * jnp.sin(freqs)).astype(x.dtype)


def split_latent(x, positions, scaling):
    """The parent's ``LatentAttention._rotated_columns``: split off the
    rotary columns, turn them, scale them, concatenate."""
    nope, rope = jnp.split(x, [NOPE], axis=-1)
    rope = split_form(rope, rotary_freqs(positions, ROPE, 10000.0, scaling))
    m = 1.0 if scaling is None else scaling.rotation_mscale
    if m != 1.0:
        rope = rope * jnp.asarray(m, rope.dtype)
    return jnp.concatenate([nope, rope], axis=-1)


def latent_rotation(scaling):
    """The latent layer's own rotation of a whole head, and its variables."""
    layer = LatentAttention(
        dim=32, heads=2, dim_head=NOPE + ROPE, q_latent_dim=16,
        kv_latent_dim=16, qk_nope_dim=NOPE, qk_rope_dim=ROPE, v_dim=NOPE,
        rope_scaling=scaling, mesh=None, use_ring=False)
    x = jnp.zeros((1, 2, 4, NOPE + ROPE))
    _, variables = layer.init_with_output(
        jax.random.PRNGKey(0), x, jnp.arange(4), method="_rotate_rope")
    return lambda x, pos: layer.apply(variables, x, pos,
                                      method="_rotate_rope")


def positions(kind, n):
    return {
        "contiguous": jnp.arange(n),
        "striped": ring_positions(n, 3, striped=True, world=4),
        "zigzag": zigzag_positions(n, 1, 4),
        "decode": jnp.full((1,), 126976),
    }[kind]


CASES = [
    # (site, positions): whole heads of 128 at each sharding's positions,
    # one decode position, and the latent layer's whole 192-wide head
    ("head", "contiguous"), ("head", "striped"), ("head", "zigzag"),
    ("head", "decode"), ("latent", "contiguous"), ("latent", "decode"),
    ("latent_yarn", "contiguous"), ("latent_yarn", "zigzag"),
]


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
@pytest.mark.parametrize("site, kind", CASES)
def test_the_product_form_is_the_split_form(site, kind, dtype):
    n = 1 if kind == "decode" else 32
    pos = positions(kind, n)
    d = 128 if site == "head" else NOPE + ROPE
    if site == "head":
        freqs = rotary_freqs(pos, d)
        new = lambda x: apply_rotary(x, freqs)  # noqa: E731
        old = lambda x: split_form(x, freqs)  # noqa: E731
    else:
        scaling = YARN if site == "latent_yarn" else None
        rotate = latent_rotation(scaling)
        new = lambda x: rotate(x, pos)  # noqa: E731
        old = lambda x: split_latent(x, pos, scaling)  # noqa: E731
    kx, kg = jax.random.split(jax.random.PRNGKey(38))
    x = (3 * jax.random.normal(kx, (2, 3, n, d))).astype(dtype)
    g = jax.random.normal(kg, x.shape).astype(dtype)

    # op by op: inside one jitted program XLA may keep a product's excess
    # precision (``xla_allow_excess_precision``), differently in two graphs
    np.testing.assert_array_equal(np.asarray(new(x)), np.asarray(old(x)))
    grad = lambda f: jax.grad(  # noqa: E731
        lambda x: jnp.sum(f(x).astype(jnp.float32) * g.astype(jnp.float32)))
    np.testing.assert_array_equal(np.asarray(grad(new)(x)),
                                  np.asarray(grad(old)(x)))

    for f in (new, grad(new)):
        text = jax.jit(f).lower(x).as_text()
        assert "slice" not in text and "concatenate" not in text
