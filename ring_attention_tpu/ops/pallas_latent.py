"""Decode over a latent cache: one row a position serves as key and value.

Latent attention (MLA) caches, for every position, the normalised
key/value latent ``c`` and the rotated positional key ``k_r`` shared by all
heads.  In the absorbed form of the layer a head's query is ``(q_n W_UK^T |
q_r)``, its score against a position ``q_lat . c + q_r . k_r``, and its
output the probability-weighted sum of the latents themselves (``W_UV`` is
applied outside).  So the latent is the key and the value, and a decode
step must read it once: ``pallas_flash_decode`` takes separate ``k`` and
``v`` of one width, and handing it the latent twice would stream the cache
twice.

The cache is two arrays a layer, both lane-aligned: the latents ``(b, 1,
positions, latent)`` and the positional keys transposed, ``(b, 1, rope,
positions)``.  One ``(b, 1, positions, latent + rope)`` array would be the
obvious form, and at 512 + 64 = 576 columns the TPU does not keep it
row-major: XLA's default layout of an array whose last dimension is no
multiple of 128 lanes makes the positions minor (no padding that way), a
Mosaic operand has to be row-major, and XLA then copies the whole cache in
front of the kernel on every step (a described-v5e compile shows the copy:
671 MB of temporaries for a 605 MB cache).  Split, no byte is padded or
copied, and ``k_r`` transposed is what its product wants anyway.

``flash_decode_latent`` (the name the profiler shows) grids over ``(session,
cache block)``: per session one block of all heads' query rows (the cache
has one "kv head", so the heads fold onto rows as ``_decode_fold_rows``
folds a GQA group), a sweep over the cache in blocks of ``block_k``
positions, the scores of a block from its latents and its positional keys,
the value product from the same latents in VMEM, through the online softmax
the other forward kernels share (``_online_update``).  It stands beside
``_flash_fwd_call`` and not on it: that launcher gives k and v a block spec
and a DMA each, which is the second read this kernel exists to avoid.  In
a file of its own so that no existing launch's call-site line moves (a
Mosaic kernel embeds it, and every program holding one would recompile).

On the v5e the kernel sits on the roofline's ridge: a position costs
(512 + 64) x 2 = 1,152 B in bfloat16 and 128 heads x (576 + 512) x 2 =
278,528 FLOP, 242 FLOP/B against the chip's 197 T / 819 G = 240.

``latent_decode_attention`` is the same arithmetic in plain XLA (float32
softmax over the whole cache): the ``use_pallas=False`` path and what the
CPU tests compare the kernel with.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..utils import compat
from .attention import EPSILON, MASK_VALUE
from .pallas_flash import (
    LANE,
    _block_sizes,
    _decode_fold_rows,
    _interpret_default,
    _log_launch,
    _online_update,
    _token_spec,
    _token_vectors,
)

# bytes of one block of latents in VMEM (the pipeline holds two): 4,096
# positions of a 512-wide bfloat16 latent
_BLOCK_BYTES = 4 * 2**20
_DEFAULT_VMEM = 16 * 2**20  # what every generation grants a kernel unasked


def _check(fn, q_lat, q_rope, latent, rope_t, kv_mask):
    b, h, dl = q_lat.shape if q_lat.ndim == 3 else (0, 0, 0)
    dr = q_rope.shape[-1]
    nk = latent.shape[2] if latent.ndim == 4 else 0
    if (q_lat.ndim != 3 or q_rope.shape != (b, h, dr)
            or latent.shape != (b, 1, nk, dl)
            or rope_t.shape != (b, 1, dr, nk)):
        raise ValueError(
            f"{fn}: expected queries (batch, heads, latent) and (batch, "
            f"heads, rope), latents (batch, 1, positions, latent) and "
            f"positional keys (batch, 1, rope, positions); got "
            f"{q_lat.shape}, {q_rope.shape}, {latent.shape}, {rope_t.shape}")
    if kv_mask is not None and kv_mask.shape != (b, nk):
        raise ValueError(
            f"{fn}: kv_mask {kv_mask.shape} is not (batch, positions) "
            f"{(b, nk)}")


def latent_decode_attention(
    q_lat: jax.Array,  # (b, h, latent): q_n W_UK^T, softmax scale folded in
    q_rope: jax.Array,  # (b, h, rope): rotated, scaled alike
    latent: jax.Array,  # (b, 1, positions, latent)
    rope_t: jax.Array,  # (b, 1, rope, positions)
    kv_mask: jax.Array | None = None,  # (b, positions) bool, True = attend
) -> jax.Array:
    """``softmax(q_lat . c + q_rope . k_r) @ c`` over the cache, float32
    ``(b, h, latent)``: the plain XLA form of ``flash_decode_latent``."""
    _check("latent_decode_attention", q_lat, q_rope, latent, rope_t, kv_mask)
    c = latent[:, 0].astype(jnp.float32)
    s = jnp.einsum("bhd,bjd->bhj", q_lat.astype(jnp.float32), c)
    s = s + jnp.einsum("bhr,brj->bhj", q_rope.astype(jnp.float32),
                       rope_t[:, 0].astype(jnp.float32))
    if kv_mask is not None:
        s = jnp.where(kv_mask[:, None, :], s, MASK_VALUE)
    return jnp.einsum("bhj,bjd->bhd", jax.nn.softmax(s, axis=-1), c)


def _latent_decode_kernel(ql_ref, qr_ref, c_ref, kr_ref, *rest, masked: bool,
                          nk_blocks: int):
    """Refs: queries ``(1, rows, latent)`` and ``(1, rows, rope)``, the
    block's latents ``(1, 1, bk, latent)`` and positional keys ``(1, 1,
    rope, bk)``, the mask's ``(1, 1, bk)`` row when ``masked``; out ``(1,
    rows, latent)``; scratch acc / m / l."""
    kvm_ref = rest[0] if masked else None
    out_ref, acc, m, l = rest[1 if masked else 0:]
    ki = pl.program_id(1)

    @pl.when(ki == 0)
    def _init():
        acc[:] = jnp.zeros_like(acc)
        m[:] = jnp.full_like(m, MASK_VALUE)
        l[:] = jnp.zeros_like(l)

    c = c_ref[0, 0]
    s = lax.dot_general(ql_ref[0], c, (((1,), (1,)), ((), ())),
                        preferred_element_type=jnp.float32)
    s = s + lax.dot_general(qr_ref[0], kr_ref[0, 0], (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    if masked:
        s = jnp.where(kvm_ref[0] != 0, s, MASK_VALUE)
    _online_update(s, c, acc, m, l)  # the keys' block is the values'

    @pl.when(ki == nk_blocks - 1)
    def _write():
        out_ref[0] = (acc[:] / jnp.maximum(l[:], EPSILON)).astype(
            out_ref.dtype)


def _padded(n: int) -> int:
    return n + (-n) % LANE


def pallas_flash_decode_latent(
    q_lat: jax.Array,  # (b, h, latent): q_n W_UK^T, softmax scale folded in
    q_rope: jax.Array,  # (b, h, rope): rotated, scaled alike
    latent: jax.Array,  # (b, 1, positions, latent)
    rope_t: jax.Array,  # (b, 1, rope, positions)
    kv_mask: jax.Array | None = None,  # (b, positions) bool, True = attend
    *,
    block_k: int | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """Decode attention over a latent cache, each byte read once: returns
    ``(b, h, latent)`` in ``q_lat.dtype``.  ``block_k`` defaults to the
    most positions whose block of latents stays within 4 MiB of VMEM (4,096
    at 512 bfloat16 columns), halved until it divides the cache; the launch
    asks for more scoped VMEM than a kernel gets unasked only where its
    shapes need it."""
    _check("pallas_flash_decode_latent", q_lat, q_rope, latent, rope_t,
           kv_mask)
    b, h, dl = q_lat.shape
    dr, nk = rope_t.shape[2:]
    interpret = _interpret_default() if interpret is None else interpret
    ql, rows, pad = _decode_fold_rows(q_lat[:, :, None, :], 1)
    qr, _, _ = _decode_fold_rows(q_rope[:, :, None, :], 1)
    bq = rows + pad
    itemsize = latent.dtype.itemsize
    if block_k is None:
        # a power of two, so that halving it finds a divisor of the cache
        fit = _BLOCK_BYTES // (_padded(dl) * itemsize)
        block_k = max(1 << fit.bit_length() - 1, LANE)
    _, bk = _block_sizes(bq, nk, bq, block_k)
    masked = kv_mask is not None

    def q_spec(width):
        return pl.BlockSpec((1, bq, width), lambda s, k: (s, 0, 0),
                            memory_space=pltpu.VMEM)

    in_specs = [
        q_spec(dl), q_spec(dr),
        pl.BlockSpec((1, 1, bk, dl), lambda s, k: (s, 0, k, 0),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((1, 1, dr, bk), lambda s, k: (s, 0, 0, k),
                     memory_space=pltpu.VMEM),
    ]
    inputs = [ql[:, 0], qr[:, 0], latent, rope_t]
    if masked:
        in_specs.append(_token_spec(bk, False, lambda s, k: (s, k)))
        inputs.append(_token_vectors(kv_mask.astype(jnp.int32), False))

    # two blocks of each cache array in flight; a block's scores, its
    # probabilities and their cast; the queries and the accumulators
    need = (2 * bk * (_padded(dl) + dr) * itemsize + 3 * bq * bk * 4
            + 4 * bq * (_padded(dl) + _padded(dr)) * 4)
    params = dict(dimension_semantics=("parallel", "arbitrary"))
    if need > _DEFAULT_VMEM * 3 // 4:
        params["vmem_limit_bytes"] = need + need // 4

    _log_launch("flash_decode_latent", bq, nk, h, 1, dl + dr, bq, bk,
                (b, nk // bk))
    out = pl.pallas_call(
        functools.partial(_latent_decode_kernel, masked=masked,
                          nk_blocks=nk // bk),
        grid=(b, nk // bk),
        in_specs=in_specs,
        out_specs=q_spec(dl),
        out_shape=jax.ShapeDtypeStruct((b, bq, dl), q_lat.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, dl), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
        ],
        compiler_params=compat.tpu_compiler_params(**params),
        interpret=interpret,
        name="flash_decode_latent",
    )(*inputs)
    return out[:, :rows]
