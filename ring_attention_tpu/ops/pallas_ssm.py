"""One decode step of the Mamba-2 recurrence: every state read and written
once, in place.

A Mamba-2 layer keeps, a session and a head, a float32 state ``S`` of
``head_dim x state`` values (64 x 128 at granite-4.0-h-small's widths: 4 MiB
a session a layer over 128 heads).  A decoded position does, per head ``h``
(arXiv 2405.21060, the recurrent form)::

    S[h]  <-  exp(dt[h] A[h]) S[h]  +  (dt[h] x[h]) (x) B
    y[h]   =  S[h] C  +  Dskip[h] x[h]

``B`` and ``C`` (``state`` wide) are shared by the heads of a group, and
this file is written for one group.  The work is the stream: 2 x 4 MiB a
session a layer against a few thousand multiplications, so the step is
bound by bytes as ``flash_decode`` is, but over a state that is read *and
written* and not over a cache that grows.

``ssm_decode_step`` (the name the profiler shows) grids over ``(session,
block of heads)``; a step of the grid holds ``block_heads`` states in VMEM
(1 MiB at 32), scales each by its head's decay (a scalar, from SMEM), adds
the rank-one update, contracts the new state with ``C`` on the lanes, and
writes the state back over the operand it came from
(``input_output_aliases``).  A state tile has ``head_dim`` on the sublanes
and ``state`` on the lanes, so ``B`` and ``C`` enter as rows and the heads'
``dt x`` as columns: the caller hands those transposed, ``(sessions, blocks,
head_dim, block_heads)``, which costs XLA a few KiB, where ``(.., head_dim,
1)`` operands would be padded to 128 lanes and stream as many bytes as the
states themselves.  ``y`` leaves the same way.  The decays ``exp(dt A)``,
``dt x`` and the skip ``Dskip x`` are the caller's (XLA's): a few thousand
values a session.

``ssm_step`` is the same arithmetic in plain XLA: the ``use_pallas=False``
path, and what the CPU tests compare the kernel with.

In a file of its own so that no existing launch's call-site line moves (a
Mosaic kernel embeds it, and every program holding one would recompile).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..utils import compat
from .pallas_flash import _interpret_default

BLOCK_HEADS = 32  # states a grid step holds: 1 MiB at 64 x 128 float32


def _check(fn, state, x, b, c, dt, a, d):
    s, h, p, n = state.shape if state.ndim == 4 else (0, 0, 0, 0)
    if (state.ndim != 4 or x.shape != (s, h, p) or b.shape != (s, n)
            or c.shape != (s, n) or dt.shape != (s, h) or a.shape != (h,)
            or d.shape != (h,)):
        raise ValueError(
            f"{fn}: expected a state (sessions, heads, head_dim, state), x "
            f"(sessions, heads, head_dim), B and C (sessions, state), dt "
            f"(sessions, heads), A and D (heads,); got {state.shape}, "
            f"{x.shape}, {b.shape}, {c.shape}, {dt.shape}, {a.shape}, "
            f"{d.shape}")
    if state.dtype != jnp.float32:
        raise ValueError(
            f"{fn}: the state is float32 (thousands of multiplications by a "
            f"decay do not survive a narrower one); got {state.dtype}")


def _terms(x, dt, a, d):
    """float32: the heads' decays ``(s, h)``, ``dt x`` and ``Dskip x``
    ``(s, h, p)``."""
    dt, xf = dt.astype(jnp.float32), x.astype(jnp.float32)
    return (jnp.exp(dt * a.astype(jnp.float32)), dt[:, :, None] * xf,
            d.astype(jnp.float32)[None, :, None] * xf)


def ssm_step(
    state: jax.Array,  # (s, h, p, n) float32
    x: jax.Array,  # (s, h, p): this position's channels, after the conv
    b: jax.Array,  # (s, n)
    c: jax.Array,  # (s, n)
    dt: jax.Array,  # (s, h): the step, after softplus
    a: jax.Array,  # (h,): negative
    d: jax.Array,  # (h,): the skip
) -> tuple[jax.Array, jax.Array]:
    """``(y (s, h, p) float32, new state)``: the plain XLA form of
    ``ssm_decode_step``."""
    _check("ssm_step", state, x, b, c, dt, a, d)
    decay, dtx, skip = _terms(x, dt, a, d)
    state = (decay[:, :, None, None] * state
             + dtx[..., None] * b.astype(jnp.float32)[:, None, None, :])
    y = jnp.sum(state * c.astype(jnp.float32)[:, None, None, :], axis=-1)
    return y + skip, state


def _ssm_step_kernel(decay_ref, dtx_ref, b_ref, c_ref, state_ref, y_ref,
                     out_ref, *, block_heads: int):
    """Refs: the decays ``(s, h)`` whole in SMEM; ``dt x`` transposed ``(1,
    1, p, block_heads)``; ``B`` and ``C`` rows ``(1, 1, n)``; the states
    ``(1, block_heads, p, n)``, in and (aliased) out; ``y`` transposed as
    ``dt x`` is."""
    s, g = pl.program_id(0), pl.program_id(1)
    dtx = dtx_ref[0, 0]  # (p, block_heads)
    b_row, c_row = b_ref[0], c_ref[0]  # (1, n)
    lane = lax.broadcasted_iota(jnp.int32, dtx.shape, 1)
    ys = jnp.zeros_like(dtx)
    for j in range(block_heads):
        column = dtx[:, j:j + 1]  # (p, 1): this head's dt x, down the rows
        new = (decay_ref[s, g * block_heads + j] * state_ref[0, j]
               + column * b_row)
        out_ref[0, j] = new
        y = jnp.sum(new * c_row, axis=-1, keepdims=True)  # (p, 1)
        ys = jnp.where(lane == j, y, ys)
    y_ref[0, 0] = ys


def pallas_ssm_decode_step(
    state: jax.Array,  # (s, h, p, n) float32
    x: jax.Array,  # (s, h, p)
    b: jax.Array,  # (s, n)
    c: jax.Array,  # (s, n)
    dt: jax.Array,  # (s, h): the step, after softplus
    a: jax.Array,  # (h,)
    d: jax.Array,  # (h,)
    *,
    block_heads: int | None = None,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    """One position of the recurrence, each state read and written once:
    ``(y (s, h, p) float32, new state)``, the new state in the operand's
    buffer where the caller donates it."""
    _check("pallas_ssm_decode_step", state, x, b, c, dt, a, d)
    s, h, p, n = state.shape
    interpret = _interpret_default() if interpret is None else interpret
    hb = min(block_heads or BLOCK_HEADS, h)
    while h % hb:
        hb //= 2
    blocks = h // hb
    decay, dtx, skip = _terms(x, dt, a, d)

    def transposed(v):  # (s, h, p) -> (s, blocks, p, hb)
        return v.reshape(s, blocks, hb, p).swapaxes(2, 3)

    t_spec = pl.BlockSpec((1, 1, p, hb), lambda i, g: (i, g, 0, 0),
                          memory_space=pltpu.VMEM)
    row_spec = pl.BlockSpec((1, 1, n), lambda i, g: (i, 0, 0),
                            memory_space=pltpu.VMEM)
    state_spec = pl.BlockSpec((1, hb, p, n), lambda i, g: (i, g, 0, 0),
                              memory_space=pltpu.VMEM)
    y_t, state = pl.pallas_call(
        functools.partial(_ssm_step_kernel, block_heads=hb),
        grid=(s, blocks),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM), t_spec, row_spec,
                  row_spec, state_spec],
        out_specs=[t_spec, state_spec],
        out_shape=[jax.ShapeDtypeStruct((s, blocks, p, hb), jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, jnp.float32)],
        input_output_aliases={4: 1},
        compiler_params=compat.tpu_compiler_params(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
        name="ssm_decode_step",
    )(decay, transposed(dtx), b.astype(jnp.float32)[:, None, :],
      c.astype(jnp.float32)[:, None, :], state)
    return y_t.swapaxes(2, 3).reshape(s, h, p) + skip, state
