"""A Mamba-2 mixer that stands where an attention module stands.

``Mamba2Mixer`` is the state-space layer of the hybrid models (after the
Mamba-2 paper, arXiv 2405.21060, and transformers' Bamba / GraniteMoeHybrid
mixer), under the three signatures the stack walker uses for an attention
module, so ``RingTransformer._blocks`` stays one walk::

    z | u | d = RMSNorm_in(h) W_in               inner | inner + 2 state | heads
    u_t = silu(sum_j w_conv[j] u_{t-taps+1+j} + b_conv)     depthwise, causal
    x | B | C = u                                 heads x head_dim | state | state
    dt_t = softplus(d_t + dt_bias) ;  A = -exp(A_log)               float32
    S_t[h] = exp(dt_t[h] A[h]) S_{t-1}[h] + dt_t[h] x_t[h] (x) B_t
    y_t[h] = S_t[h] C_t + Dskip[h] x_t[h]
    out = RMSNorm_inner(y * silu(z)) W_out        the gate first, then the norm

One layer, two arithmetic forms, as latent attention has.  ``__call__`` and
``prefill`` compute the *chunked* form (``chunk_scan``): inside a chunk of
``chunk`` positions the recurrence is three products on the MXU, and one
float32 state is handed from chunk to chunk.  ``decode_step`` computes the
*recurrent* form for one position from the cached state
(``ops/pallas_ssm.py``: the ``ssm_decode_step`` kernel, or its XLA form).

The cache entry, one pair a layer, does not grow with the capacity: ``k`` is
the convolution's tail, the last ``conv - 1`` rows of ``u`` before the
convolution, ``(sessions, 1, conv - 1, inner + 2 state)`` in the model's
dtype; ``v`` the state ``(sessions, heads, head_dim, state)`` in float32.
``prefill`` starts from a zero state (a new session), whatever the entry
held.

The decays and the state are float32; the products inside a chunk take the
model dtype's operands (bfloat16 when it is) and float32 accumulators.  A
sequence that is no multiple of ``chunk`` is padded with positions whose
``dt`` is zero: they leave the state alone.  Written for one group (B and C
shared by all heads).  On a sequence-sharded mesh a state would have to be
handed from rank to rank: not yet (ROADMAP R7), and said so, in all three
calls.
"""

from __future__ import annotations

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh

from ..ops.pallas_ssm import pallas_ssm_decode_step, ssm_step
from ..parallel.mesh import seq_world
from .layers import RMSNorm
from .moe import COUNTERS


def _taps_init(key, shape, dtype=jnp.float32):
    return jax.random.uniform(key, shape, dtype, -0.5, 0.5)


def _a_log_init(key, shape, dtype=jnp.float32):
    """The Mamba-2 reference initialisation: ``A = -U[1, 16]``."""
    return jnp.log(jax.random.uniform(key, shape, dtype, 1.0, 16.0))


def _dt_bias_init(key, shape, dtype=jnp.float32):
    """The inverse softplus of a step drawn log-uniform in [0.001, 0.1]."""
    dt = jnp.exp(jax.random.uniform(
        key, shape, dtype, jnp.log(0.001), jnp.log(0.1)))
    return dt + jnp.log(-jnp.expm1(-dt))


def causal_conv(u: jax.Array, tail: jax.Array, kernel: jax.Array,
                bias: jax.Array) -> tuple[jax.Array, jax.Array]:
    """``silu`` of the causal depthwise convolution of ``u: (b, n, channels)``
    behind ``tail: (b, taps - 1, channels)``, the rows before position 0
    (zeros for a new sequence); ``kernel: (taps, channels)``.  Returns the
    activated rows in ``u``'s dtype and the new tail."""
    taps, n = kernel.shape[0], u.shape[1]
    rows = jnp.concatenate([tail.astype(u.dtype), u], axis=1)
    acc = bias.astype(jnp.float32) + sum(
        kernel[j].astype(jnp.float32) * rows[:, j:j + n].astype(jnp.float32)
        for j in range(taps))
    return nn.silu(acc).astype(u.dtype), rows[:, n:]


def chunk_scan(
    x: jax.Array,  # (b, n, h * p): the heads' channels side by side
    b: jax.Array,  # (b, n, state)
    c: jax.Array,  # (b, n, state)
    dt: jax.Array,  # (b, n, h) float32, after softplus
    a: jax.Array,  # (h,) float32, negative
    d: jax.Array,  # (h,) the skip
    state: jax.Array,  # (b, h, p, state) float32: the state before position 0
    chunk: int,
) -> tuple[jax.Array, jax.Array]:
    """The chunked form of the recurrence: ``(y (b, n, h * p) in x's dtype,
    the state after position n - 1)``.  With ``c_i`` the running sum of ``dt
    A`` inside a chunk of Q positions and ``S`` the state before it::

        Y_i   = sum_{j<=i} exp(c_i - c_j) (C_i . B_j) dt_j x_j
                + exp(c_i) S C_i + Dskip x_i
        S_end = exp(c_{Q-1}) S + sum_j exp(c_{Q-1} - c_j) dt_j x_j (x) B_j

    A ``lax.scan`` over the chunks with all sequences in a step: the decay
    matrices of one chunk, heads x Q x Q float32, are what a step holds
    (all chunks at once would be gigabytes a layer).  ``x`` and ``y`` cross
    the scan's edge flat and take their ``(h, p)`` shape inside a step: a
    whole sequence's array with ``p`` = 64 as its last dimension is padded
    to 128 lanes and copied to get there (2 + 1 GiB at 4 x 8,192 tokens)."""
    bsz, n, _ = x.shape
    h, p = state.shape[1:3]
    dtype = x.dtype
    pad = -n % chunk
    if pad:  # dt = 0: such a position neither decays the state nor adds
        x, b, c, dt = (jnp.pad(v, [(0, 0), (0, pad)] + [(0, 0)] * (v.ndim - 2))
                       for v in (x, b, c, dt))
    chunks = (n + pad) // chunk

    def by_chunk(v):  # (b, n, ...) -> (chunks, b, Q, ...)
        return jnp.moveaxis(v.reshape(bsz, chunks, chunk, *v.shape[2:]), 1, 0)

    causal = jnp.tril(jnp.ones((chunk, chunk), bool))
    a, skip = a.astype(jnp.float32), d.astype(jnp.float32)

    def one_chunk(state, inputs):
        x, b, c, dt = inputs
        x = x.reshape(bsz, chunk, h, p)
        dt = dt.swapaxes(1, 2)  # (b, h, Q)
        cs = jnp.cumsum(dt * a[:, None], axis=-1)  # c_i, float32
        decay = jnp.exp(jnp.where(
            causal, cs[..., :, None] - cs[..., None, :], -jnp.inf))
        cb = jnp.einsum("bin,bjn->bij", c, b,
                        preferred_element_type=jnp.float32)
        within = (decay * cb[:, None] * dt[:, :, None, :]).astype(dtype)
        y = jnp.einsum("bhij,bjhp->bihp", within, x,
                       preferred_element_type=jnp.float32)
        carried = jnp.einsum("bin,bhpn->bihp", c, state.astype(dtype),
                             preferred_element_type=jnp.float32)
        y = y + jnp.exp(cs).swapaxes(1, 2)[..., None] * carried
        y = y + skip[:, None] * x.astype(jnp.float32)
        last = cs[..., -1:]  # (b, h, 1)
        weight = (jnp.exp(last - cs) * dt).swapaxes(1, 2)  # (b, Q, h)
        added = jnp.einsum(
            "bjhp,bjn->bhpn",
            (x.astype(jnp.float32) * weight[..., None]).astype(dtype), b,
            preferred_element_type=jnp.float32)
        state = jnp.exp(last)[..., None] * state + added
        return state, y.astype(dtype).reshape(bsz, chunk, h * p)

    state, ys = lax.scan(one_chunk, state.astype(jnp.float32),
                         tuple(map(by_chunk, (x, b, c, dt))))
    return jnp.moveaxis(ys, 0, 1).reshape(bsz, n + pad, h * p)[:, :n], state


class Mamba2Mixer(nn.Module):
    dim: int
    heads: int
    head_dim: int
    state: int
    conv: int = 4  # taps of the causal depthwise convolution
    chunk: int = 256  # positions a chunk of the chunked form
    norm_eps: float = 1e-12
    use_ring: bool = True
    force_regular_attn: bool = False
    mesh: Mesh | None = None
    use_pallas: bool = False
    impl: str | None = None  # as RingAttention.impl: overrides use_pallas
    dtype: jnp.dtype | None = None

    def setup(self):
        inner, channels = self._inner(), self._inner() + 2 * self.state
        matrix = nn.initializers.lecun_normal()
        self.prenorm = RMSNorm(self.dim, self.norm_eps)
        # z | u (x, B, C) | dt side by side: bare matrices, as
        # LatentAttention.to_kv is
        self.in_proj = self.param(
            "in_proj", matrix, (self.dim, inner + channels + self.heads))
        self.conv_kernel = self.param(
            "conv_kernel", _taps_init, (self.conv, channels))
        self.conv_bias = self.param("conv_bias", _taps_init, (channels,))
        self.dt_bias = self.param("dt_bias", _dt_bias_init, (self.heads,))
        self.A_log = self.param("A_log", _a_log_init, (self.heads,))
        self.D = self.param("D", nn.initializers.ones, (self.heads,))
        self.gate_norm = RMSNorm(inner, self.norm_eps)
        self.out_proj = self.param("out_proj", matrix, (inner, self.dim))

    def _inner(self) -> int:
        return self.heads * self.head_dim

    def _use_pallas(self) -> bool:
        if self.impl is None:
            return self.use_pallas
        from ..utils import resilience

        return resilience.resolve_ring_impl(self.impl) in ("pallas", "fused")

    def _off_the_ring(self, call: str) -> None:
        if (self.mesh is not None and self.use_ring
                and not self.force_regular_attn and seq_world(self.mesh) > 1):
            raise NotImplementedError(
                f"Mamba2Mixer.{call}: a state-space layer is not sharded "
                f"over a sequence mesh yet, its state would have to be "
                f"handed from rank to rank (ROADMAP R7); run a hybrid model "
                f"with mesh=None or use_ring=False")

    def _project_in(self, x):
        """``z (b, n, inner)``, ``u (b, n, inner + 2 state)`` before the
        convolution and the float32 step ``dt (b, n, heads)``."""
        with jax.named_scope("ssm/in_proj"):
            normed = self.prenorm(x)
            dtype = self.dtype or normed.dtype
            zud = jnp.dot(normed.astype(dtype), self.in_proj.astype(dtype))
            z, u, dt = jnp.split(
                zud, [self._inner(), zud.shape[-1] - self.heads], axis=-1)
            return z, u, nn.softplus(
                dt.astype(jnp.float32) + self.dt_bias.astype(jnp.float32))

    def _convolve(self, u, tail):
        """The activated ``x (b, n, heads x head_dim)``, ``B`` and ``C`` ``(b,
        n, state)``, and the new tail."""
        with jax.named_scope("ssm/conv"):
            u, tail = causal_conv(u, tail, self.conv_kernel, self.conv_bias)
            return *jnp.split(
                u, [self._inner(), self._inner() + self.state], axis=-1), tail

    def _project_out(self, y, z):
        """``y, z: (b, n, inner)`` -> ``(b, n, dim)``: the gate, then the
        norm over all of ``inner`` (one group), then ``W_out``."""
        with jax.named_scope("ssm/gate_norm"):
            gated = self.gate_norm(
                (y.astype(jnp.float32) * nn.silu(z.astype(jnp.float32))
                 ).astype(z.dtype))
        with jax.named_scope("ssm/out_proj"):
            return jnp.dot(gated, self.out_proj.astype(gated.dtype))

    def _a(self):
        return -jnp.exp(self.A_log.astype(jnp.float32))

    def _scan(self, x, conv_tail, state):
        """The chunked form over a whole sequence from ``conv_tail`` and
        ``state``: ``(out (b, n, dim), new tail, new state)``."""
        bsz, n, _ = x.shape
        z, u, dt = self._project_in(x)
        xs, b, c, conv_tail = self._convolve(u, conv_tail)
        with jax.named_scope("ssm/scan"):
            y, state = chunk_scan(xs, b, c, dt, self._a(), self.D, state,
                                  self.chunk)
        padded = -n % self.chunk
        for name, value in (("ssm_chunks", bsz * ((n + padded) // self.chunk)),
                            ("ssm_padded_positions", bsz * padded)):
            self._count(name, value)
        return self._project_out(y, z), conv_tail, state

    def _count(self, name, value):
        # free unless the caller asks: apply(..., mutable=["counters"])
        if not self.is_initializing():
            self.sow(COUNTERS, name, jnp.asarray(value, jnp.int32),
                     init_fn=lambda: None, reduce_fn=lambda _, new: new)

    def _fresh(self, bsz, dtype):
        channels = self._inner() + 2 * self.state
        return (jnp.zeros((bsz, self.conv - 1, channels), dtype),
                jnp.zeros((bsz, self.heads, self.head_dim, self.state),
                          jnp.float32))

    def __call__(self, x: jax.Array, mask: jax.Array | None = None,
                 segment_ids: jax.Array | None = None) -> jax.Array:
        """``x: (b, n, dim)`` -> ``(b, n, dim)``, every sequence from a zero
        state."""
        self._off_the_ring("__call__")
        if mask is not None or segment_ids is not None:
            raise NotImplementedError(
                "Mamba2Mixer: a state-space layer takes no key-padding mask "
                "and no packed documents yet: a state would have to be "
                "reset at each boundary (ROADMAP R7)")
        return self._scan(x, *self._fresh(x.shape[0], x.dtype))[0]

    def prefill(self, x: jax.Array, cache_conv: jax.Array,
                cache_state: jax.Array):
        """The prompt in one pass of the chunked form, from a zero state;
        the cache gets the convolution's tail and the state at the prompt's
        end.  Returns ``(out (b, n, dim), cache_conv, cache_state)``."""
        self._off_the_ring("prefill")
        tail, state = self._fresh(x.shape[0], cache_conv.dtype)
        out, tail, state = self._scan(x, tail, state)
        # as RingAttention.prefill: tie the cache write to its layer.  Free
        # of it XLA makes the tail at the program's end, from a product of
        # its own over the last rows of the layer's normed input, which it
        # keeps until then: 256 MB a layer at 4 x 8,192 tokens
        out, tail, state = lax.optimization_barrier((out, tail, state))
        return out, tail[:, None].astype(cache_conv.dtype), state

    def decode_step(self, x: jax.Array, cache_conv: jax.Array,
                    cache_state: jax.Array, pos: jax.Array):
        """One position in the recurrent form, from the cached tail and
        state (``pos`` is not read: a state has no position)."""
        self._off_the_ring("decode_step")
        bsz = x.shape[0]
        z, u, dt = self._project_in(x)
        xs, b, c, tail = self._convolve(u, cache_conv[:, 0])
        self._count("ssm_state_bytes", 2 * cache_state.size * 4)
        with jax.named_scope("ssm/step"):
            step = pallas_ssm_decode_step if self._use_pallas() else ssm_step
            y, state = step(
                cache_state, xs.reshape(bsz, self.heads, self.head_dim),
                b[:, 0], c[:, 0], dt[:, 0], self._a(), self.D)
        out = self._project_out(y.reshape(bsz, 1, -1), z)
        return out, tail[:, None].astype(cache_conv.dtype), state
