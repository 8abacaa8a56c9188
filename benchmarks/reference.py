"""The plain reference: the block as the configuration files state it, in
float32 ``jax.numpy``, and the comparison that decides ``correct``.

No kernels, no cache, no sharding, nothing imported from the program.  The
block is the one every configuration here runs (the ``changed`` keys of the
config files): token embedding; per layer ``x += attention(rmsnorm(x))``
and ``x += W1 gelu(W0 rmsnorm(x))`` with no biases, erf GELU, rotary
(half-rotation, theta ``rope_theta``) grouped-query causal attention over
the whole context; final RMSNorm and an untied output head.  Attention is
computed a block of queries at a time so that the scores of a few thousand
positions fit.  On a TPU a float32 matmul runs in lower precision unless
asked, so everything runs under ``default_matmul_precision("highest")``.

It reads the parameters out of the program's own tree (flax names), cast
to float32: the weights are the system's, the arithmetic is not.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

QUERY_BLOCK = 1024

# Tolerances, system against reference.  The system computes in bfloat16
# (8 bits of mantissa: a relative step of 2^-8 = 3.9e-3 at each rounding,
# accumulated in float32), the reference in float32 from the same weights.
# Measured on the chip at published widths over 7 seeds (my chip runs,
# PR 24): logits differ by a relative L2 of 7.4e-3 to 7.9e-3 (3b trainer,
# 8,192 positions, one chip), 6.3e-3 to 6.6e-3 (7b server, prefill + 16
# decoded positions); the mean loss by at most 1.2e-5 relative.  The logit
# bound is 1.5 times the largest of these, so it holds bfloat16 and little
# more: a dropped term, a wrong mask or position, or compute in a lower
# precision than the configuration states moves every logit (int8 attention
# alone is 2e-2 relative L2 on the attention output, docs/precision.md).
# The loss bound is 8 times the largest seen; it catches a wrong
# cross-entropy path, which the logits do not pass through.
LOGITS_REL_L2 = 1.2e-2
LOSS_REL = 1e-4


def _rmsnorm(x, gamma):
    rms = jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + 1e-12)
    return x / rms * gamma


def _rotary(x, theta):
    """x: (heads, n, d); half-rotation (NeoX) rotary at positions 0..n-1."""
    n, d = x.shape[-2:]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.arange(n, dtype=jnp.float32)[:, None] * inv[None, :]
    ang = jnp.concatenate([ang, ang], axis=-1)
    x1, x2 = jnp.split(x, 2, axis=-1)
    return x * jnp.cos(ang) + jnp.concatenate([-x2, x1], -1) * jnp.sin(ang)


def _attention(q, k, v):
    """q: (h, n, d), k and v: (hk, n, d); causal, grouped queries (query
    head i reads kv head i // (h / hk)); a block of queries at a time."""
    h, n, d = q.shape
    hk = k.shape[0]
    q = q.reshape(hk, h // hk, n, d) * d ** -0.5
    cols = jnp.arange(n)[None, :]
    out = []
    for lo in range(0, n, QUERY_BLOCK):
        hi = min(lo + QUERY_BLOCK, n)
        s = jnp.einsum("kgid,kjd->kgij", q[:, :, lo:hi], k)
        s = jnp.where(cols <= jnp.arange(lo, hi)[:, None], s, -jnp.inf)
        out.append(jnp.einsum("kgij,kjd->kgid", jax.nn.softmax(s, -1), v))
    return jnp.concatenate(out, axis=2).reshape(h, n, d)


def hidden(params, tokens, config):
    """Final-norm features ``(n, hidden)`` of one sequence ``(n,)``."""
    p = jax.tree.map(lambda w: w.astype(jnp.float32), params["params"])
    h, hk = config["num_attention_heads"], config["num_key_value_heads"]
    d = config["hidden_size"] // h
    theta = config["rope_theta"]
    n = tokens.shape[0]
    x = p["embed"]["embedding"][tokens]
    for i in range(config["num_hidden_layers"]):
        a = p[f"attn_layers_{i}"]
        qkv = _rmsnorm(x, a["prenorm"]["gamma"]) @ a["to_qkv"]["kernel"]
        q, k, v = jnp.split(qkv, [h * d, (h + hk) * d], axis=-1)
        q = _rotary(q.reshape(n, h, d).transpose(1, 0, 2), theta)
        k = _rotary(k.reshape(n, hk, d).transpose(1, 0, 2), theta)
        v = v.reshape(n, hk, d).transpose(1, 0, 2)
        o = _attention(q, k, v).transpose(1, 0, 2).reshape(n, h * d)
        x = x + o @ a["to_out"]["kernel"]
        f = p[f"ff_layers_{i}"]
        y = _rmsnorm(x, f["RMSNorm_0"]["gamma"]) @ f["Dense_0"]["kernel"]
        x = x + jax.nn.gelu(y, approximate=False) @ f["Dense_1"]["kernel"]
    return _rmsnorm(x, p["final_norm"]["gamma"])


def logits(params, tokens, config, last: int | None = None):
    """Logits ``(n or last, vocab)`` of one sequence ``(n,)``; ``last``
    keeps the head to the final positions."""
    with jax.default_matmul_precision("highest"):
        x = hidden(params, tokens, config)
        if last is not None:
            x = x[-last:]
        return x @ params["params"]["to_logits"]["kernel"].astype(jnp.float32)


def loss(all_logits, labels):
    """Mean next-token cross-entropy of ``(n, vocab)`` against ``(n,)``."""
    lse = jax.nn.logsumexp(all_logits, axis=-1)
    chosen = jnp.take_along_axis(all_logits, labels[:, None], axis=-1)[:, 0]
    return jnp.mean(lse - chosen)


def rel_l2(got, want):
    got, want = got.astype(jnp.float32), want.astype(jnp.float32)
    return jnp.linalg.norm(got - want) / jnp.linalg.norm(want)


def verdict(logits_rel_l2: float, loss_rel: float | None = None) -> bool:
    """The comparison that decides ``correct`` (NaN compares false)."""
    ok = logits_rel_l2 <= LOGITS_REL_L2
    if loss_rel is not None:
        ok = ok and loss_rel <= LOSS_REL
    return bool(ok)
