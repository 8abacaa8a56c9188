"""Parity: end-to-end RingTransformer, ring vs regular attention.

JAX-native analogue of the reference's ``assert.py``: a depth-2 transformer
with ring attention + auto-shard over 8 devices must match the identical
parameters run with regular attention — forward logits, loss, and
token-embedding gradients (ref ``assert.py:114-137``) — including striped
layout, odd sequence lengths (padding), GQA, and a 2x4 mesh
(``num_sharded_batches`` analogue).
"""

import math
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ring_attention_tpu.models import RingTransformer
from ring_attention_tpu.parallel import create_mesh

ATOL = 3e-5
GRAD_ATOL = 1e-3  # ref uses 1e-2 for embedding grads (assert.py:131-135)

VOCAB = 256


def make_pair(mesh, **kw):
    common = dict(
        num_tokens=VOCAB, dim=32, depth=2, heads=4, dim_head=8,
        bucket_size=4, causal=True,
    )
    common.update(kw)
    ring_model = RingTransformer(use_ring=True, mesh=mesh, **common)
    ref_model = RingTransformer(
        use_ring=False, force_regular_attn=True,
        **{k: v for k, v in common.items() if k not in ("striped", "use_pallas", "sequence_parallel")},
    )
    return ring_model, ref_model


@pytest.fixture(scope="module")
def mesh():
    return create_mesh(ring_size=8)


@pytest.mark.parametrize("striped", [False, True])
@pytest.mark.parametrize("seq_len", [64, 63])
def test_logits_parity(rng, mesh, striped, seq_len):
    ring_model, ref_model = make_pair(mesh, striped=striped)
    tokens = jnp.asarray(rng.integers(0, VOCAB, (2, seq_len)), jnp.int32)
    params = ref_model.init(jax.random.PRNGKey(0), tokens)
    ref = ref_model.apply(params, tokens)
    out = ring_model.apply(params, tokens)
    np.testing.assert_allclose(out, ref, atol=ATOL)


def test_loss_and_embedding_grads(rng, mesh):
    """Token-embedding gradient parity through loss (ref assert.py:125-135)."""
    ring_model, ref_model = make_pair(mesh, striped=True)
    tokens = jnp.asarray(rng.integers(0, VOCAB, (2, 63)), jnp.int32)
    params = ref_model.init(jax.random.PRNGKey(0), tokens)

    def loss(model, p):
        return model.apply(p, tokens, return_loss=True)

    l_ref = loss(ref_model, params)
    l_ring = loss(ring_model, params)
    np.testing.assert_allclose(l_ring, l_ref, atol=ATOL)

    g_ref = jax.grad(lambda p: loss(ref_model, p))(params)
    g_ring = jax.grad(lambda p: loss(ring_model, p))(params)
    emb_ref = g_ref["params"]["embed"]["embedding"]
    emb_ring = g_ring["params"]["embed"]["embedding"]
    np.testing.assert_allclose(emb_ring, emb_ref, atol=GRAD_ATOL)


def test_gqa_and_lookback(rng, mesh):
    """GQA + per-layer lookback tuple (local -> global over depth)."""
    ring_model, ref_model = make_pair(
        mesh, striped=False, kv_heads=2, max_lookback_seq_len=(16, None)
    )
    tokens = jnp.asarray(rng.integers(0, VOCAB, (2, 64)), jnp.int32)
    params = ref_model.init(jax.random.PRNGKey(0), tokens)
    np.testing.assert_allclose(
        ring_model.apply(params, tokens), ref_model.apply(params, tokens), atol=ATOL
    )


def test_data_parallel_rings(rng):
    """2x4 mesh: batch over data axis, two independent rings."""
    mesh = create_mesh(ring_size=4, data_size=2)
    ring_model, ref_model = make_pair(mesh, striped=True)
    tokens = jnp.asarray(rng.integers(0, VOCAB, (4, 64)), jnp.int32)
    params = ref_model.init(jax.random.PRNGKey(0), tokens)
    np.testing.assert_allclose(
        ring_model.apply(params, tokens), ref_model.apply(params, tokens), atol=ATOL
    )


def test_non_causal_with_mask(rng, mesh):
    ring_model, ref_model = make_pair(mesh, causal=False)
    tokens = jnp.asarray(rng.integers(0, VOCAB, (2, 63)), jnp.int32)
    mask = jnp.asarray(rng.random((2, 63)) > 0.2)
    params = ref_model.init(jax.random.PRNGKey(0), tokens, mask)
    np.testing.assert_allclose(
        ring_model.apply(params, tokens, mask),
        ref_model.apply(params, tokens, mask),
        atol=ATOL,
    )


def test_non_causal_padding_without_mask(rng, mesh):
    """Padding in non-causal mode must not let real tokens attend pad slots
    even when the user passes no mask (regression: synthesized pad mask)."""
    ring_model, ref_model = make_pair(mesh, causal=False)
    tokens = jnp.asarray(rng.integers(0, VOCAB, (2, 61)), jnp.int32)
    params = ref_model.init(jax.random.PRNGKey(0), tokens)
    np.testing.assert_allclose(
        ring_model.apply(params, tokens), ref_model.apply(params, tokens), atol=ATOL
    )


def test_odd_bucket_interaction(rng, mesh):
    """seq 56 over ring 8 -> n_local 7, bucket_size 4 not a divisor."""
    ring_model, ref_model = make_pair(mesh, striped=True)
    tokens = jnp.asarray(rng.integers(0, VOCAB, (2, 56)), jnp.int32)
    params = ref_model.init(jax.random.PRNGKey(0), tokens)
    np.testing.assert_allclose(
        ring_model.apply(params, tokens), ref_model.apply(params, tokens), atol=ATOL
    )


def test_pallas_transformer_parity(rng, mesh):
    """End-to-end transformer on the Pallas kernel path (interpret on CPU)."""
    ring_model, ref_model = make_pair(mesh, striped=True, use_pallas=True)
    tokens = jnp.asarray(rng.integers(0, VOCAB, (2, 64)), jnp.int32)
    params = ref_model.init(jax.random.PRNGKey(0), tokens)
    np.testing.assert_allclose(
        ring_model.apply(params, tokens), ref_model.apply(params, tokens),
        atol=ATOL,
    )


def test_bf16_training_path(rng, mesh):
    """bf16 activations end-to-end: loss finite and grads flow."""
    model = RingTransformer(
        num_tokens=VOCAB, dim=32, depth=1, heads=4, dim_head=8,
        causal=True, striped=True, bucket_size=8, mesh=mesh,
        dtype=jnp.bfloat16,
    )
    tokens = jnp.asarray(rng.integers(0, VOCAB, (2, 65)), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), tokens)
    loss, grads = jax.value_and_grad(
        lambda p: model.apply(p, tokens, return_loss=True)
    )(params)
    assert bool(jnp.isfinite(loss))
    assert all(bool(jnp.isfinite(g).all()) for g in jax.tree.leaves(grads))


def test_remat_parity(rng, mesh):
    """remat=True must not change values (only memory/recompute)."""
    common = dict(num_tokens=VOCAB, dim=32, depth=2, heads=4, dim_head=8,
                  bucket_size=4, causal=True, striped=True, mesh=mesh)
    m1 = RingTransformer(**common)
    m2 = RingTransformer(remat=True, **common)
    tokens = jnp.asarray(rng.integers(0, VOCAB, (2, 64)), jnp.int32)
    params = m1.init(jax.random.PRNGKey(0), tokens)
    # remat + shard_map requires jit (as any real train step is)
    l1, g1 = jax.jit(jax.value_and_grad(lambda p: m1.apply(p, tokens, return_loss=True)))(params)
    l2, g2 = jax.jit(jax.value_and_grad(lambda p: m2.apply(p, tokens, return_loss=True)))(params)
    np.testing.assert_allclose(l1, l2, atol=1e-6)
    for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(g2)):
        np.testing.assert_allclose(a, b, atol=1e-5)


def test_remat_save_attn_policy_parity(rng, mesh):
    """remat_policy="save_attn" (saved flash residuals, no O(n^2) recompute
    in the backward) must be value-identical to plain full-block remat."""
    common = dict(num_tokens=VOCAB, dim=32, depth=2, heads=4, dim_head=8,
                  bucket_size=4, causal=True, striped=True, mesh=mesh,
                  remat=True)
    m1 = RingTransformer(**common)
    m2 = RingTransformer(remat_policy="save_attn", **common)
    tokens = jnp.asarray(rng.integers(0, VOCAB, (2, 64)), jnp.int32)
    params = m1.init(jax.random.PRNGKey(0), tokens)
    l1, g1 = jax.jit(jax.value_and_grad(lambda p: m1.apply(p, tokens, return_loss=True)))(params)
    l2, g2 = jax.jit(jax.value_and_grad(lambda p: m2.apply(p, tokens, return_loss=True)))(params)
    np.testing.assert_allclose(l1, l2, atol=1e-6)
    for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(g2)):
        np.testing.assert_allclose(a, b, atol=1e-5)


def _train_dots(model, params, tokens):
    """Number of dot ops in the compiled train step (scan bodies count once,
    so an elided attention recompute is a strict drop regardless of trip
    count — CPU cost_analysis flops don't scale scan bodies and can't see
    the gap)."""
    f = jax.jit(
        jax.value_and_grad(lambda p, t: model.apply(p, t, return_loss=True))
    )
    return f.lower(params, tokens).compile().as_text().count("dot(")


@pytest.mark.parametrize("use_mesh", [False, True], ids=["local", "ring"])
def test_remat_save_attn_actually_elides(rng, mesh, use_mesh):
    """remat_policy="save_attn" must REDUCE backward compute, not just match
    values: the saved (flash_out, flash_lse) residuals let the backward's
    residual recompute dead-code-eliminate the attention forward.  The
    parity test above passes even if the policy names match nothing
    (ADVICE r2); this pins the elision itself in the compiled program: the
    score and pv matmuls (2 per layer) must vanish from the recompute."""
    common = dict(num_tokens=32, dim=32, depth=2, heads=4, dim_head=8,
                  bucket_size=8, causal=True, remat=True)
    if use_mesh:
        common.update(mesh=mesh, striped=True)
    else:
        common.update(use_ring=False)
    m_plain = RingTransformer(**common)
    m_save = RingTransformer(remat_policy="save_attn", **common)
    tokens = jnp.asarray(rng.integers(0, 32, (2, 128)), jnp.int32)
    params = m_plain.init(jax.random.PRNGKey(0), tokens)
    dots_plain = _train_dots(m_plain, params, tokens)
    dots_save = _train_dots(m_save, params, tokens)
    assert dots_save <= dots_plain - 2 * m_plain.depth, (dots_save, dots_plain)


@pytest.mark.slow
def test_variable_per_rank_batch(rng):
    """Variable per-rank batch through the model path (the reference's
    ``batch_size_var_len``, assert_attn.py:81-82 via distributed.py:58-84):
    data-parallel rows contribute DIFFERENT numbers of real examples, padded
    to a static max and masked out of the loss with ``example_mask``.  Loss
    and token-embedding grads must match running only the real examples."""
    mesh = create_mesh(ring_size=4, data_size=2)
    ring_model, ref_model = make_pair(mesh, striped=True)

    n = 64
    # data row 0 holds 1 real example, row 1 holds 2 (base + rank, like the
    # reference's var-len test); pad both rows to 2
    real = jnp.asarray(rng.integers(0, VOCAB, (3, n)), jnp.int32)
    pad_example = jnp.zeros((1, n), jnp.int32)
    padded = jnp.concatenate([real[:1], pad_example, real[1:]], axis=0)  # (4, n)
    example_mask = jnp.asarray([True, False, True, True])

    params = ref_model.init(jax.random.PRNGKey(0), real)

    l_ref = ref_model.apply(params, real, return_loss=True)
    l_ring = ring_model.apply(
        params, padded, return_loss=True, example_mask=example_mask
    )
    np.testing.assert_allclose(l_ring, l_ref, atol=ATOL)

    g_ref = jax.grad(lambda p: ref_model.apply(p, real, return_loss=True))(params)
    g_ring = jax.grad(
        lambda p: ring_model.apply(
            p, padded, return_loss=True, example_mask=example_mask
        )
    )(params)
    np.testing.assert_allclose(
        g_ring["params"]["embed"]["embedding"],
        g_ref["params"]["embed"]["embedding"],
        atol=GRAD_ATOL,
    )


def test_variable_batch_gather_roundtrip(rng):
    """all_gather_variable feeds the padded-batch recipe: ragged per-device
    shards gather into (padded global, validity mask) whose real rows are
    exactly the unpadded examples — the mask is what example_mask consumes."""
    from ring_attention_tpu.utils.compat import shard_map
    from jax.sharding import PartitionSpec as P

    from ring_attention_tpu.parallel import all_gather_variable, create_mesh

    mesh = create_mesh(ring_size=1, data_size=8)
    max_b, n = 3, 8
    x = jnp.asarray(rng.integers(0, VOCAB, (8 * max_b, n)), jnp.int32)
    lengths = jnp.asarray([(1 + r) % (max_b + 1) for r in range(8)], jnp.int32)

    def gather(x, length):
        g, m = all_gather_variable(x, length[0], "data", axis=0)
        return g, m

    g, m = shard_map(
        gather, mesh=mesh,
        in_specs=(P("data", None), P("data")),
        out_specs=(P(), P()),
        check_vma=False,  # outputs replicated over the trivial seq axis too
    )(x, lengths)
    assert g.shape == (8 * max_b, n)
    assert int(m.sum()) == int(lengths.sum())
    # masked rows are exactly each shard's first `length` rows
    want = np.zeros(8 * max_b, bool)
    for r in range(8):
        want[r * max_b : r * max_b + int(lengths[r])] = True
    np.testing.assert_array_equal(np.asarray(m), want)


@pytest.mark.parametrize("sp", ["zigzag", "ulysses"])
def test_transformer_sequence_parallel_modes(rng, mesh, sp):
    """End-to-end transformer under each context-parallel scheme."""
    ring_model, ref_model = make_pair(mesh, sequence_parallel=sp, heads=8, dim_head=4)
    tokens = jnp.asarray(rng.integers(0, VOCAB, (2, 63)), jnp.int32)
    params = ref_model.init(jax.random.PRNGKey(0), tokens)
    np.testing.assert_allclose(
        ring_model.apply(params, tokens), ref_model.apply(params, tokens), atol=ATOL
    )


def test_ring_dkv_dtype_through_model(rng, mesh):
    """ring_dkv_dtype="bfloat16" must reach the ring through the model
    layer (the train-path consumer it exists for): loss matches the f32
    circulation and grads stay finite and close."""
    common = dict(num_tokens=VOCAB, dim=32, depth=2, heads=4, dim_head=8,
                  bucket_size=8, causal=True, striped=True, mesh=mesh)
    m32 = RingTransformer(**common)
    m16 = RingTransformer(ring_dkv_dtype="bfloat16", **common)
    tokens = jnp.asarray(rng.integers(0, VOCAB, (2, 64)), jnp.int32)
    params = m32.init(jax.random.PRNGKey(0), tokens)
    l32, g32 = jax.jit(jax.value_and_grad(
        lambda p: m32.apply(p, tokens, return_loss=True)))(params)
    l16, g16 = jax.jit(jax.value_and_grad(
        lambda p: m16.apply(p, tokens, return_loss=True)))(params)
    np.testing.assert_allclose(l16, l32, atol=1e-6)  # fwd identical
    for a, b in zip(jax.tree.leaves(g16), jax.tree.leaves(g32)):
        assert bool(jnp.isfinite(a).all())
        np.testing.assert_allclose(a, b, atol=3e-2, rtol=3e-2)


def _ce_mesh(layout):
    """Mesh and model options of one ``test_chunked_ce_matches_dense``
    layout (built per case: a parametrize argument may not touch jax)."""
    if layout == "local":
        return dict(use_ring=False)
    if layout == "data2-seq4":
        return dict(mesh=create_mesh(ring_size=4, data_size=2), striped=True)
    kw = dict(mesh=create_mesh(ring_size=8))
    if layout == "striped":
        kw["striped"] = True
    elif layout in ("zigzag", "ulysses"):
        kw.update(sequence_parallel=layout, heads=8, dim_head=4)
    else:
        assert layout == "contiguous", layout
    return kw


# (chunk, layout, seq_len, extra): the ring cases run 8 shards (4 on the
# data2-seq4 mesh), so after the label shift a shard holds (seq_len - 1) / 8
# rows and the chunk is rows per shard per scan step
@pytest.mark.parametrize("chunk,layout,seq_len,extra", [
    pytest.param(8, "local", 33, None, id="8-local"),
    pytest.param(5, "local", 33, None, id="5-local"),
    pytest.param(64, "local", 33, None, id="64-local"),  # 64 > n: clamp path
    pytest.param(8, "striped", 33, None, id="8-striped"),
    pytest.param(8, "zigzag", 33, None, id="8-zigzag"),
    pytest.param(4, "contiguous", 65, None, id="contiguous-ring"),
    pytest.param(3, "striped", 65, None, id="pad-within-shard"),  # 8 -> 9
    pytest.param(64, "striped", 65, None, id="chunk-over-shard"),  # clamp to 8
    pytest.param(4, "striped", 60, None, id="model-top-pad"),  # 59 -> 64
    pytest.param(4, "zigzag", 60, None, id="model-top-pad-zigzag"),
    pytest.param(4, "ulysses", 65, None, id="ulysses"),
    # documents end at 13 and 37: inside the contiguous shards 8..15, 32..39
    pytest.param(4, "contiguous", 65, "segments", id="segments-in-shard"),
    pytest.param(4, "striped", 65, "example_mask", id="example-mask"),
    pytest.param(4, "data2-seq4", 65, None, id="data2-seq4"),
])
def test_chunked_ce_matches_dense(rng, chunk, layout, seq_len, extra):
    """loss_chunk_size: the chunk-scan loss (and the gradients it makes)
    equals the dense logits+CE path — including a chunk size that doesn't
    divide the sequence, one larger than the sequence (clamped), an
    ignore_index tail, and every sequence-parallel layout, where the
    features stay in their shards, the labels are permuted to them and the
    chunks are taken within each shard (padded, clamped, under model-top
    padding, packed documents, a ragged batch and a data axis)."""
    kw = dict(
        num_tokens=VOCAB, dim=32, depth=2, heads=4, dim_head=8,
        causal=True, bucket_size=8,
    )
    kw.update(_ce_mesh(layout))
    dense = RingTransformer(**kw)
    chunked = RingTransformer(loss_chunk_size=chunk, **kw)
    tokens = jnp.asarray(rng.integers(0, VOCAB, (2, seq_len)), jnp.int32)
    tokens = tokens.at[0, 20:].set(-1)  # ignore_index tail in row 0
    params = dense.init(jax.random.PRNGKey(0), jnp.abs(tokens))
    call = {}
    if extra == "segments":
        seg = np.zeros((2, seq_len), np.int32)
        seg[:, 13:] = 1
        seg[:, 37:] = 2
        call["segment_ids"] = jnp.asarray(seg)
        tokens = jnp.abs(tokens)  # labels on both sides of each boundary
    elif extra == "example_mask":
        call["example_mask"] = jnp.asarray([False, True])

    def loss_fn(model):
        return lambda p: model.apply(p, tokens, return_loss=True, **call)

    ld, gd = jax.jit(jax.value_and_grad(loss_fn(dense)))(params)
    lc, gc = jax.jit(jax.value_and_grad(loss_fn(chunked)))(params)
    np.testing.assert_allclose(lc, ld, rtol=2e-6)

    flat_d = jax.tree_util.tree_leaves_with_path(gd)
    flat_c = {jax.tree_util.keystr(p): l
              for p, l in jax.tree_util.tree_leaves_with_path(gc)}
    for p, leaf in flat_d:
        key = jax.tree_util.keystr(p)
        np.testing.assert_allclose(
            flat_c[key], leaf, atol=5e-6, err_msg=key
        )


def test_chunked_ce_program_does_not_materialize_logits(rng):
    """The chunked-loss jaxpr must contain no (b, n, vocab) intermediate —
    the whole point is that only (b, chunk, vocab) logits ever exist."""
    n, chunk = 64, 8
    model = RingTransformer(
        num_tokens=VOCAB, dim=16, depth=1, heads=2, dim_head=8,
        causal=True, bucket_size=8, use_ring=False, loss_chunk_size=chunk,
    )
    tokens = jnp.asarray(rng.integers(0, VOCAB, (1, n + 1)), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), tokens)
    jaxpr = jax.make_jaxpr(
        lambda p: model.apply(p, tokens, return_loss=True)
    )(params)
    full = f"1,{n},{VOCAB}"
    assert full not in str(jaxpr), f"found full-logits shape ({full})"


def _vocab_products(closed_jaxpr, vocab):
    """Every ``dot_general`` of a traced program with a vocabulary-sized
    operand or result, scan bodies and the other sub-programs included."""
    from ring_attention_tpu.analysis.contracts import _sub_jaxprs

    found = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "dot_general" and any(
                    vocab in v.aval.shape for v in (*eqn.invars, *eqn.outvars)):
                found.append(eqn)
            for value in eqn.params.values():
                for sub in _sub_jaxprs(value):
                    walk(sub)

    walk(closed_jaxpr.jaxpr)
    return found


def _small_model(**kw):
    return RingTransformer(
        num_tokens=VOCAB, dim=32, depth=1, heads=2, dim_head=16, causal=True,
        bucket_size=8, use_ring=False, **kw)


@pytest.mark.parametrize("differentiated,products", [(False, 1), (True, 3)])
def test_chunked_ce_products_a_chunk(rng, differentiated, products):
    """The chunk scan makes its own gradient: the differentiated program
    holds three vocabulary-sized products (logits, dx, dW), all in the one
    scan body, and nothing recomputes the logits; the undifferentiated one
    holds the logits' product and no gradient product."""
    model = _small_model(loss_chunk_size=8)
    tokens = jnp.asarray(rng.integers(0, VOCAB, (2, 33)), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), tokens)

    def loss(p):
        return model.apply(p, tokens, return_loss=True)

    program = jax.value_and_grad(loss) if differentiated else loss
    found = _vocab_products(jax.make_jaxpr(program)(params), VOCAB)
    assert len(found) == products, found
    results = sorted(eqn.outvars[0].aval.shape for eqn in found)
    logits, dx, dw = (2, 8, VOCAB), (2, 8, 32), (32, VOCAB)
    assert results == ([dx, logits, dw] if differentiated else [logits])


@pytest.mark.parametrize("case", ["scaled-cotangent", "no-valid-label"])
def test_chunked_ce_backward_rule(rng, case):
    """The backward rule is a multiplication by the incoming cotangent:
    ``grad(3 * loss) = 3 * grad(loss)``; and a batch with no valid label
    gives zero gradients and nothing non-finite."""
    model = _small_model(loss_chunk_size=8)
    tokens = jnp.asarray(rng.integers(0, VOCAB, (2, 33)), jnp.int32)
    tokens = tokens.at[0, 20:].set(-1)  # ignore_index tail in row 0
    params = model.init(jax.random.PRNGKey(0), jnp.abs(tokens))
    scale, call = 3.0, {}
    if case == "no-valid-label":
        call["example_mask"] = jnp.asarray([False, False])

    def loss(p, k=1.0):
        return k * model.apply(p, tokens, return_loss=True, **call)

    value, grads = jax.jit(jax.value_and_grad(loss))(params)
    scaled = jax.jit(jax.grad(lambda p: loss(p, scale)))(params)
    for g, gs in zip(jax.tree.leaves(grads), jax.tree.leaves(scaled)):
        assert bool(jnp.isfinite(g).all())
        np.testing.assert_allclose(gs, scale * g, rtol=1e-5, atol=1e-6)
        if case == "no-valid-label":
            assert not np.asarray(g).any()
    if case == "no-valid-label":
        assert float(value) == 0.0
    else:
        head = grads["params"]["to_logits"]["kernel"]
        assert float(jnp.abs(head).max()) > 0


def test_chunked_ce_bfloat16_gradients(rng):
    """bfloat16 compute: ``dx`` and ``dW`` of the in-scan gradient sit
    inside bfloat16 rounding of autodiff's on the dense path, and ``dW``
    (accumulated in float32 across the chunks) is no further from a
    float32 reference than autodiff of the chunk scan was, which rounds
    each chunk's product to bfloat16 before the float32 sum."""
    from ring_attention_tpu.models.transformer import (
        _chunked_nll_sum,
        _head_logits,
        _position_nll,
    )

    nc, b, c, dim = 8, 2, 16, 32
    xs = jnp.asarray(rng.standard_normal((nc, b, c, dim)), jnp.bfloat16)
    kernel = jnp.asarray(rng.standard_normal((dim, VOCAB)) * dim ** -0.5,
                         jnp.float32)
    labels = jnp.asarray(rng.integers(0, VOCAB, (nc, b, c)), jnp.int32)
    valid = jnp.asarray(rng.random((nc, b, c)) < 0.8)

    def dense(dtype):
        def total(x, w):
            x = x if dtype else x.astype(jnp.float32)
            return _position_nll(
                _head_logits(x, w, dtype)[0], labels, valid).sum()
        return total

    def grads(fn):
        dx, dw = jax.jit(jax.grad(fn, argnums=(0, 1)))(xs, kernel)
        return np.asarray(dx, np.float32), np.asarray(dw, np.float32)

    bf16 = jnp.bfloat16
    dx, dw = grads(lambda x, w: _chunked_nll_sum(x, w, labels, valid, bf16))
    # the undecorated function: the same scan, differentiated by autodiff
    dx_scan, dw_scan = grads(
        lambda x, w: _chunked_nll_sum.fun(x, w, labels, valid, bf16))
    dx_dense, dw_dense = grads(dense(bf16))
    _, dw_f32 = grads(dense(None))

    def assert_close(got, want, ulps):
        # bfloat16 keeps 8 bits: half an ulp is 2 ** -9 of the value
        np.testing.assert_allclose(
            got, want, rtol=ulps * 2.0 ** -8,
            atol=ulps * 2.0 ** -8 * np.abs(want).max())

    assert_close(dx, dx_dense, 4)
    assert_close(dw, dw_dense, 4)
    assert_close(dx, dx_scan, 1)  # rounded at the same place, chunk by chunk

    def off(a):
        return float(np.linalg.norm(a - dw_f32) / np.linalg.norm(dw_f32))

    assert off(dw) <= off(dw_scan), (off(dw), off(dw_scan))
    assert off(dw) <= off(dw_dense) * 1.05, (off(dw), off(dw_dense))


def test_chunked_ce_gradient_accumulation(rng):
    """``make_train_step(accum_steps=2)`` over the chunked loss gives the
    update of ``accum_steps=1`` (plain SGD at rate 1: the update is the
    gradient), and both the dense path's."""
    import optax

    from ring_attention_tpu.utils import make_train_step

    tokens = jnp.asarray(rng.integers(0, VOCAB, (4, 33)), jnp.int32)
    chunked, dense = _small_model(loss_chunk_size=8), _small_model()
    params = dense.init(jax.random.PRNGKey(0), tokens)
    opt = optax.sgd(1.0)

    def update(model, accum):
        step = jax.jit(make_train_step(
            lambda p, t: model.apply(p, t, return_loss=True), opt,
            accum_steps=accum))
        new, _, loss = step(params, opt.init(params), tokens)
        return loss, jax.tree.map(lambda a, b: a - b, params, new)

    loss_1, grad_1 = update(chunked, 1)
    for model, accum in ((chunked, 2), (dense, 1)):
        loss, grad = update(model, accum)
        np.testing.assert_allclose(loss, loss_1, rtol=2e-6)
        for a, b in zip(jax.tree.leaves(grad), jax.tree.leaves(grad_1)):
            np.testing.assert_allclose(a, b, atol=5e-6)


def _hlo_shapes(lines):
    """Every array shape (a tuple of ints) written in these HLO lines."""
    return {
        tuple(int(d) for d in dims.split(",") if d)
        for line in lines
        for dims in re.findall(r"\b(?:pred|[a-z]+[0-9]+)\[([0-9,]*)\]", line)
    }


def test_chunked_ce_ring_program_scores_own_rows_only(rng):
    """On a ring the compiled chunked loss is sharded over the sequence:
    every device scans its own n / ring rows, chunks taken within the
    shard.  Read from the partitioned HLO of ``value_and_grad``:

    (a) nothing in the loss holds the features of more than one shard, let
        alone (b, n)-by-vocab logits;
    (b) the per-device logits are (b, c, vocab), the scan has
        n_local / c steps;
    (c) the loss gathers nothing, and beside the scalar carry its one
        all-reduce is the head's weight gradient (a partial sum a device).

    Before the scan kept its shard axis the partitioner replicated it:
    an all-gather of the (b, n, dim) features and n / c steps on every
    device."""
    ring, n, dim, vocab, chunk = 4, 64, 24, 136, 8
    n_local = n // ring
    mesh = create_mesh(ring_size=ring, devices=jax.devices()[:ring])
    model = RingTransformer(
        num_tokens=vocab, dim=dim, depth=1, heads=2, dim_head=12,
        causal=True, bucket_size=8, striped=True, mesh=mesh,
        loss_chunk_size=chunk,
    )
    tokens = jnp.asarray(rng.integers(0, vocab, (1, n + 1)), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), tokens)
    hlo = jax.jit(jax.value_and_grad(
        lambda p: model.apply(p, tokens, return_loss=True)
    )).lower(params).compile().as_text()
    loss = [line for line in hlo.splitlines() if "_chunked_ce" in line]
    assert loss, "no instruction carries the _chunked_ce scope"
    shapes = _hlo_shapes(loss)
    head = {(dim, vocab), (vocab, dim)}  # the kernel and its gradient

    # (a) and (b)
    for shape in shapes - head:
        if shape[-1:] == (dim,):
            assert math.prod(shape[:-1]) <= n_local, (
                f"features of more than one shard in the loss: {shape}")
        if vocab in shape:
            assert math.prod(shape) // vocab <= chunk, (
                f"logits of more than one chunk in the loss: {shape}")
    assert (1, 1, chunk, vocab) in shapes  # (b, this shard, c, vocab)
    assert (n_local // chunk, 1, 1, chunk, dim) in shapes  # the scanned xs

    # (c)
    def collectives(lines, kind):
        return [line for line in lines
                if re.search(rf"\b{kind}(-start)?\(", line)]

    for kind in ("all-gather", "all-to-all", "collective-permute"):
        assert not collectives(loss, kind), f"{kind} in the loss"
    assert not collectives(hlo.splitlines(), "all-gather")
    reduced = [
        shape
        for line in collectives(loss, "all-reduce")
        for shape in _hlo_shapes([line.split(" all-reduce")[0]])
        if shape  # () is the scalar carry: sum of nll, count
    ]
    assert len(reduced) == 1 and reduced[0] in head, reduced
