"""The granitemoehybrid block (granite-4.0-h-small: Mamba-2 mixers behind a
recurrent-state cache beside the KV cache, softmax top-k routing, four
multipliers, a tied head) at tiny widths on the CPU: the program, built from
a frozen ``ModelConfig`` through ``RingTransformer``'s own constructor,
against the plain reference the benchmark keeps
(``benchmarks/references/granite_hybrid.py``), with seeded weights."""

import hashlib
import json
import math
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ring_attention_tpu.models import (
    Mamba2Mixer,
    ModelConfig,
    RingTransformer,
    RoutedFeedForward,
)
from ring_attention_tpu.models.ssm import causal_conv, chunk_scan
from ring_attention_tpu.ops.pallas_ssm import pallas_ssm_decode_step, ssm_step
from ring_attention_tpu.parallel import create_mesh

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
from benchmarks.kinds import serve_hybrid  # noqa: E402
from benchmarks.references import granite_hybrid  # noqa: E402
from tests.test_mla import TINY as DOTS_TOY  # noqa: E402

VOCAB = 96
TINY = dict(
    model_type="granitemoehybrid", vocab_size=VOCAB, hidden_size=32,
    num_attention_heads=4, num_key_value_heads=2, intermediate_size=16,
    shared_intermediate_size=24, num_hidden_layers=3,
    layer_types=["mamba", "attention", "mamba"], mamba_n_heads=8,
    mamba_d_head=8, mamba_d_state=16, mamba_n_groups=1, mamba_d_conv=4,
    mamba_expand=2, mamba_chunk_size=8, mamba_conv_bias=True,
    mamba_proj_bias=False, attention_bias=False, hidden_act="silu",
    normalization_function="rmsnorm", position_embedding_type="nope",
    embedding_multiplier=12, residual_multiplier=0.22,
    attention_multiplier=0.2, logits_scaling=16, rms_norm_eps=1e-5,
    rope_theta=10000, num_local_experts=4,
    published={"num_local_experts": 8}, first_expert=2,
    num_experts_per_tok=3, tie_word_embeddings=True)
ATOL = 2e-5


def build(config=TINY, **options):
    options = {"mesh": None, "use_ring": False, "bucket_size": 4, **options}
    return RingTransformer.from_config(ModelConfig.from_dict(config), **options)


@pytest.fixture(scope="module")
def tiny():
    model = build()
    tokens = jnp.asarray(
        np.random.default_rng(0).integers(0, VOCAB, (3, 21)), jnp.int32)
    return model, model.init(jax.random.PRNGKey(0), tokens), tokens


@pytest.mark.parametrize("row", [0, 1, 2])
def test_forward_matches_reference(tiny, row):
    """``__call__`` through ``_blocks`` (the chunked form, 21 positions in
    chunks of 8) against the reference's position-by-position recurrence."""
    model, params, tokens = tiny
    got = model.apply(params, tokens)[row]
    want = granite_hybrid.logits(params, tokens[row], TINY)
    assert got.shape == want.shape == (21, VOCAB)
    np.testing.assert_allclose(got, want, atol=ATOL)


@pytest.mark.parametrize("n", [5, 8, 16, 21, 40])
def test_chunked_form_equals_the_recurrence(n):
    """Lengths under a chunk, of one chunk, of many, and no multiple of it,
    from a state that is not zero: ``chunk_scan`` in chunks of 8 against the
    reference's ``lax.scan`` over positions."""
    rng = np.random.default_rng(n)
    h, p, s = 4, 8, 16
    x = jnp.asarray(rng.normal(size=(n, h, p)), jnp.float32)
    b, c = (jnp.asarray(rng.normal(size=(n, s)), jnp.float32) for _ in "bc")
    dt = jnp.asarray(rng.uniform(0.001, 0.5, size=(n, h)), jnp.float32)
    a = -jnp.asarray(rng.uniform(1, 16, size=(h,)), jnp.float32)
    d = jnp.asarray(rng.normal(size=(h,)), jnp.float32)
    want_y, want_s = granite_hybrid.recurrence(x, b, c, dt, a, d)
    y, state = chunk_scan(x.reshape(1, n, h * p), b[None], c[None], dt[None],
                          a, d, jnp.zeros((1, h, p, s)), chunk=8)
    np.testing.assert_allclose(y[0].reshape(n, h, p), want_y, atol=ATOL)
    np.testing.assert_allclose(state[0], want_s, atol=ATOL)
    # the second half from the state the first half left
    half = n // 2
    _, first = chunk_scan(x[None, :half].reshape(1, half, -1), b[None, :half],
                          c[None, :half], dt[None, :half], a, d,
                          jnp.zeros((1, h, p, s)), chunk=8)
    y2, second = chunk_scan(x[None, half:].reshape(1, n - half, -1),
                            b[None, half:], c[None, half:], dt[None, half:],
                            a, d, first, chunk=8)
    np.testing.assert_allclose(y2[0].reshape(-1, h, p), want_y[half:],
                               atol=ATOL)
    np.testing.assert_allclose(second[0], want_s, atol=ATOL)


@pytest.mark.parametrize("batch", [1, 3])
@pytest.mark.parametrize("use_pallas", [False, True])
def test_prefill_then_decode_through_both_kinds_of_cache(tiny, use_pallas,
                                                         batch):
    """``prefill`` (13 positions: no multiple of the chunk) and then
    ``decode_step`` through the attention layer's rows and the mixers'
    states equal the reference's full forward at every position."""
    _, params, tokens = tiny
    model = build(use_pallas=use_pallas)
    tokens = tokens[:batch]
    n = 13
    cache = model.apply({}, batch, 32, method=RingTransformer.init_cache)
    logits, cache = model.apply(params, tokens[:, :n], cache,
                                method=RingTransformer.prefill)
    got = [logits]
    for i in range(n, tokens.shape[1]):
        logits, cache = model.apply(params, tokens[:, i], cache, jnp.int32(i),
                                    method=RingTransformer.decode_step)
        got.append(logits)
    got = jnp.stack(got, 1)
    for row in range(batch):
        want = granite_hybrid.logits(params, tokens[row], TINY)[n - 1:]
        np.testing.assert_allclose(got[row], want, atol=ATOL)


def test_generate_walks_the_same_stack(tiny):
    model, params, tokens = tiny
    out = model.apply(params, tokens[:, :6], 32, 5,
                      method=RingTransformer.generate)
    assert out.shape == (3, 5)
    seq = jnp.concatenate([tokens[:1, :6], out[:1]], axis=1)[0]
    want = granite_hybrid.logits(params, seq[:-1], TINY)
    np.testing.assert_array_equal(out[0], jnp.argmax(want[5:], -1))


def test_the_cache_holds_the_references_state_and_tail(tiny):
    """After the prompt the mixers' entries are the reference's convolution
    tail and state at the prompt's end, the attention layer's its k and v
    rows; a decode step continues all of them."""
    model, params, tokens = tiny
    n = 13
    cache = model.apply({}, 1, 32, method=RingTransformer.init_cache)
    _, cache = model.apply(params, tokens[:1, :n], cache,
                           method=RingTransformer.prefill)

    def agrees(cache, upto):
        _, inside = granite_hybrid.forward(params, tokens[0, :upto], TINY)
        rows = serve_hybrid._cache_rows(cache, np.arange(upto))
        for i, kind in enumerate(TINY["layer_types"]):
            for mine, its in zip(rows[i], inside["kv"][i]):
                assert mine.shape == its.shape
                np.testing.assert_allclose(mine, its, atol=ATOL)
            assert (rows[i][1].shape == (8, 8, 16)) is (kind == "mamba")

    agrees(cache, n)
    _, cache = model.apply(params, tokens[:1, n], cache, jnp.int32(n),
                           method=RingTransformer.decode_step)
    agrees(cache, n + 1)
    # a short prompt's tail keeps the zeros before position 0
    cache = model.apply({}, 1, 32, method=RingTransformer.init_cache)
    _, cache = model.apply(params, tokens[:1, :2], cache,
                           method=RingTransformer.prefill)
    assert not np.asarray(cache["k"][0][0, 0, 0]).any()
    agrees(cache, 2)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_init_cache_bytes_of_a_state_do_not_grow_with_the_capacity(dtype):
    """At the published widths, by ``jax.eval_shape``: a Mamba-2 layer's
    entry is three rows of the convolution's 8,448 channels and one float32
    state of 128 x 64 x 128 a session, whatever ``max_len`` is; the
    attention layer's is rows."""
    cfg = ModelConfig.from_file(os.path.join(
        REPO, "benchmarks", "configs", "granite-4.0-h-small.json"))
    model = RingTransformer.from_config(cfg, mesh=None, use_ring=False,
                                        dtype=dtype)
    itemsize = jnp.dtype(dtype).itemsize
    sessions = 4

    def bytes_by_layer(max_len):
        cache = jax.eval_shape(lambda: model.apply(
            {}, sessions, max_len, method=RingTransformer.init_cache))
        assert all(v.dtype == jnp.float32 and k.ndim == v.ndim == 4
                   for k, v, layer in zip(cache["k"], cache["v"], cfg.layers)
                   if layer.mixer == "mamba")
        return [sum(math.prod(a.shape) * a.dtype.itemsize for a in pair)
                for pair in zip(cache["k"], cache["v"])]

    short, long = bytes_by_layer(4096), bytes_by_layer(131072)
    state = sessions * (3 * 8448 * itemsize + 128 * 64 * 128 * 4)
    for layer, a, b in zip(cfg.layers, short, long):
        if layer.mixer == "mamba":
            assert a == b == state
        else:
            assert (a, b) == tuple(
                sessions * n * 2 * 8 * 128 * itemsize for n in (4096, 131072))


@pytest.mark.parametrize("s, h, p, n, block, dtype", [
    (2, 8, 16, 128, 4, jnp.float32),
    (1, 4, 8, 16, None, jnp.float32),
    (3, 6, 8, 128, 4, jnp.bfloat16),  # 6 heads: the block halves to 2
])
def test_ssm_decode_kernel_against_its_xla_form(s, h, p, n, block, dtype):
    rng = np.random.default_rng(0)
    state = jnp.asarray(rng.normal(size=(s, h, p, n)), jnp.float32)
    x = jnp.asarray(rng.normal(size=(s, h, p)), dtype)
    b, c = (jnp.asarray(rng.normal(size=(s, n)), dtype) for _ in "bc")
    dt = jnp.asarray(rng.uniform(0.001, 0.1, size=(s, h)), jnp.float32)
    a = -jnp.asarray(rng.uniform(1, 16, size=(h,)), jnp.float32)
    d = jnp.asarray(rng.normal(size=(h,)), jnp.float32)
    want_y, want_state = ssm_step(state, x, b, c, dt, a, d)
    y, new = pallas_ssm_decode_step(state, x, b, c, dt, a, d,
                                    block_heads=block, interpret=True)
    assert y.dtype == new.dtype == jnp.float32
    np.testing.assert_allclose(y, want_y, atol=1e-5)
    np.testing.assert_allclose(new, want_state, atol=1e-6)
    # and the XLA form is the reference's recurrence, one position of it
    ref_y, ref_state = granite_hybrid.recurrence(
        *(v[0, None].astype(jnp.float32) for v in (x, b, c, dt)), a, d)
    assert state[0].any()  # from a state that is not zero: add what it gives
    carried = jnp.exp(dt[0] * a)[:, None, None] * state[0]
    np.testing.assert_allclose(want_state[0], ref_state + carried, atol=1e-5)
    np.testing.assert_allclose(
        want_y[0], ref_y[0] + carried @ c[0].astype(jnp.float32), atol=1e-4)


@pytest.mark.parametrize("step", [ssm_step, pallas_ssm_decode_step])
def test_ssm_decode_step_refuses_a_state_that_is_not_float32(step):
    args = (jnp.zeros((1, 4, 8)), jnp.zeros((1, 16)), jnp.zeros((1, 16)),
            jnp.zeros((1, 4)), jnp.zeros((4,)), jnp.zeros((4,)))
    with pytest.raises(ValueError, match="the state is float32"):
        step(jnp.zeros((1, 4, 8, 16), jnp.bfloat16), *args)
    with pytest.raises(ValueError, match="expected a state"):
        step(jnp.zeros((1, 4, 8, 16)), jnp.zeros((1, 8, 4)), *args[1:])


def test_causal_conv_keeps_the_last_rows():
    rng = np.random.default_rng(0)
    u = jnp.asarray(rng.normal(size=(2, 9, 6)), jnp.float32)
    kernel = jnp.asarray(rng.normal(size=(4, 6)), jnp.float32)
    bias = jnp.asarray(rng.normal(size=(6,)), jnp.float32)
    whole, tail = causal_conv(u, jnp.zeros((2, 3, 6)), kernel, bias)
    np.testing.assert_array_equal(tail, u[:, -3:])
    # in two parts, the second behind the first one's tail
    first, t = causal_conv(u[:, :5], jnp.zeros((2, 3, 6)), kernel, bias)
    second, t = causal_conv(u[:, 5:], t, kernel, bias)
    np.testing.assert_allclose(jnp.concatenate([first, second], 1), whole,
                               atol=1e-6)
    np.testing.assert_array_equal(t, tail)


# ----------------------------------------------------------------------
# the router, and the share of the deployment
# ----------------------------------------------------------------------

def routed_layer(held, first, experts=8, k=3, **kw):
    return RoutedFeedForward(
        dim=32, expert_dim=16, num_experts=experts, experts_per_token=k,
        experts_held=held, first_expert=first, shared_dim=24, norm_eps=1e-5,
        router_score="softmax", **kw)


@pytest.fixture(scope="module")
def uncut():
    """One routed layer holding all 8 experts, its parameters, tokens, and
    the configuration the reference reads."""
    x = jnp.asarray(np.random.default_rng(1).normal(size=(2, 9, 32)),
                    jnp.float32)
    layer = routed_layer(held=8, first=0)
    params = layer.init(jax.random.PRNGKey(3), x)
    config = {"num_experts_per_tok": 3, "num_local_experts": 8,
              "first_expert": 0}
    return layer, params, x, config


def reference_layer(params, x, config):
    p = params["params"]
    m = granite_hybrid._rmsnorm(x.reshape(-1, 32), p["norm"]["gamma"], 1e-5)
    with jax.default_matmul_precision("highest"):
        return granite_hybrid._routed(m, p, config)


def test_a_softmax_router_has_no_bias_and_softmax_weights(uncut):
    """No ``expert_bias`` parameter; the weights are the softmax over the
    chosen logits: an expert's output enters with ``exp(r_e) / sum over the
    chosen``, which the reference computes from the logits alone."""
    layer, params, x, config = uncut
    assert "expert_bias" not in params["params"]
    assert "expert_bias" in routed_layer(8, 0).clone(
        router_score="sigmoid").init(jax.random.PRNGKey(3), x)["params"]
    want, chose, _ = reference_layer(params, x, config)
    got = layer.apply(params, x).reshape(-1, 32)
    np.testing.assert_allclose(got, want, atol=ATOL)
    assert (chose.sum(-1) == 3).all()
    # with every expert the identity's multiple the weights show: experts
    # whose down-projection is zero but one leave that one's weight
    p = params["params"]
    m = granite_hybrid._rmsnorm(x.reshape(-1, 32), p["norm"]["gamma"], 1e-5)
    logits = m @ p["router"]
    top, _ = jax.lax.top_k(logits, 3)
    weights = jnp.exp(top) / jnp.exp(top).sum(-1, keepdims=True)
    np.testing.assert_allclose(weights, jax.nn.softmax(top, -1), atol=1e-6)
    np.testing.assert_allclose(weights.sum(-1), 1.0, atol=1e-6)


def test_two_shares_and_the_shared_expert_once_make_the_layer(uncut):
    """The share test of the model-configs guide: the two holders' routed
    parts plus the shared expert, counted once, add up to the uncut
    reference's layer."""
    layer, params, x, config = uncut
    want, _, _ = reference_layer(params, x, config)
    p = params["params"]
    routed = 0.0
    for first in (0, 4):
        share = routed_layer(held=4, first=first)
        mine = {"params": {
            **p, "experts_gate_up": p["experts_gate_up"][first:first + 4],
            "experts_down": p["experts_down"][first:first + 4]}}
        # each holder's output holds the shared expert; take it off
        zero = jax.tree.map(jnp.zeros_like, p["shared"])
        with_shared = share.apply(mine, x)
        alone = share.apply({"params": {**mine["params"], "shared": zero}}, x)
        shared = with_shared - alone
        routed = routed + alone
    np.testing.assert_allclose((routed + shared).reshape(-1, 32), want,
                               atol=ATOL)
    # and the reference, given a share, computes that share
    part, _, _ = reference_layer(mine, x, {**config, "num_local_experts": 4,
                                           "first_expert": 4})
    np.testing.assert_allclose(with_shared.reshape(-1, 32), part, atol=ATOL)


def test_a_holder_with_many_pairs_takes_its_tokens_in_blocks(uncut,
                                                             monkeypatch):
    """Where a holder expects more pairs than a pass takes, the routed part
    runs a block of tokens at a time, each block one pass: the same sum and
    the same counters.  The accepted cells' holders stay in one block."""
    from ring_attention_tpu.models import moe

    layer, params, x, _ = uncut
    want, sown = layer.apply(params, x, mutable=["counters"])
    assert layer._blocks(18) == 1
    assert routed_layer(36, 0, experts=72, k=10)._blocks(32768) == 8
    assert routed_layer(36, 0, experts=72, k=10)._blocks(3072) == 1
    assert routed_layer(36, 0, experts=72, k=10)._blocks(4) == 1
    assert routed_layer(32, 0, experts=256, k=4)._blocks(32768) == 1
    assert routed_layer(16, 0, experts=256, k=8)._blocks(16384) == 1
    monkeypatch.setattr(moe, "PASS_ROWS", 8)  # 18 tokens x 3: 9, 9 and ...
    assert layer._blocks(18) == 2  # ... 18 is no multiple of four
    got, counted = layer.apply(params, x, mutable=["counters"])
    np.testing.assert_allclose(got, want, atol=1e-6)
    for name, value in sown["counters"].items():
        np.testing.assert_array_equal(counted["counters"][name], value)


# ----------------------------------------------------------------------
# the multipliers, the tied head, the softmax scale
# ----------------------------------------------------------------------

NEUTRAL = dict(embedding_multiplier=1, residual_multiplier=1.0,
               attention_multiplier=8 ** -0.5, logits_scaling=1,
               tie_word_embeddings=False)


@pytest.mark.parametrize("key, value", [
    (None, None), ("embedding_multiplier", 12), ("residual_multiplier", 0.22),
    ("attention_multiplier", 0.05), ("logits_scaling", 16),
    ("tie_word_embeddings", True)])
def test_each_multiplier_alone_matches_the_reference(key, value):
    """Each of the four multipliers, and the tied head, with the others at
    their neutral values: the program equals the reference, and differs from
    the neutral program."""
    tokens = jnp.asarray(
        np.random.default_rng(2).integers(0, VOCAB, (1, 12)), jnp.int32)
    neutral = {**TINY, **NEUTRAL}
    config = neutral if key is None else {**neutral, key: value}
    model = build(config)
    params = model.init(jax.random.PRNGKey(0), tokens)
    assert ("to_logits" in params["params"]) is (
        not config["tie_word_embeddings"])
    got = model.apply(params, tokens)[0]
    np.testing.assert_allclose(
        got, granite_hybrid.logits(params, tokens[0], config), atol=ATOL)
    if key is not None and key != "tie_word_embeddings":
        base = build(neutral).apply(params, tokens)[0]
        assert float(jnp.abs(got - base).max()) > 1e-3


@pytest.mark.parametrize("use_pallas", [False, True])
def test_softmax_scale_reaches_every_path(use_pallas):
    """``attention_multiplier`` is not ``head_dim ** -0.5``: folded into the
    queries it reaches the forward, the prefill and the decode step, on the
    XLA and the Pallas paths, and matches the reference."""
    config = {**TINY, **NEUTRAL, "layer_types": ["attention"] * 3,
              "attention_multiplier": 1 / 128}
    tokens = jnp.asarray(
        np.random.default_rng(3).integers(0, VOCAB, (2, 16)), jnp.int32)
    model = build(config, use_pallas=use_pallas)
    params = model.init(jax.random.PRNGKey(0), tokens)
    want = jnp.stack([granite_hybrid.logits(params, row, config)
                      for row in tokens])
    np.testing.assert_allclose(model.apply(params, tokens), want, atol=ATOL)
    default = build({**config, "attention_multiplier": 8 ** -0.5})
    assert float(jnp.abs(default.apply(params, tokens) - want).max()) > 1e-3
    cache = model.apply({}, 2, 32, method=RingTransformer.init_cache)
    logits, cache = model.apply(params, tokens[:, :12], cache,
                                method=RingTransformer.prefill)
    np.testing.assert_allclose(logits, want[:, 11], atol=ATOL)
    logits, cache = model.apply(params, tokens[:, 12], cache, jnp.int32(12),
                                method=RingTransformer.decode_step)
    np.testing.assert_allclose(logits, want[:, 12], atol=ATOL)


# ----------------------------------------------------------------------
# counters, errors
# ----------------------------------------------------------------------

def test_counters_of_the_state_space_layers(tiny):
    model, params, tokens = tiny
    cache = model.apply({}, 3, 32, method=RingTransformer.init_cache)
    (_, cache), sown = model.apply(
        params, tokens[:, :13], cache, method=RingTransformer.prefill,
        mutable=["counters"])
    mixers = [sown["counters"][f"attn_layers_{i}"] for i in (0, 2)]
    assert "attn_layers_1" not in sown["counters"]
    for layer in mixers:  # 13 positions: two chunks of 8 a session
        assert int(layer["ssm_chunks"]) == 3 * 2
        assert int(layer["ssm_padded_positions"]) == 3 * 3
    _, sown = model.apply(
        params, tokens[:, 13], cache, jnp.int32(13),
        method=RingTransformer.decode_step, mutable=["counters"])
    for i in (0, 2):  # read and written: twice the states' bytes
        assert int(sown["counters"][f"attn_layers_{i}"]["ssm_state_bytes"]
                   ) == 2 * 3 * 8 * 8 * 16 * 4
    assert "tokens_per_expert" in sown["counters"]["ff_layers_0"]


def test_a_state_space_layer_on_a_sequence_mesh_names_the_roadmap(tiny,
                                                                  devices):
    _, params, tokens = tiny
    mesh = create_mesh(ring_size=4, data_size=1, devices=devices[:4])
    model = build(mesh=mesh, use_ring=True)
    with pytest.raises(NotImplementedError, match="ROADMAP R7"):
        model.apply(params, tokens[:, :16])
    with pytest.raises(NotImplementedError, match="ROADMAP R7"):
        model.apply({}, 1, 32, method=RingTransformer.init_cache)
    mixer = Mamba2Mixer(dim=32, heads=8, head_dim=8, state=16, chunk=8,
                        mesh=mesh)
    x = jnp.zeros((1, 16, 32))
    mine = {"params": params["params"]["attn_layers_0"]}
    tail, state = jnp.zeros((1, 1, 3, 96)), jnp.zeros((1, 8, 8, 16))
    for method, args in (("prefill", (x, tail, state)),
                         ("decode_step", (x[:, :1], tail, state, 0))):
        with pytest.raises(NotImplementedError, match="ROADMAP R7"):
            mixer.apply(mine, *args, method=getattr(Mamba2Mixer, method))
    # off the ring the same mesh holds only data parallelism: it runs
    build(mesh=mesh, use_ring=False).apply(params, tokens[:, :16])


@pytest.mark.parametrize("edit, words", [
    (dict(ssm_state=0), "needs all of ssm_heads"),
    (dict(ssm_chunk=0, ssm_conv=0), "needs all of ssm_heads"),
    (dict(ssm_groups=2), "ssm_groups = 1"),
    (dict(router_score="tanh"), "score functions are sigmoid, softmax"),
])
def test_a_half_given_state_space_configuration_is_a_one_line_error(edit,
                                                                    words):
    import dataclasses

    cfg = ModelConfig.from_dict(TINY)
    with pytest.raises(ValueError, match=words) as e:
        dataclasses.replace(cfg, **edit)
    assert "\n" not in str(e.value)


@pytest.mark.parametrize("edit, words", [
    (dict(layer_types=["mamba", "attention"]), "layer_types"),
    (dict(layer_types=["mamba", "linear", "mamba"]), "layer_types"),
    (dict(position_embedding_type="rope"), "nope"),
    (dict(mamba_expand=3), "mamba_expand x hidden_size"),
    (dict(model_type="granitemoe"), "no translation for the family"),
])
def test_a_bad_hybrid_file_is_a_one_line_error(edit, words):
    with pytest.raises(ValueError, match=words) as e:
        ModelConfig.from_dict({**TINY, **edit})
    assert "\n" not in str(e.value)


def test_a_mixer_takes_no_mask_and_no_packed_documents(tiny):
    model, params, tokens = tiny
    with pytest.raises(NotImplementedError, match="ROADMAP R7"):
        model.apply(params, tokens, segment_ids=jnp.zeros_like(tokens))
    with pytest.raises(NotImplementedError, match="loss_chunk_size=None"):
        build(loss_chunk_size=8).apply(params, tokens, return_loss=True)
    assert np.isfinite(model.apply(params, tokens, return_loss=True))


# sha256 (first 16 hex digits) of ``jax.jit(call).lower(...).as_text()`` of
# the dots toy (tests/test_mla.py TINY) at the parent commit of PR 34
# (4d43669), jax 0.9.0, under this suite's conftest; the afmoe and starcoder2
# toys' are in tests/test_mla.py and did not change either.  Re-recorded on
# purpose in PR 35, all four: the routed layer's combine lost its mask over
# the products' output and the layer sows ``combine_rows_copied``
# (tests/test_mla.py's ``afmoe.*`` with them; its ``starcoder2.*`` stay).
# Re-recorded on purpose in PR 38, all four: the rotation is a product by a
# signed permutation over the whole head, and the split and concatenation
# around the rotary columns are gone (tests/test_mla.py's eight with them).
# The four ``granite.*`` (this file's TINY) were recorded at PR 38's parent
# (7d3bfd5) and did not move: granite's attention layer has no positional
# encoding, so the rotary change does not reach its programs.
# Re-recorded on purpose, ``dots_vlm.prefill`` and ``granite.prefill``: the
# prefill takes the last position before the head in every family, from the
# stream's rows with positions leading (granite's tied head took a slice of
# the (b, n, d) stream before).
PARENT_TEXT = {
    "dots_vlm.forward": "fd21250b5e54d705",
    "dots_vlm.loss": "dd5ac33d57d54db4",
    "dots_vlm.prefill": "d0aa059dafa3892d",
    "dots_vlm.decode_step": "1be70d2490bb56ae",
    "granite.forward": "5617b19ff4357215",
    "granite.loss": "55bb1d7ce28f7ebe",
    "granite.prefill": "cf2931b137369ee6",
    "granite.decode_step": "392ea7921a601472",
}


@pytest.mark.parametrize("name", sorted(PARENT_TEXT))
def test_the_latent_family_lowers_to_the_parents_text(name):
    """The mixer's kind, the multipliers, the softmax scale, the tied head
    and the softmax router are taken only by a configuration that asks for
    them: the dots toy's programs lower to the text they lowered to before
    this family was added, the granite toy's to its parent's of PR 38
    (Trinity's and StarCoder2's:
    ``tests/test_mla.py::test_the_other_families_lower_to_the_parents_text``)."""
    family, call = name.split(".")
    model = build({"dots_vlm": DOTS_TOY, "granite": TINY}[family])
    tokens = jnp.zeros((2, 20), jnp.int32)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0), tokens)
    cache = jax.eval_shape(
        lambda: model.apply({}, 2, 32, method=RingTransformer.init_cache))
    fn, args = {
        "forward": (lambda p, t: model.apply(p, t), (params, tokens)),
        "loss": (lambda p, t: model.apply(p, t, return_loss=True),
                 (params, tokens)),
        "prefill": (lambda p, t, c: model.apply(
            p, t, c, method=RingTransformer.prefill),
            (params, tokens[:, :14], cache)),
        "decode_step": (lambda p, t, c, i: model.apply(
            p, t, c, i, method=RingTransformer.decode_step,
            mutable=["counters"]),
            (params, tokens[:, 0], cache, jnp.int32(14))),
    }[call]
    text = jax.jit(fn).lower(*args).as_text()
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == PARENT_TEXT[name]


# ----------------------------------------------------------------------
# the benchmark's side: the cell's configuration file, the check, the driver
# ----------------------------------------------------------------------

LIMITS = {"logits_rel_l2": 1e-2, "attn_rel_l2": [1e-2, 1e-2, 3e-2],
          "cache_rel_l2": [[1e-2, 1e-2], 1e-2, 3e-2], "routing_margin": 0.05,
          "min_positions": 3, "state_bfloat16_share": 0.01}


@pytest.mark.parametrize("fault, ok, kept", [
    (None, True, 6), ("logits", False, 6), ("one_mixers_output", False, 6),
    ("one_layers_state", False, 6), ("one_layers_tail", False, 6),
    ("a_state_kept_in_bfloat16", False, 6),
    ("the_attention_layers_rows", False, 6), ("no_probes", False, 6),
    ("last_layer_within_its_own_limit", True, 6),
    ("a_prompt_position_near_a_tie", True, 5),
    ("routed_differently_under_the_margin", True, 5),
    ("a_state_above_a_turned_position_is_left_out", True, 5),
    ("and_a_state_under_it_is_compared", False, 5),
    ("routed_differently_beyond_the_margin", False, 5),
    ("too_few_positions", False, 2)])
def test_verdict_holds_each_limit(fault, ok, kept):
    """``correct`` needs the logits, every layer's mixer output within that
    layer's limit, what every layer's cache holds and enough positions, each
    against one fault at a time.  Rows are compared where the routed layers
    below agree; a state and a tail whole."""
    rng = np.random.default_rng(0)
    logits = jnp.asarray(rng.normal(size=(6, 16)), jnp.float32)
    attn = jnp.asarray(rng.normal(size=(3, 6, 8)), jnp.float32)

    def normal(*shape):
        return jnp.asarray(rng.normal(size=shape), jnp.float32)

    kv = [(normal(3, 12), normal(4, 8, 16)),  # mamba: tail, state
          (normal(2, 6, 8), normal(2, 6, 8)),  # attention: k and v rows
          (normal(3, 12), normal(4, 8, 16))]
    chose = rng.random(size=(3, 6, 4)) < 0.3
    margins = np.full((3, 6), 1.0)
    inside = {"margins": margins, "margin": margins.min(0), "attn": attn,
              "kv": kv, "chose": chose, "routed_layers": [0, 1, 2],
              "mamba_layers": [0, 2]}
    routing = {"chose": chose[:, 2:].copy()}  # the last four are known
    got = {"logits": logits, "attn": attn, "kv": list(kv), "routing": routing}
    if fault == "logits":
        got["logits"] = logits * 1.02
    if fault == "one_mixers_output":
        got["attn"] = attn.at[0].multiply(1.02)
    if fault == "last_layer_within_its_own_limit":
        got["attn"] = attn.at[2].multiply(1.02)
        got["kv"][2] = (kv[2][0], kv[2][1] * 1.02)
    if fault == "one_layers_state":
        got["kv"][0] = (kv[0][0], kv[0][1] * 1.02)
    if fault == "a_state_kept_in_bfloat16":  # inside its limit, and caught
        narrow = kv[2][1].astype(jnp.bfloat16).astype(jnp.float32)
        assert float(granite_hybrid.rel_l2(narrow, kv[2][1])) < 3e-3
        got["kv"][2] = (kv[2][0], narrow)
    if fault == "one_layers_tail":
        got["kv"][0] = (kv[0][0] * 1.02, kv[0][1])
    if fault == "the_attention_layers_rows":
        got["kv"][1] = (kv[1][0], kv[1][1] * 1.02)
    if fault == "no_probes":
        got["attn"] = None
    if fault == "a_prompt_position_near_a_tie":
        margins[0, 0] = 0.04  # and wrong above it, which is not compared
        got = {**got, "logits": logits.at[0].set(0.0),
               "attn": attn.at[1:, 0].set(0.0)}
        got["kv"][1] = (kv[1][0].at[:, 0].set(0.0), kv[1][1])
    if fault in ("routed_differently_under_the_margin",
                 "a_state_above_a_turned_position_is_left_out",
                 "and_a_state_under_it_is_compared"):
        margins[0, 5] = 0.01
        routing["chose"][0, -1] = ~routing["chose"][0, -1]
        got = {**got, "logits": logits.at[-1].set(0.0),
               "attn": attn.at[1:, -1].set(0.0)}
        if fault.startswith("a_state"):  # layer 2: over the turned router
            got["kv"][2] = (kv[2][0] * 1.5, kv[2][1] * 1.5)
        if fault.startswith("and_a_state"):  # layer 0: under every router
            got["kv"][0] = (kv[0][0], kv[0][1] * 1.05)
    if fault == "routed_differently_beyond_the_margin":
        routing["chose"][1, -2] = ~routing["chose"][1, -2]
    if fault == "too_few_positions":
        margins[0, :4] = 0.01
        routing["chose"][0, :2] = ~routing["chose"][0, :2]
    out = granite_hybrid.verdict(got, logits, inside, LIMITS)
    assert out["ok"] is ok
    assert out["positions_compared"] == kept
    assert out["routed_differently_beyond_margin"] is (
        fault == "routed_differently_beyond_the_margin")
    assert out["positions_compared_by_layer"][0] == 6
    # the last layer's state is compared where all six positions are, its
    # tail where the last three are
    tail, state = out["cache_rel_l2_pairs_by_layer"][2]
    assert (state is None) is (kept < 6)
    assert (tail is None) is (fault is not None and (
        any(w in fault for w in ("routed", "turned", "under_it", "few"))))
    assert out["state_bfloat16_share"] == (
        1.0 if fault == "a_state_kept_in_bfloat16" else 0.0)


def test_margin_is_the_least_distance_of_a_held_expert_from_the_edge():
    logits = jnp.asarray([[3.0, 2.0, 1.0, 0.9, 0.2, -1.0]])
    # top-3 of six: the edge lies between 1.0 (in) and 0.9 (out)
    assert float(granite_hybrid.margin_of(logits, 3, 0, 6)[0]) == pytest.approx(0.1)
    # holding only experts 0 and 5: 0 leaves at 0.9, 5 enters at 1.0
    assert float(granite_hybrid.margin_of(
        logits[:, [0, 5, 1, 2, 3, 4]], 3, 0, 2)[0]) == pytest.approx(2.0)
    assert float(granite_hybrid.margin_of(logits, 3, 3, 2)[0]
                 ) == pytest.approx(0.1)  # expert 3 enters at 1.0


def test_the_cells_configuration_file():
    """It builds, states every published number beside its cut, and holds
    the 4,757,211,776 parameters ISSUE 34 reckoned (8.86 GiB in bfloat16):
    a mixer 102,286,976 + its norm, the attention layer 41,943,040 + its
    norm, a routed layer 358,907,904 + its norm, the tied matrix once."""
    path = os.path.join(REPO, "benchmarks", "configs",
                        "granite-4.0-h-small.json")
    cfg = ModelConfig.from_file(path)
    assert (cfg.dim, cfg.heads, cfg.kv_heads, cfg.dim_head) == (
        4096, 32, 8, 128)
    assert (cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state, cfg.ssm_groups,
            cfg.ssm_conv, cfg.ssm_chunk) == (128, 64, 128, 1, 4, 256)
    assert [layer.mixer for layer in cfg.layers] == (
        ["mamba"] * 5 + ["attention"] + ["mamba"] * 4)
    assert all((layer.window, layer.rotary, layer.ffn)
               == (None, False, "routed") for layer in cfg.layers)
    assert (cfg.num_experts, cfg.experts_held, cfg.first_expert,
            cfg.experts_per_token, cfg.expert_dim, cfg.shared_expert_dim,
            cfg.router_score, cfg.route_scale) == (
        72, 36, 0, 10, 768, 1536, "softmax", 1.0)
    assert (cfg.embed_scale, cfg.residual_scale, cfg.logit_scale,
            cfg.softmax_scale, cfg.tie_embeddings, cfg.norm_eps) == (
        12.0, 0.22, 1 / 16, 1 / 128, True, 1e-5)
    with open(path) as f:
        raw = json.load(f)
    assert {k: raw["published"][k] for k in raw["reduced"]} == {
        "num_hidden_layers": 40, "num_local_experts": 72,
        "vocab_size": 100352,
        "layer_types": ["mamba"] * 5 + (["attention"] + ["mamba"] * 9) * 3
        + ["attention"] + ["mamba"] * 4}
    assert {k: raw[k] for k in raw["reduced"]} == {
        "num_hidden_layers": 10, "num_local_experts": 36, "vocab_size": 50176,
        "layer_types": raw["published"]["layer_types"][:10]}
    cut = set(raw["reduced"])
    assert all(raw[k] == v for k, v in raw["published"].items()
               if k not in cut)
    assert raw["reference"] == "granite_hybrid"
    for key in ("limits_why", "changed", "assumed", "reduced_why",
                "deployment", "cut_effects", "weights"):
        assert raw[key], key
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        entry = next(c for c in json.load(f)["configs"]
                     if c["name"] == "granite-4.0-h-small")
    assert entry["reduced"] == raw["reduced"]
    model = RingTransformer.from_config(cfg, mesh=None, use_ring=False,
                                        dtype=jnp.bfloat16)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                            jnp.zeros((1, 8), jnp.int32))["params"]

    def count(tree):
        return sum(math.prod(a.shape) for a in jax.tree.leaves(tree))

    assert count(shapes["attn_layers_0"]) == 102_286_976 + 4096
    assert count(shapes["attn_layers_5"]) == 41_943_040 + 4096
    assert count(shapes["ff_layers_0"]) == (
        294_912 + 339_738_624 + 18_874_368 + 4096)
    assert count(shapes["embed"]) == 205_520_896
    assert "to_logits" not in shapes
    assert count(shapes) == 4_757_211_776
    cache = jax.eval_shape(lambda: model.apply(
        {}, 4, 131072, method=RingTransformer.init_cache))
    assert sum(math.prod(a.shape) * a.dtype.itemsize
               for a in jax.tree.leaves(cache)) == (
        4 * 131072 * 4096 + 4 * 9 * (4 * 2**20 + 50_688))


def test_the_driver_draws_the_mixers_vectors_by_the_reference_rule():
    """``serve_hybrid._weights``: ``serve_sessions``'s rule for every leaf
    it knows, the Mamba-2 initialisation for the rest."""
    model = build(dtype=jnp.bfloat16)
    params = serve_hybrid._weights(model, jax.random.PRNGKey(5), {})["params"]
    want = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                          jnp.zeros((1, 8), jnp.int32))["params"]
    assert jax.tree.map(lambda a: a.shape, params) == jax.tree.map(
        lambda a: a.shape, want)
    mixer = params["attn_layers_0"]
    a = np.exp(np.asarray(mixer["A_log"]))
    assert mixer["A_log"].dtype == jnp.float32 and (
        (a >= 1) & (a <= 16)).all()
    dt = np.log1p(np.exp(np.asarray(mixer["dt_bias"])))
    assert ((dt >= 0.001 * 0.999) & (dt <= 0.1 * 1.001)).all()
    assert (np.asarray(mixer["D"]) == 1).all()
    taps = np.asarray(mixer["conv_kernel"], np.float32)
    assert mixer["conv_kernel"].dtype == jnp.bfloat16
    assert np.abs(taps).max() <= 0.5 and taps.std() > 0.2
    assert mixer["in_proj"].dtype == jnp.bfloat16
    assert float(jnp.std(mixer["in_proj"].astype(jnp.float32))
                 ) == pytest.approx(32 ** -0.5, rel=0.1)
    assert (np.asarray(mixer["gate_norm"]["gamma"], np.float32) == 1).all()
    # layers differ, and the same key draws the same values
    assert not np.array_equal(mixer["A_log"],
                              params["attn_layers_2"]["A_log"])
    again = serve_hybrid._weights(model, jax.random.PRNGKey(5), {})["params"]
    np.testing.assert_array_equal(again["attn_layers_0"]["dt_bias"],
                                  mixer["dt_bias"])


TOY = os.path.join(REPO, "benchmarks", "tests", "toy_hybrid")


@pytest.mark.parametrize("flags", [(), ("--use-pallas", "--bf16")])
def test_generate_example_builds_a_hybrid_model_from_a_file(flags):
    """``examples/generate.py --config FILE`` is the entry point's way to a
    hybrid model: prefill, then decode through both kinds of cache."""
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "examples", "generate.py"),
         "--config", os.path.join(TOY, "configs", "toy_hybrid.json"),
         "--steps", "5", "--prompt-len", "16", "--max-len", "64",
         "--devices", "1", *flags],
        cwd=REPO, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "generated 5 tokens" in proc.stdout, proc.stdout[-1500:]


def test_rehearsal_of_the_hybrid_driver():
    """``run.py --rehearse`` drives ``kinds/serve_hybrid.py`` end to end on
    the CPU against a toy manifest of its own: two Mamba-2 layers around an
    attention layer."""
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmarks", "run.py"),
         "--manifest", os.path.join(TOY, "BENCHMARK.json"),
         "--rehearse", "--workload", "toy_hybrid.serve", "--seed",
         str(2**31 + 5), "--seconds", "1", "--trace", "0"],
        cwd=REPO, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = proc.stdout.strip().splitlines()
    out = json.loads(lines[-1])
    assert set(out) == {"correct", "attempted", "failed", "metrics", "device"}
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] >= 2 and out["device"]["platform"] == "cpu"
    assert set(out["metrics"]) == {"setup_s"}
    check = json.loads(next(line for line in lines
                            if line.startswith("check "))[len("check "):])
    assert check["positions"] == 9 and check["positions_compared"] >= 3
    assert check["positions_compared_by_layer"][0] == 9
    assert check["routed_differently_beyond_margin"] is False
    assert check["routing"]["pairs_on_held"] > 0
    assert len(check["cache_rel_l2_by_layer"]) == 3
    ssm = check["ssm"]
    # every byte the steps move is a byte the shapes say they move
    assert ssm["state_bytes_per_step"] == ssm["state_bytes_by_shape_per_step"]
    assert ssm["state_bytes_per_step"][0] == 2 * 2 * 8 * 64 * 128 * 4
    assert ssm["prefill_chunks"] == ssm["prefill_chunks_by_shape"] == 2 * 4
    assert (ssm["prefill_padded_positions"]
            == ssm["prefill_padded_positions_by_shape"] == 2 * 56)
