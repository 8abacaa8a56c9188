"""The dots_vlm language model (the DeepSeek-V3 block: latent attention, a
latent cache, group-limited sigmoid routing) at tiny widths on the CPU: the
program, built from a frozen ``ModelConfig`` through ``RingTransformer``'s
own constructor, against the plain reference the benchmark keeps
(``benchmarks/references/dots_vlm.py``), with seeded weights and a seeded
non-zero expert bias."""

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
    LatentAttention,
    ModelConfig,
    RingTransformer,
    RoutedFeedForward,
)
from ring_attention_tpu.ops.pallas_latent import (
    latent_decode_attention,
    pallas_flash_decode_latent,
)
from ring_attention_tpu.ops.rotary import YarnScaling, rotary_freqs
from ring_attention_tpu.parallel import create_mesh

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
from benchmarks.references import afmoe, dots_vlm  # noqa: E402

VOCAB = 96
YARN = {"beta_fast": 32, "beta_slow": 1, "factor": 40, "mscale": 1,
        "mscale_all_dim": 1, "original_max_position_embeddings": 8,
        "type": "yarn"}
TINY = dict(
    model_type="dots_vlm", vocab_size=VOCAB, hidden_size=32,
    num_attention_heads=4, num_key_value_heads=4, intermediate_size=64,
    moe_intermediate_size=16, num_hidden_layers=3, first_k_dense_replace=1,
    moe_layer_freq=1, q_lora_rank=24, kv_lora_rank=16, qk_nope_head_dim=8,
    qk_rope_head_dim=4, v_head_dim=8, rms_norm_eps=1e-6, rope_theta=10000,
    rope_scaling=YARN, n_routed_experts=4, published={"n_routed_experts": 16},
    first_expert=4, num_experts_per_tok=4, n_shared_experts=1, n_group=4,
    topk_group=2, routed_scaling_factor=2.5, norm_topk_prob=True,
    scoring_func="sigmoid", topk_method="noaux_tc")
ATOL = 2e-5
LATENT, ROPE = 16, 4


def build(config=TINY, **options):
    options = {"mesh": None, "use_ring": False, "bucket_size": 4, **options}
    return RingTransformer.from_config(ModelConfig.from_dict(config), **options)


def seeded(model, tokens, bias_std=0.3):
    params = model.init(jax.random.PRNGKey(0), tokens)

    def bias(path, leaf):
        if "expert_bias" not in jax.tree_util.keystr(path):
            return leaf
        return bias_std * jax.random.normal(jax.random.PRNGKey(7), leaf.shape)

    return jax.tree_util.tree_map_with_path(bias, params)


@pytest.fixture(scope="module")
def tiny():
    model = build()
    tokens = jnp.asarray(
        np.random.default_rng(0).integers(0, VOCAB, (3, 20)), jnp.int32)
    return model, seeded(model, tokens), tokens


@pytest.mark.parametrize("row", [0, 1, 2])
def test_forward_matches_reference(tiny, row):
    model, params, tokens = tiny
    got = model.apply(params, tokens)[row]
    want, inside = dots_vlm.forward(params, tokens[row], TINY)
    np.testing.assert_allclose(got, want, atol=ATOL)
    # the held quarter of 16 experts gets some of the pairs, not all
    assert inside["counts"].shape == (2, 4)
    assert 0 < int(inside["counts"].sum()) < 2 * 20 * 4
    assert inside["groups"].sum(-1).tolist() == [80, 80]


@pytest.mark.parametrize("batch", [1, 3])
@pytest.mark.parametrize("use_pallas", [False, True])
def test_prefill_then_decode_through_the_latent_cache(tiny, use_pallas, batch):
    """The prompt in the expanded form, then token by token in the absorbed
    form over the latent cache, against the reference's full forward."""
    _, params, tokens = tiny
    tokens = tokens[:batch]
    model = build(use_pallas=use_pallas)
    want = np.stack([dots_vlm.logits(params, row, TINY) for row in tokens])
    cache = model.apply({}, batch, 32, method=RingTransformer.init_cache)
    prompt = 14
    logits, cache = model.apply(params, tokens[:, :prompt], cache,
                                method=RingTransformer.prefill)
    np.testing.assert_allclose(logits, want[:, prompt - 1], atol=ATOL)
    step = jax.jit(lambda p, t, c, i: model.apply(
        p, t, c, i, method=RingTransformer.decode_step))
    for i in range(prompt, 20):
        logits, cache = step(params, tokens[:, i], cache, jnp.int32(i))
        np.testing.assert_allclose(logits, want[:, i], atol=ATOL, err_msg=i)


def test_generate_walks_the_same_stack(tiny):
    model, params, tokens = tiny
    out = model.apply(params, tokens[:2, :5], 32, 4,
                      method=RingTransformer.generate)
    logits = model.apply(params, tokens[:2, :5])
    assert out.shape == (2, 4)
    np.testing.assert_array_equal(out[:, 0], jnp.argmax(logits[:, -1], -1))


@pytest.fixture(scope="module")
def layer():
    """One latent attention layer with seeded weights and an input."""
    cfg = ModelConfig.from_dict(TINY)
    attn = LatentAttention(
        dim=32, heads=4, dim_head=12, kv_heads=4, causal=True, use_ring=False,
        bucket_size=4, norm_eps=1e-6, q_latent_dim=24, kv_latent_dim=LATENT,
        qk_nope_dim=8, qk_rope_dim=ROPE, v_dim=8,
        rope_scaling=cfg.rope_scaling)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 11, 32))
    return attn, attn.init(jax.random.PRNGKey(2), x), x


@pytest.mark.parametrize("use_pallas", [False, True])
def test_absorbed_equals_expanded_on_the_same_weights(layer, use_pallas):
    """``decode_step`` (W_UK folded into the query, W_UV applied to the
    attended latents) gives the rows ``__call__`` (per-head keys and
    values) gives."""
    attn, params, x = layer
    attn = attn.clone(use_pallas=use_pallas)
    want = attn.apply(params, x)
    cache_k = jnp.zeros((2, 1, ROPE, 16))
    cache_v = jnp.zeros((2, 1, 16, LATENT))
    for i in range(x.shape[1]):
        out, cache_k, cache_v = attn.apply(
            params, x[:, i:i + 1], cache_k, cache_v, jnp.int32(i),
            method=LatentAttention.decode_step)
        np.testing.assert_allclose(out[:, 0], want[:, i], atol=ATOL)


def test_the_cache_holds_the_references_latents_and_rotated_keys(tiny):
    """At every written position, by the prefill and by the decode steps
    alike: ``RMSNorm_kv(c_kv)`` and ``RoPE(k_r)`` of the reference, and
    nothing anywhere else."""
    from benchmarks.kinds.serve_latent import _cache_rows

    model, params, tokens = tiny
    cache = model.apply({}, 1, 32, method=RingTransformer.init_cache)
    _, cache = model.apply(params, tokens[:1, :14], cache,
                           method=RingTransformer.prefill)
    for i in (14, 15, 16):
        _, cache = model.apply(params, tokens[:1, i], cache, jnp.int32(i),
                               method=RingTransformer.decode_step)
    _, inside = dots_vlm.forward(params, tokens[0, :17], TINY)
    got = _cache_rows(cache, np.arange(17))
    assert len(got) == len(inside["kv"]) == 3
    for (k_r, c), (want_k_r, want_c) in zip(got, inside["kv"]):
        assert k_r.shape == (1, 17, ROPE) and c.shape == (1, 17, LATENT)
        np.testing.assert_allclose(k_r, want_k_r, atol=ATOL)
        np.testing.assert_allclose(c, want_c, atol=ATOL)
    for k, v in zip(cache["k"], cache["v"]):
        assert not np.asarray(k[..., 17:]).any()
        assert not np.asarray(v[:, :, 17:]).any()


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_init_cache_bytes_are_one_latent_row_a_position(dtype):
    """sessions x capacity x layers x (kv_lora_rank + qk_rope_head_dim) x
    itemsize: the latent and its rotated ``k_r``, stored once, and no
    expanded key or value."""
    model = build(dtype=dtype)
    cache = jax.eval_shape(
        lambda: model.apply({}, 3, 64, method=RingTransformer.init_cache))
    leaves = jax.tree.leaves(cache)
    assert sum(math.prod(a.shape) * a.dtype.itemsize for a in leaves) == (
        3 * 64 * 3 * (LATENT + ROPE) * jnp.dtype(dtype).itemsize)
    assert [a.shape for a in cache["k"]] == [(3, 1, ROPE, 64)] * 3
    assert [a.shape for a in cache["v"]] == [(3, 1, 64, LATENT)] * 3


def test_yarn_frequencies_by_hand():
    """The published keys of dots.vlm1.inst over 64 rotary dimensions:
    index 10 and below keep their frequency (they turn more than 32 times
    in 4,096 positions), index 23 and above are divided by 40, and a linear
    ramp lies between; ``m`` = 0.1 ln 40 + 1."""
    yarn = YarnScaling.from_dict({**YARN,
                                  "original_max_position_embeddings": 4096})
    low = math.floor(64 * math.log(4096 / (32 * 2 * math.pi))
                     / (2 * math.log(10000)))
    high = math.ceil(64 * math.log(4096 / (2 * math.pi))
                     / (2 * math.log(10000)))
    assert (low, high) == (10, 23)
    got = np.asarray(yarn.inv_freq(64, 10000.0), np.float64)
    plain = 10000.0 ** (-np.arange(32) / 32)
    ramp = np.clip((np.arange(32) - 10) / 13, 0, 1)
    np.testing.assert_allclose(got, plain / 40 * ramp + plain * (1 - ramp),
                               rtol=1e-6)
    np.testing.assert_allclose(got[:11], plain[:11], rtol=1e-6)
    np.testing.assert_allclose(got[23:], plain[23:] / 40, rtol=1e-6)
    assert got[16] == pytest.approx(plain[16] * (6 / 13 / 40 + 7 / 13),
                                    rel=1e-6)
    assert yarn.softmax_mscale == pytest.approx(1.36888, abs=1e-5)
    assert yarn.rotation_mscale == 1.0
    angles = rotary_freqs(jnp.arange(5), 64, 10000.0, yarn)
    np.testing.assert_allclose(angles[3, :32], 3 * got, rtol=1e-6)
    np.testing.assert_array_equal(angles[:, :32], angles[:, 32:])


def test_null_rope_scaling_is_todays_frequencies():
    assert YarnScaling.from_dict(None) is None
    pos = jnp.arange(9)
    np.testing.assert_array_equal(rotary_freqs(pos, 8, 10000.0, None),
                                  rotary_freqs(pos, 8, 10000.0))
    inv = 1.0 / (10000.0 ** (jnp.arange(0, 8, 2, dtype=jnp.float32) / 8))
    np.testing.assert_array_equal(
        rotary_freqs(pos, 8)[:, :4], pos.astype(jnp.float32)[:, None] * inv)


def routed_layer(held, first, experts=32, groups=8, keep=4, **kw):
    return RoutedFeedForward(
        dim=32, expert_dim=16, num_experts=experts, experts_per_token=8,
        experts_held=held, first_expert=first, route_scale=2.5,
        norm_eps=1e-6, expert_groups=groups, groups_per_token=keep, **kw)


GROUPED = {"num_experts_per_tok": 8, "n_group": 8, "topk_group": 4,
           "n_routed_experts": 32, "first_expert": 0, "n_shared_experts": 1,
           "routed_scaling_factor": 2.5}


@pytest.fixture(scope="module")
def uncut():
    """One routed layer holding all 32 experts (8 groups of 4, 4 kept,
    top-8), its seeded parameters, an input, and the uncut reference's
    output for it."""
    layer = routed_layer(32, 0, shared_dim=16)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 24, 32))
    p = layer.init(jax.random.PRNGKey(2), x)["params"]
    p = {**p, "expert_bias": 0.3 * jax.random.normal(jax.random.PRNGKey(3),
                                                     (32,))}
    with jax.default_matmul_precision("highest"):
        m = afmoe._rmsnorm(x.reshape(48, 32), p["norm"]["gamma"], 1e-6)
        want, chose, _, by_group = dots_vlm._routed(m, p, GROUPED)
    return layer, p, x, m, want, np.asarray(chose.sum(0)), by_group


def test_uncut_layer_matches_reference(uncut):
    layer, p, x, _, want, _, by_group = uncut
    got, col = layer.apply({"params": p}, x, mutable=["counters"])
    np.testing.assert_allclose(got.reshape(48, 32), want, atol=ATOL)
    np.testing.assert_array_equal(col["counters"]["pairs_per_group"],
                                  by_group)
    assert int(by_group.sum()) == 48 * 8


def test_sixteen_shares_and_the_shared_expert_once_make_the_layer(uncut):
    """The share test of the model-configs guide's section 4: 32 experts
    in 16 shares of 2, each share routing over all 32 (with the groups)
    and computing its own experts' part; the parts and the shared expert,
    which every holder computes alike, counted once, are the uncut
    reference's layer."""
    _, p, x, m, want, counts, _ = uncut
    total = afmoe._gated(m, p["shared"])
    pairs = 0
    for share in range(16):
        held = slice(2 * share, 2 * share + 2)
        part = {k: v for k, v in p.items() if k != "shared"}
        part["experts_gate_up"] = p["experts_gate_up"][held]
        part["experts_down"] = p["experts_down"][held]
        out, col = routed_layer(2, 2 * share).apply(
            {"params": part}, x, mutable=["counters"])
        total = total + out.reshape(48, 32)
        got = np.asarray(col["counters"]["tokens_per_expert"])
        np.testing.assert_array_equal(got, counts[held])
        pairs += got.sum()
    assert pairs == 48 * 8  # every pair the router chose, on one holder
    np.testing.assert_allclose(total, want, atol=ATOL)


def test_group_limited_choice_is_not_the_global_top_k():
    """Scores built so that the two differ: the eight largest scores lie
    one in each group, and the groups are ranked by their two best, so the
    choice is the best two of each of the four best groups."""
    scores = np.full((1, 32), 0.10, np.float32)
    scores[0, ::4] = 0.9 - 0.01 * np.arange(8)  # each group's best
    scores[0, 1::4] = 0.2 + 0.05 * np.arange(8)  # the later groups' second
    bias = jnp.zeros(32)
    chosen, _, rank = dots_vlm.choose(jnp.asarray(scores), bias, GROUPED)
    global_top = set(np.argsort(-scores[0])[:8].tolist())
    assert global_top == set(range(0, 32, 4))
    kept = np.argsort(-np.asarray(rank[0]))[:4]
    assert sorted(kept.tolist()) == [4, 5, 6, 7]
    want = {4 * g + j for g in kept for j in (0, 1)}
    assert set(np.asarray(chosen[0]).tolist()) == want != global_top
    # the program's router makes the same choice from the same scores
    layer = routed_layer(32, 0)
    eligible = layer._eligible(jnp.asarray(scores) + bias)
    assert set(np.argsort(-np.asarray(eligible[0]))[:8].tolist()) == want
    assert np.isinf(np.asarray(eligible[0, :16])).all()


def _scores(by_expert: dict) -> np.ndarray:
    scores = np.full((1, 32), 0.01, np.float32)
    for expert, score in by_expert.items():
        scores[0, expert] = score
    return scores


# 8 groups of 4, 4 kept, top-8; group 0 is held.  Groups 1 to 3 (and 4) are
# kept on their two best; ``third`` puts a third expert of groups 1 and 2 at
# the choice's edge.
_KEPT = {4: 0.9, 5: 0.85, 8: 0.85, 9: 0.8, 12: 0.8, 13: 0.75}
_EDGE = {0: 0.9, 4: 0.9, 5: 0.8, 8: 0.9, 9: 0.8, 12: 0.9, 6: 0.5, 10: 0.49}


@pytest.mark.parametrize("case,by_expert,want", [
    # the check of seed 1861123715 (PR 32): kept 1.75 1.65 1.55 1.5, dropped
    # 1.49 and then the held group at 1.48, which the program kept
    ("held_group_second_among_the_dropped",
     {**_KEPT, 16: 0.75, 17: 0.75, 20: 0.745, 21: 0.745, 0: 0.74, 1: 0.74},
     0.02),
    ("held_group_the_strongest_dropped",
     {**_KEPT, 16: 0.75, 17: 0.75, 20: 0.74, 21: 0.74, 0: 0.745, 1: 0.745},
     0.01),
    ("held_group_kept_and_another_at_the_edge",
     {**_KEPT, 0: 0.9, 1: 0.9, 20: 0.745, 21: 0.745}, 0.06),
    ("held_expert_one_inside_the_edge", {**_EDGE, 1: 0.505, 13: 0.3}, 0.015),
    ("held_expert_one_outside_the_edge", {**_EDGE, 1: 0.48, 13: 0.3}, 0.01),
    ("no_held_expert_near_the_edge", {**_EDGE, 1: 0.3, 13: 0.8}, 0.2),
])
def test_margin_is_the_distance_to_another_choice_of_held_experts(
        case, by_expert, want):
    """``margin_of`` by hand: the least any score has to move for this
    holder's experts to be chosen otherwise, whichever of them is nearest,
    not only where one stands at the edge."""
    config = {**GROUPED, "n_routed_experts": 4}
    _, (ranked, eligible), rank = dots_vlm.choose(
        jnp.asarray(_scores(by_expert)), jnp.zeros(32), config)
    got = dots_vlm.margin_of(ranked, eligible, rank, config)
    np.testing.assert_allclose(got, [want], atol=1e-6)


def test_bias_moves_the_choice_and_not_the_weights(uncut):
    _, p, x, m, _, _, _ = uncut
    held = 5
    part = {k: v for k, v in p.items() if k != "shared"}
    part["experts_gate_up"] = p["experts_gate_up"][held:held + 1]
    part["experts_down"] = p["experts_down"][held:held + 1]
    part["expert_bias"] = jnp.zeros(32).at[held].set(10.0)
    out, col = routed_layer(1, held).apply(
        {"params": part}, x, mutable=["counters"])
    # the choice: a bias of 10 puts the expert's group first and the
    # expert first in it, for every token
    assert int(col["counters"]["tokens_per_expert"][0]) == 48
    assert int(col["counters"]["pairs_per_group"][held // 4]) >= 48
    # the weights: the scores without the bias, over the eight chosen
    scores = jax.nn.sigmoid(m @ p["router"])
    chosen, _, _ = dots_vlm.choose(scores, part["expert_bias"], GROUPED)
    scores, chosen = np.asarray(scores, np.float64), np.asarray(chosen)
    assert (chosen[:, 0] == held).all()
    weight = 2.5 * scores[:, held] / np.take_along_axis(
        scores, chosen, 1).sum(1)
    h = np.asarray(m @ p["experts_gate_up"][held], np.float64)
    expert = (h[:, :16] / (1 + np.exp(-h[:, :16])) * h[:, 16:]) @ np.asarray(
        p["experts_down"][held], np.float64)
    np.testing.assert_allclose(out.reshape(48, 32), weight[:, None] * expert,
                               atol=ATOL)


def test_counters_of_the_latent_layers_and_the_groups(tiny):
    model, params, tokens = tiny
    _, col = model.apply(params, tokens[:1], mutable=["counters"])
    _, inside = dots_vlm.forward(params, tokens[0], TINY)
    layers = [col["counters"][f"ff_layers_{i}"] for i in (1, 2)]
    np.testing.assert_array_equal(
        np.stack([c["tokens_per_expert"] for c in layers]), inside["counts"])
    np.testing.assert_array_equal(
        np.stack([c["pairs_per_group"] for c in layers]), inside["groups"])
    # a decode step at position 9 of two sessions reads ten rows each
    cache = model.apply({}, 2, 32, method=RingTransformer.init_cache)
    _, col = model.apply(params, tokens[:2, 9], cache, jnp.int32(9),
                         method=RingTransformer.decode_step,
                         mutable=["counters"])
    for i in range(3):
        read = col["counters"][f"attn_layers_{i}"]["latent_cache_bytes_read"]
        assert int(read) == 2 * 10 * (LATENT + ROPE) * 4


@pytest.mark.parametrize("b, h, dl, dr, nk, block_k, dtype, tol", [
    (1, 4, 32, 16, 64, None, jnp.float32, 1e-5),
    (3, 8, 32, 16, 512, 128, jnp.float32, 1e-5),
    (2, 128, 512, 64, 1024, 256, jnp.bfloat16, 4e-3),
])
def test_latent_decode_kernel_against_its_xla_form(b, h, dl, dr, nk, block_k,
                                                   dtype, tol):
    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    scale = (dl + dr) ** -0.5
    q_lat = (scale * jax.random.normal(keys[0], (b, h, dl))).astype(dtype)
    q_rope = (scale * jax.random.normal(keys[1], (b, h, dr))).astype(dtype)
    latent = jax.random.normal(keys[2], (b, 1, nk, dl)).astype(dtype)
    rope_t = jax.random.normal(keys[3], (b, 1, dr, nk)).astype(dtype)
    mask = jnp.arange(nk)[None, :] < jnp.arange(nk - b, nk)[:, None]
    want = latent_decode_attention(q_lat, q_rope, latent, rope_t, mask)
    got = pallas_flash_decode_latent(q_lat, q_rope, latent, rope_t, mask,
                                     block_k=block_k)
    assert got.shape == (b, h, dl) and got.dtype == dtype
    np.testing.assert_allclose(got.astype(jnp.float32), want, atol=tol)
    # a masked position's row changes nothing
    other = pallas_flash_decode_latent(
        q_lat, q_rope, latent.at[:, :, -1].set(9.0), rope_t, mask,
        block_k=block_k)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(other))


def test_latent_decode_kernel_refuses_a_wrong_layout():
    q, r = jnp.zeros((2, 4, 32)), jnp.zeros((2, 4, 16))
    c, k = jnp.zeros((2, 1, 64, 32)), jnp.zeros((2, 1, 16, 64))
    with pytest.raises(ValueError, match="positional keys"):
        pallas_flash_decode_latent(q, r, c, k.swapaxes(2, 3))
    with pytest.raises(ValueError, match="kv_mask"):
        latent_decode_attention(q, r, c, k, jnp.ones((2, 63), bool))


@pytest.mark.parametrize("edit, words", [
    ({"kv_lora_rank": None}, "every latent width; missing kv_lora_rank"),
    ({"q_lora_rank": 0, "v_head_dim": 0}, "missing q_lora_rank, v_head_dim"),
    ({"qk_rope_head_dim": 3}, "latent attention needs all of"),
    ({"rope_scaling": {"type": "linear", "factor": 4}}, "type 'yarn'"),
    ({"rope_scaling": {"type": "yarn", "factor": 4}},
     "original_max_position_embeddings"),
    ({"topk_method": "greedy"}, "noaux_tc"),
    ({"n_group": 3}, "equal groups"),
    ({"topk_group": 1, "num_experts_per_tok": 8}, "equal groups"),
])
def test_a_bad_latent_configuration_is_a_one_line_error(edit, words):
    with pytest.raises(ValueError) as e:
        ModelConfig.from_dict({**TINY, **edit})
    assert words in str(e.value) and "\n" not in str(e.value)


def test_half_given_latent_fields_are_an_error():
    ok = ModelConfig.from_dict(TINY)
    assert ok.latent and ok.dim_head == 12 and ok.kv_heads == 4
    with pytest.raises(ValueError, match="latent attention needs all of"):
        ModelConfig(num_tokens=8, dim=8, heads=2, dim_head=4, kv_heads=2,
                    layers=ok.layers[:1], ffn_dim=8, kv_latent_dim=4)


def test_a_latent_layer_on_a_sequence_mesh_names_the_roadmap(tiny, devices):
    _, params, tokens = tiny
    model = build(mesh=create_mesh(ring_size=4, data_size=2), use_ring=True)
    with pytest.raises(NotImplementedError, match="ROADMAP R5"):
        model.apply({}, 2, 32, method=RingTransformer.init_cache)
    cache = build().apply({}, 2, 32, method=RingTransformer.init_cache)
    for method, args in ((RingTransformer.prefill, (tokens[:2, :8], cache)),
                         (RingTransformer.decode_step,
                          (tokens[:2, 0], cache, jnp.int32(0)))):
        with pytest.raises(NotImplementedError, match="ROADMAP R5") as e:
            model.apply(params, *args, method=method)
        assert "\n" not in str(e.value)


def test_the_expanded_forward_runs_on_the_ring(tiny, devices):
    """``__call__`` is ordinary multi-head attention, so the layouts the
    repo has apply: the striped ring over four devices gives the logits
    one device gives."""
    model, params, tokens = tiny
    ring = build(mesh=create_mesh(ring_size=4, data_size=2), use_ring=True,
                 striped=True)
    got = jax.jit(lambda p, t: ring.apply(p, t))(params, tokens[:2])
    np.testing.assert_allclose(got, model.apply(params, tokens[:2]),
                               atol=5e-5)


# ----------------------------------------------------------------------
# the other families' programs are the parent's
# ----------------------------------------------------------------------

AFMOE_TOY = dict(
    model_type="afmoe", vocab_size=VOCAB, hidden_size=32,
    num_attention_heads=4, num_key_value_heads=2, head_dim=8,
    intermediate_size=64, moe_intermediate_size=16, num_hidden_layers=5,
    num_dense_layers=1, sliding_window=6, rms_norm_eps=1e-5,
    layer_types=["sliding_attention"] * 4 + ["full_attention"],
    rope_theta=10000, mup_enabled=True, num_experts=4,
    published={"num_experts": 16}, first_expert=4, num_experts_per_tok=4,
    num_shared_experts=1, route_scale=2.448, route_norm=True,
    score_func="sigmoid")
STARCODER2_TOY = dict(
    model_type="starcoder2", vocab_size=VOCAB, hidden_size=32,
    intermediate_size=128, num_attention_heads=4, num_key_value_heads=2,
    num_hidden_layers=2, sliding_window=None, rope_theta=10000.0)
# sha256 (first 16 hex digits) of ``jax.jit(call).lower(...).as_text()`` at
# the parent commit of PR 32 (c1c81eb), jax 0.9.0, under this suite's
# conftest (float32 products at the highest precision): a program that lowers to
# the same text is the same program.  A later PR that changes one of these
# programs on purpose records the new digest here and says so.  PR 35 did,
# for the four ``afmoe.*``: the routed layer's combine lost its mask over the
# products' output (a select by each slot's ``mine`` stands in the sum) and
# the layer sows ``combine_rows_copied``.  PR 38 did, for all eight: every
# rotary site turns q and k by a product with a signed permutation
# (``ops/rotary.py::apply_rotary``) in place of rotate_half's split,
# negation and concatenation, and the angle table's halves are tiled, not
# concatenated; the numbers are the same (``tests/test_rotary.py``).
# Re-recorded on purpose, ``afmoe.prefill`` and ``starcoder2.prefill``: the
# prefill takes the last position before the head in every family, from the
# stream's rows with positions leading.
PARENT_TEXT = {
    "afmoe.forward": "a0071562c4e72b69",
    "afmoe.loss": "36b8f1fc1c10045a",
    "afmoe.prefill": "b6aa60f41e4882ff",
    "afmoe.decode_step": "923c1b6c51c51418",
    "starcoder2.forward": "1fe7a7d1b6964d6d",
    "starcoder2.loss": "009adadee0165a28",
    "starcoder2.prefill": "0967666451b34176",
    "starcoder2.decode_step": "3943076a98e7bff6",
}


@pytest.mark.parametrize("name", sorted(PARENT_TEXT))
def test_the_other_families_lower_to_the_parents_text(name):
    """Latent attention, the yarn blend and the group-limited choice are
    taken only by a configuration that asks for them: Trinity's and
    StarCoder2's toy programs (``decode_step`` with the routing counters
    on) lower to the text they lowered to before this family was added."""
    family, call = name.split(".")
    model = build({"afmoe": AFMOE_TOY, "starcoder2": STARCODER2_TOY}[family])
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
          "cache_rel_l2": 1e-2, "routing_margin": 0.05, "min_positions": 3}


@pytest.mark.parametrize("fault, ok, kept", [
    (None, True, 6), ("logits", False, 6),
    ("one_layers_attention", False, 6), ("one_layers_latents", False, 6),
    ("one_layers_rotated_keys", False, 6), ("no_probes", False, 6),
    ("last_layer_within_its_own_limit", True, 6),
    ("a_prompt_position_near_a_tie", True, 5),
    ("routed_differently_under_the_margin", True, 5),
    ("off_below_the_layer_that_routed_differently", False, 5),
    ("routed_differently_beyond_the_margin", False, 5),
    ("and_differently_above_it_at_any_margin", True, 5),
    ("too_few_positions", False, 2)])
def test_verdict_holds_each_limit(fault, ok, kept):
    """``correct`` needs the logits, every layer's attention output within
    that layer's limit, every layer's cache rows and enough positions.  A
    decoded position is compared where the program's counters say it was
    routed as the reference routed it, a prompt position where the
    reference's margin is wide; what a layer made is compared wherever the
    routed layers below it agree."""
    rng = np.random.default_rng(0)
    logits = jnp.asarray(rng.normal(size=(6, 16)), jnp.float32)
    attn = jnp.asarray(rng.normal(size=(3, 6, 8)), jnp.float32)
    kv = [(jnp.asarray(rng.normal(size=(1, 6, 4)), jnp.float32),
           jnp.asarray(rng.normal(size=(1, 6, 16)), jnp.float32))
          for _ in range(3)]
    chose = rng.random(size=(2, 6, 4)) < 0.3
    margins = np.full((2, 6), 1.0)
    margins[1, 5] = 0.01  # where the tests below turn a choice
    inside = {"margins": margins, "margin": margins.min(0), "attn": attn,
              "kv": kv, "chose": chose,
              "routed_layers": [1, 2], "groups": jnp.zeros((2, 4), jnp.int32)}
    # the program knows the last four positions' choices; it agrees
    routing = {"chose": chose[:, 2:].copy()}
    got = {"logits": logits, "attn": attn, "kv": list(kv), "routing": routing}
    margins[0, 0] = 0.06  # a prompt position, clear of the margin
    if fault == "logits":
        got["logits"] = logits * 1.02
    if fault == "one_layers_attention":
        got["attn"] = attn.at[1].multiply(1.02)
    if fault == "last_layer_within_its_own_limit":
        got["attn"] = attn.at[2].multiply(1.02)
    if fault == "one_layers_latents":
        got["kv"][2] = (kv[2][0], kv[2][1] * 1.02)
    if fault == "one_layers_rotated_keys":
        got["kv"][0] = (kv[0][0] * 1.02, kv[0][1])
    if fault == "no_probes":
        got["attn"] = None
    if fault == "a_prompt_position_near_a_tie":
        margins[0, 0] = 0.04  # and wrong there, which is then not compared
        got = {**got, "logits": logits.at[0].set(0.0),
               "attn": attn.at[2:, 0].set(0.0)}
    if fault in ("routed_differently_under_the_margin",
                 "off_below_the_layer_that_routed_differently"):
        # the last layer's router (routed layer 1 is layer 2 of the stack)
        # chose otherwise at the last position: its logits are not compared,
        # what the layers made up to that router still is
        routing["chose"][1, -1] = ~routing["chose"][1, -1]
        got["logits"] = logits.at[-1].set(0.0)
        if fault.startswith("off"):
            got["attn"] = attn.at[2, -1].set(0.0)
    if fault == "routed_differently_beyond_the_margin":
        routing["chose"][0, -2] = ~routing["chose"][0, -2]
    if fault == "and_differently_above_it_at_any_margin":
        # turned under the margin in the first routed layer: to the second
        # it is another token, whose choice may differ at a margin of 1
        margins[0, 3] = 0.01
        routing["chose"][:, 1] = ~routing["chose"][:, 1]
        got = {**got, "logits": logits.at[3].set(0.0),
               "attn": attn.at[2, 3].set(0.0)}
    if fault == "too_few_positions":
        margins[:, :2] = 0.01
        routing["chose"][0, :2] = ~routing["chose"][0, :2]
        margins[0, 2:4] = 0.01
    out = dots_vlm.verdict(got, logits, inside, LIMITS)
    assert out["ok"] is ok
    assert out["positions_compared"] == kept
    assert out["routed_differently_beyond_margin"] is (
        fault == "routed_differently_beyond_the_margin")
    assert out["positions_compared_by_layer"][:2] == [6, 6]


def test_the_cells_configuration_file():
    """It builds, states every published number beside its cut, and holds
    the 4.57 G parameters ISSUE 32 reckoned (8.50 GiB in bfloat16) and
    2.81 GiB of latent cache at the cell's four 131,072-position
    sessions."""
    path = os.path.join(REPO, "benchmarks", "configs", "dots-vlm1-inst.json")
    cfg = ModelConfig.from_file(path)
    assert (cfg.dim, cfg.heads, cfg.kv_heads, cfg.dim_head) == (
        7168, 128, 128, 192)
    assert (cfg.q_latent_dim, cfg.kv_latent_dim, cfg.qk_nope_dim,
            cfg.qk_rope_dim, cfg.v_dim) == (1536, 512, 128, 64, 128)
    assert [(layer.window, layer.rotary, layer.ffn) for layer in cfg.layers
            ] == [(None, True, "gated")] + [(None, True, "routed")] * 4
    assert (cfg.num_experts, cfg.experts_held, cfg.first_expert,
            cfg.experts_per_token, cfg.expert_groups, cfg.groups_per_token
            ) == (256, 16, 0, 8, 8, 4)
    assert (cfg.ffn_dim, cfg.expert_dim, cfg.shared_expert_dim,
            cfg.route_scale, cfg.norm_eps) == (18432, 2048, 2048, 2.5, 1e-6)
    assert cfg.rope_scaling == YarnScaling(40.0, 4096, 32.0, 1.0, 1.0, 1.0)
    with open(path) as f:
        raw = json.load(f)
    assert {k: raw["published"][k] for k in raw["reduced"]} == {
        "num_hidden_layers": 61, "first_k_dense_replace": 3,
        "n_routed_experts": 256, "vocab_size": 129280}
    assert {k: raw[k] for k in raw["reduced"]} == {
        "num_hidden_layers": 5, "first_k_dense_replace": 1,
        "n_routed_experts": 16, "vocab_size": 16160}
    cut = set(raw["reduced"])
    assert all(raw[k] == v for k, v in raw["published"].items()
               if k not in cut)
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        entry = next(c for c in json.load(f)["configs"]
                     if c["name"] == "dots-vlm1-inst")
    assert entry["reduced"] == raw["reduced"]
    model = RingTransformer.from_config(cfg, mesh=None, use_ring=False,
                                        dtype=jnp.bfloat16)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                            jnp.zeros((1, 8), jnp.int32))
    count = sum(math.prod(a.shape) for a in jax.tree.leaves(shapes))
    assert count == 4_565_721_088
    cache = jax.eval_shape(lambda: model.apply(
        {}, 4, 131072, method=RingTransformer.init_cache))
    assert sum(math.prod(a.shape) * 2 for a in jax.tree.leaves(cache)) == (
        4 * 131072 * 5 * 1152)


@pytest.mark.parametrize("flags", [(), ("--use-pallas", "--bf16")])
def test_generate_example_builds_a_latent_model_from_a_file(flags):
    """``examples/generate.py --config FILE`` is the entry point's way to a
    latent model: prefill, then decode through the latent cache."""
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "examples", "generate.py"),
         "--config", os.path.join(REPO, "benchmarks", "tests", "toy_mla",
                                  "configs", "toy_mla.json"),
         "--steps", "5", "--prompt-len", "16", "--max-len", "64",
         "--devices", "1", *flags],
        cwd=REPO, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "generated 5 tokens" in proc.stdout, proc.stdout[-1500:]


def test_rehearsal_of_the_latent_driver():
    """``run.py --rehearse`` drives ``kinds/serve_latent.py`` end to end on
    the CPU against a toy manifest of its own."""
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmarks", "run.py"),
         "--manifest",
         os.path.join(REPO, "benchmarks", "tests", "toy_mla",
                      "BENCHMARK.json"),
         "--rehearse", "--workload", "toy_mla.serve", "--seed",
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
    # the layers under the first routed one are compared at every position
    assert check["positions_compared_by_layer"][:2] == [9, 9]
    assert check["routed_differently_beyond_margin"] is False
    assert check["routing"]["pairs_on_held"] > 0
    latent = check["latent"]
    # every byte the steps read is a byte the shapes say they read
    assert (latent["cache_bytes_read_per_step"]
            == latent["cache_bytes_by_shape_per_step"])
    got = np.asarray(latent["pairs_per_group"])
    want = np.asarray(check["reference_pairs_per_group"])
    assert got.shape == want.shape == (2, 4)
    assert got.sum() == want.sum() == 2 * 200 * 4
    assert np.abs(got - want).sum() <= 40
