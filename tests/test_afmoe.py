"""The afmoe block (Trinity-Large-Preview) at tiny widths on the CPU: the
program, built from a frozen ``ModelConfig`` through ``RingTransformer``'s
own constructor, against the plain reference the benchmark keeps
(``benchmarks/references/afmoe.py``), with seeded weights and a seeded
non-zero expert bias."""

import json
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from ring_attention_tpu.models import (
    ModelConfig,
    RingTransformer,
    RoutedFeedForward,
    moe,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
from benchmarks.references import afmoe  # noqa: E402

VOCAB, WINDOW = 96, 6
TINY = dict(
    model_type="afmoe", vocab_size=VOCAB, hidden_size=32,
    num_attention_heads=4, num_key_value_heads=2, head_dim=8,
    intermediate_size=64, moe_intermediate_size=16, num_hidden_layers=5,
    num_dense_layers=1, sliding_window=WINDOW, rms_norm_eps=1e-5,
    layer_types=["sliding_attention"] * 4 + ["full_attention"],
    rope_theta=10000, mup_enabled=True, num_experts=4,
    published={"num_experts": 16}, first_expert=4, num_experts_per_tok=4,
    num_shared_experts=1, route_scale=2.448, route_norm=True,
    score_func="sigmoid")
ATOL = 2e-5


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
        np.random.default_rng(0).integers(0, VOCAB, (2, 20)), jnp.int32)
    params = seeded(model, tokens)
    return model, params, tokens


def test_init_returns_parameters_only(tiny):
    # the counters and the probes are sown on request, never at init
    assert set(tiny[1]) == {"params"}


@pytest.mark.parametrize("call", ["forward", "prefill", "decode_step"])
def test_probes_give_each_layers_attention_output(tiny, call):
    """``mutable=["probes"]`` returns what each layer's attention made,
    before its norm, from whichever call walked the stack; the reference
    gives the same rows."""
    model, params, tokens = tiny
    _, inside = afmoe.forward(params, tokens[0], TINY)
    want = np.asarray(inside["attn"])  # (layers, n, hidden)
    if call == "forward":
        _, col = model.apply(params, tokens[:1], mutable=["probes"])
        rows = slice(None)
    else:
        cache = model.apply({}, 1, 32, method=RingTransformer.init_cache)
        (_, cache), col = model.apply(
            params, tokens[:1, :14], cache, method=RingTransformer.prefill,
            mutable=["probes"])
        rows = slice(0, 14)
        if call == "decode_step":
            _, col = model.apply(
                params, tokens[:1, 14], cache, jnp.int32(14),
                method=RingTransformer.decode_step, mutable=["probes"])
            rows = slice(14, 15)
    assert sorted(col["probes"]) == [f"attn_out_{i}" for i in range(5)]
    for i in range(5):
        np.testing.assert_allclose(
            col["probes"][f"attn_out_{i}"][0], want[i, rows], atol=ATOL)


@pytest.mark.parametrize("quantize_cache", [False, True])
def test_the_checks_cache_rows_are_the_references(tiny, quantize_cache):
    """What the driver's check reads out of both cache kinds (a ring buffer
    that has wrapped, a full cache; dense or int8 pairs) are the
    reference's k and v rows at those positions: to float32 rounding, or
    to an int8 row's step."""
    from benchmarks.kinds.serve_sessions import _cache_rows

    _, params, tokens = tiny
    model = build(quantize_cache=quantize_cache)
    cache = model.apply({}, 1, 32, method=RingTransformer.init_cache)
    _, cache = model.apply(params, tokens[:1, :14], cache,
                           method=RingTransformer.prefill)
    _, cache = model.apply(params, tokens[:1, 14], cache, jnp.int32(14),
                           method=RingTransformer.decode_step)
    positions = np.arange(12, 15)
    got = _cache_rows(cache, positions)
    _, inside = afmoe.forward(params, tokens[0, :15], TINY, last=3)
    assert got.shape == inside["kv"].shape == (5, 2, 2, 3, 8)
    worst = float(jnp.abs(got - inside["kv"]).max())
    if quantize_cache:
        assert 1e-4 < worst < 3e-2  # a 127th of a row's largest entry
    else:
        assert worst < ATOL


LIMITS = {"logits_rel_l2": 1e-2, "attn_rel_l2": 1e-2, "cache_rel_l2": 1e-2,
          "routing_margin": 3e-3, "min_positions": 3}


@pytest.mark.parametrize("fault, ok", [
    (None, True), ("logits", False), ("one_layers_attention", False),
    ("one_layers_v_rows", False), ("a_nan_in_one_layer", False),
    ("no_probes", False), ("too_few_positions", False),
    ("off_where_routing_is_near_a_tie", True)])
def test_verdict_holds_each_limit(fault, ok):
    """``correct`` needs the logits, every layer's attention output, every
    layer's cache rows and enough positions; a position near a routing tie
    is left out of all three."""
    rng = np.random.default_rng(0)
    logits = jnp.asarray(rng.normal(size=(6, 16)), jnp.float32)
    attn = jnp.asarray(rng.normal(size=(3, 6, 8)), jnp.float32)
    kv = jnp.asarray(rng.normal(size=(3, 2, 2, 6, 4)), jnp.float32)
    margin = jnp.asarray([1.0, 1.0, 1e-3, 1.0, 1.0, jnp.inf])
    got = {"logits": logits, "attn": attn, "kv": kv}
    if fault == "logits":
        got["logits"] = logits * 1.02
    if fault == "one_layers_attention":
        got["attn"] = attn.at[1].multiply(1.02)
    if fault == "one_layers_v_rows":
        got["kv"] = kv.at[2, 1].multiply(1.02)
    if fault == "a_nan_in_one_layer":
        got["attn"] = attn.at[2, 0, 0].set(jnp.nan)
    if fault == "no_probes":
        got["attn"] = None
    if fault == "too_few_positions":
        margin = margin.at[:4].set(1e-3)
    if fault == "off_where_routing_is_near_a_tie":
        got = {"logits": logits.at[2].set(0.0), "attn": attn.at[:, 2].set(0.0),
               "kv": kv.at[..., 2, :].set(0.0)}
    out = afmoe.verdict(got, logits,
                        {"margin": margin, "attn": attn, "kv": kv}, LIMITS)
    assert out["ok"] is ok
    assert out["positions_compared"] == (2 if fault == "too_few_positions"
                                         else 5)


@pytest.mark.parametrize("row", [0, 1])
def test_forward_matches_reference(tiny, row):
    model, params, tokens = tiny
    got = model.apply(params, tokens)[row]
    want, routing = afmoe.forward(params, tokens[row], TINY)
    np.testing.assert_allclose(got, want, atol=ATOL)
    # the held quarter of 16 experts gets about a quarter of the pairs
    assert routing["counts"].shape == (4, 4)
    assert 0 < int(routing["counts"].sum()) < 4 * 20 * 4


def test_counters_give_the_routers_choices(tiny):
    model, params, tokens = tiny
    _, col = model.apply(params, tokens[:1], mutable=["counters"])
    _, routing = afmoe.forward(params, tokens[0], TINY)
    layers = [col["counters"][f"ff_layers_{i}"] for i in range(1, 5)]
    got = np.stack([c["tokens_per_expert"] for c in layers])
    np.testing.assert_array_equal(got, routing["counts"])
    for c, row in zip(layers, got):
        assert float(c["held_share"]) == pytest.approx(row.sum() / 80)
        assert int(c["combine_rows_copied"]) == row.sum()
        assert int(c["experts_touched"]) == int((row > 0).sum())


@pytest.mark.parametrize("use_pallas", [False, True])
def test_prefill_past_the_window_then_decode(tiny, use_pallas):
    """Two sessions: a prompt longer than the window wraps the sliding
    layers' ring buffers and fills the full layer's cache; decoding goes on
    through both kinds."""
    _, params, tokens = tiny
    model = build(use_pallas=use_pallas)
    want = np.stack([afmoe.logits(params, tokens[b], TINY) for b in (0, 1)])
    cache = model.apply({}, 2, 32, method=RingTransformer.init_cache)
    sizes = [c.shape for c in cache["k"]]
    assert sizes == [(2, 2, WINDOW, 8)] * 4 + [(2, 2, 32, 8)]
    prompt = 14
    assert prompt > 2 * WINDOW
    logits, cache = model.apply(params, tokens[:, :prompt], cache,
                                method=RingTransformer.prefill)
    np.testing.assert_allclose(logits, want[:, prompt - 1], atol=ATOL)
    step = jax.jit(lambda p, t, c, i: model.apply(
        p, t, c, i, method=RingTransformer.decode_step))
    for i in range(prompt, 20):
        logits, cache = step(params, tokens[:, i], cache, jnp.int32(i))
        np.testing.assert_allclose(logits, want[:, i], atol=ATOL, err_msg=i)


def routed_layer(held, first, **kw):
    return RoutedFeedForward(
        dim=32, expert_dim=16, num_experts=16, experts_per_token=4,
        experts_held=held, first_expert=first, route_scale=2.448,
        norm_eps=1e-5, **kw)


def held_part(p, held, first):
    """A holder's parameters: the router whole, its own experts' matrices."""
    part = {k: v for k, v in p.items() if k != "shared"}
    part["experts_gate_up"] = p["experts_gate_up"][first:first + held]
    part["experts_down"] = p["experts_down"][first:first + held]
    return part


@pytest.fixture(scope="module")
def uncut():
    """One routed layer holding all 16 experts, its seeded parameters, an
    input, and the uncut reference's output for it."""
    layer = routed_layer(16, 0, shared_dim=16)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 24, 32))
    p = layer.init(jax.random.PRNGKey(2), x)["params"]
    p = {**p, "expert_bias": 0.3 * jax.random.normal(jax.random.PRNGKey(3),
                                                     (16,))}
    config = {**TINY, "num_experts": 16, "first_expert": 0}
    with jax.default_matmul_precision("highest"):
        m = afmoe._rmsnorm(x.reshape(48, 32), p["norm"]["gamma"], 1e-5)
        want, chose, _ = afmoe._routed(m, p, config)
    return layer, p, x, m, want, np.asarray(chose.sum(0))


def test_uncut_layer_matches_reference(uncut):
    layer, p, x, _, want, _ = uncut
    got = layer.apply({"params": p}, x).reshape(48, 32)
    np.testing.assert_allclose(got, want, atol=ATOL)


def test_four_shares_and_the_shared_expert_once_make_the_layer(uncut):
    """16 experts in 4 shares of 4: each share routes over all 16 and
    computes its own experts' part; the parts and the shared expert, which
    every holder computes alike, counted once, are the uncut layer."""
    _, p, x, m, want, counts = uncut
    total = afmoe._gated(m, p["shared"])
    pairs = 0
    for share in range(4):
        held = slice(4 * share, 4 * share + 4)
        out, col = routed_layer(4, 4 * share).apply(
            {"params": held_part(p, 4, 4 * share)}, x, mutable=["counters"])
        total = total + out.reshape(48, 32)
        got = np.asarray(col["counters"]["tokens_per_expert"])
        np.testing.assert_array_equal(got, counts[held])
        pairs += got.sum()
    assert pairs == 48 * 4  # every pair the router chose, on one holder
    np.testing.assert_allclose(total, want, atol=ATOL)


def test_bias_moves_the_selection_and_not_the_weights(uncut):
    _, p, x, m, _, _ = uncut
    held = 5
    part = held_part(p, 1, held)
    part["expert_bias"] = jnp.zeros(16).at[held].set(10.0)
    out, col = routed_layer(1, held).apply(
        {"params": part}, x, mutable=["counters"])
    # selection: with a bias of 10 every token chooses the expert
    assert int(col["counters"]["tokens_per_expert"][0]) == 48
    # weights: the scores without the bias, over the four chosen
    scores = np.asarray(jax.nn.sigmoid(m @ p["router"]), np.float64)
    biased = scores.copy()
    biased[:, held] += 10.0
    chosen = np.argsort(-biased, axis=1)[:, :4]
    assert (chosen[:, 0] == held).all()
    weight = 2.448 * scores[:, held] / np.take_along_axis(
        scores, chosen, 1).sum(1)
    h = np.asarray(m @ p["experts_gate_up"][held], np.float64)
    expert = (h[:, :16] / (1 + np.exp(-h[:, :16])) * h[:, 16:]) @ np.asarray(
        p["experts_down"][held], np.float64)
    np.testing.assert_allclose(out.reshape(48, 32), weight[:, None] * expert,
                               atol=ATOL)


@pytest.mark.parametrize("held,first", [(16, 0), (4, 8)])
def test_more_pairs_than_a_pass_takes_more_passes(uncut, monkeypatch,
                                                  held, first):
    """No pair is dropped: with every pair on this holder (the worst case)
    and a pass of 40 rows, 192 pairs take five passes and give what one
    pass gives."""
    _, p, x, _, _, _ = uncut
    part, layer = held_part(p, held, first), routed_layer(held, first)
    one = layer.apply({"params": part}, x)
    monkeypatch.setattr(moe, "PASS_ROWS", 40)
    many = jax.jit(lambda x: layer.apply({"params": part}, x))(x)
    np.testing.assert_allclose(many, one, atol=ATOL)


def poison_the_rows_past_the_last_pair(monkeypatch):
    """``lax.ragged_dot`` defines the rows of its groups; here it writes NaN
    into every row past them, which it is free to do."""
    ragged_dot = lax.ragged_dot

    def poisoned(lhs, rhs, group_sizes, **kw):
        out = ragged_dot(lhs, rhs, group_sizes, **kw)
        defined = jnp.arange(out.shape[0]) < group_sizes.sum()
        return jnp.where(defined[:, None], out, jnp.nan)

    monkeypatch.setattr(moe.lax, "ragged_dot", poisoned)


@pytest.mark.parametrize("held,first", [(16, 0), (4, 8)])
@pytest.mark.parametrize("how, blocks", [
    ("one_pass", (1, 1)), ("several_passes", (1, 1)), ("in_blocks", (8, 2))])
def test_rows_past_the_last_pair_are_never_read(uncut, monkeypatch, how,
                                                blocks, held, first):
    """The combine reads a row of the products' output only through a held
    pair, whose place is below the pass's last pair: whatever the product
    left in the rows past it (here NaN) reaches no token's sum, in one
    pass, in five passes of 40 rows, and a block of tokens at a time."""
    _, p, x, _, _, _ = uncut
    part, layer = held_part(p, held, first), routed_layer(held, first)
    want = layer.apply({"params": part}, x)
    if how != "one_pass":
        monkeypatch.setattr(moe, "PASS_ROWS", 40)
    if how == "several_passes":
        monkeypatch.setattr(RoutedFeedForward, "_blocks", lambda self, n: 1)
    assert layer._blocks(48) == blocks[held == 4]
    poison_the_rows_past_the_last_pair(monkeypatch)
    got = jax.jit(lambda x: layer.apply({"params": part}, x))(x)
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got, want, atol=ATOL)


@pytest.mark.parametrize("held,first", [(16, 0), (4, 8)])
@pytest.mark.parametrize("pass_rows", [None, 40])
def test_combine_rows_copied_counts_the_held_pairs(uncut, monkeypatch, held,
                                                   first, pass_rows):
    """``combine_rows_copied`` is the rows the combine needs of the
    products' output, one a held pair: ``held_share`` x slots, whether the
    call runs in one pass or a block of tokens at a time; the other
    counters read as before."""
    _, p, x, _, _, counts = uncut
    if pass_rows:
        monkeypatch.setattr(moe, "PASS_ROWS", pass_rows)
    _, col = routed_layer(held, first).apply(
        {"params": held_part(p, held, first)}, x, mutable=["counters"])
    col = col["counters"]
    assert set(col) == {"tokens_per_expert", "held_share", "experts_touched",
                        "combine_rows_copied"}
    mine = counts[first:first + held]
    np.testing.assert_array_equal(col["tokens_per_expert"], mine)
    assert int(col["experts_touched"]) == int((mine > 0).sum())
    slots = 48 * 4
    assert col["combine_rows_copied"].dtype == jnp.int32
    assert int(col["combine_rows_copied"]) == mine.sum()
    assert int(col["combine_rows_copied"]) == round(
        float(col["held_share"]) * slots)


def test_the_combine_makes_nothing_of_the_products_whole_shape():
    """A structural guard on a routed layer's lowered text: under
    ``moe/combine`` no operation (a select, a call to ``where``, a product
    with a mask) makes an array of the products' whole ``(rows, d)`` shape:
    ``y`` is only gathered from.  48 tokens, 192 rows, d 32."""
    layer, x = routed_layer(4, 8), jnp.zeros((2, 24, 32))
    params = jax.eval_shape(layer.init, jax.random.PRNGKey(0), x)
    text = jax.jit(layer.apply).lower(params, x).as_text(debug_info=True)
    scope = dict(re.findall(r'^(#loc\d+) = loc\("([^"]*)"', text, re.M))
    combine = [
        line for line in text.splitlines()
        if (at := re.search(r"loc\((#loc\d+)\)\s*$", line))
        and "moe/combine" in scope.get(at.group(1), "")]
    assert any("call @_take" in line for line in combine)  # the gathers
    results = [re.findall(r"tensor<[^>]*>", line.rsplit("->", 1)[-1])[-1]
               for line in combine if "tensor<" in line]
    assert not [r for r in results if r.startswith("tensor<192x32x")]
    assert [r for r in results if r.startswith("tensor<48x32x")]


STARCODER2_TOY = dict(
    model_type="starcoder2", vocab_size=VOCAB, hidden_size=32, intermediate_size=128,
    num_attention_heads=4, num_key_value_heads=2, num_hidden_layers=2,
    sliding_window=None, rope_theta=10000.0)


def test_starcoder2_block_through_the_new_constructor(tiny):
    """The keyword constructor and the configuration's give one model: the
    same parameter tree and, from the same parameters, the same logits."""
    tokens = tiny[2]
    new = build(STARCODER2_TOY)
    old = RingTransformer(
        num_tokens=VOCAB, dim=32, depth=2, heads=4, dim_head=8, kv_heads=2,
        ff_mult=4, causal=True, mesh=None, use_ring=False, bucket_size=4)
    params = old.init(jax.random.PRNGKey(0), tokens)
    shapes = jax.tree.map(jnp.shape, params)
    assert shapes == jax.tree.map(
        jnp.shape, new.init(jax.random.PRNGKey(0), tokens))
    assert set(shapes["params"]) == {
        "embed", "attn_layers_0", "attn_layers_1", "ff_layers_0",
        "ff_layers_1", "final_norm", "to_logits"}
    np.testing.assert_array_equal(
        new.apply(params, tokens), old.apply(params, tokens))


def test_rotary_theta_comes_from_the_configuration(tiny):
    tokens = tiny[2]
    base = build(STARCODER2_TOY)
    other = build({**STARCODER2_TOY, "rope_theta": 1e6})
    params = base.init(jax.random.PRNGKey(0), tokens)
    assert ModelConfig.from_dict(
        {**STARCODER2_TOY, "rope_theta": 1e6}).rotary_theta == 1e6
    assert not np.allclose(base.apply(params, tokens),
                           other.apply(params, tokens))


def test_the_cells_configuration_file():
    path = os.path.join(REPO, "benchmarks", "configs",
                        "trinity-large-preview.json")
    cfg = ModelConfig.from_file(path)
    assert (cfg.dim, cfg.heads, cfg.kv_heads, cfg.dim_head) == (
        3072, 48, 8, 128)
    assert [(layer.window, layer.rotary, layer.ffn) for layer in cfg.layers] == [
        (4096, True, "gated")] + [(4096, True, "routed")] * 3 + [
        (None, False, "routed")]
    assert (cfg.num_experts, cfg.experts_held, cfg.first_expert,
            cfg.experts_per_token) == (256, 32, 0, 4)
    assert (cfg.ffn_dim, cfg.expert_dim, cfg.shared_expert_dim) == (
        12288, 3072, 3072)
    assert cfg.qk_norm and cfg.attn_gate and cfg.sandwich_norm
    assert cfg.embed_scale == 3072 ** 0.5 and cfg.norm_eps == 1e-5
    # every published number stands beside its cut
    with open(path) as f:
        raw = json.load(f)
    assert raw["published"]["num_experts"] == 256
    assert {k: raw["published"][k] for k in raw["reduced"]
            if k != "layer_types"} == {
        "num_hidden_layers": 60, "num_dense_layers": 6, "num_experts": 256,
        "vocab_size": 200192}
    cut = set(raw["reduced"])
    assert all(raw[k] == v for k, v in raw["published"].items()
               if k not in cut)


def test_a_bad_configuration_is_a_one_line_error():
    with pytest.raises(ValueError, match="held experts"):
        ModelConfig.from_dict({**TINY, "first_expert": 14})
    with pytest.raises(ValueError, match="layer_types"):
        ModelConfig.from_dict({**TINY, "num_hidden_layers": 4})


@pytest.mark.parametrize("name", [{"model_type": "llama"}, {}, {
    "model_type": None, "architectures": ["Qwen3ForCausalLM"]}])
def test_an_unknown_family_is_an_error_not_another_familys_block(name):
    config = {k: v for k, v in STARCODER2_TOY.items() if k != "model_type"}
    with pytest.raises(
            ValueError, match="afmoe, dots_vlm, granitemoehybrid, starcoder2"):
        ModelConfig.from_dict({**config, **name})


@pytest.mark.parametrize("name, depth", [("starcoder2-3b", 2),
                                         ("starcoder2-7b", 4)])
def test_the_starcoder2_files_name_their_family(name, depth):
    """The benchmark's StarCoder2 files carry ``architectures``, not
    ``model_type``: they build the uniform block they are run as."""
    cfg = ModelConfig.from_file(
        os.path.join(REPO, "benchmarks", "configs", name + ".json"))
    assert cfg.layers == (cfg.layers[0],) * depth
    assert (cfg.layers[0].window, cfg.layers[0].rotary,
            cfg.layers[0].ffn) == (None, True, "gelu")
    assert not (cfg.qk_norm or cfg.attn_gate or cfg.sandwich_norm)
    assert cfg.dim_head == 128 and cfg.ffn_dim == 4 * cfg.dim


def test_rehearsal_of_the_sessions_driver():
    """``run.py --rehearse`` drives ``kinds/serve_sessions.py`` end to end
    on the CPU against a toy manifest of its own."""
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmarks", "run.py"),
         "--manifest",
         os.path.join(REPO, "benchmarks", "tests", "toy_afmoe",
                      "BENCHMARK.json"),
         "--rehearse", "--workload", "toy_afmoe.serve", "--seed",
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
    assert check["positions"] == 9 and check["positions_compared"] >= 4
    routing = check["routing"]
    assert routing["pairs_on_held"] > 0
    assert len(routing["prefill_held_share"]) == 2  # the routed layers
