"""Parity: hybrid Ulysses x Ring 2-D sequence parallelism vs the oracle.

Capability beyond the reference (1-D context parallelism only): the
sequence axis factors as ``seq = ulysses x ring`` — all-to-all head
parallelism over the inner mesh axis, the existing KV-rotation ring over
the outer axis on each device's head subset — and must match dense
attention in outputs AND gradients on every factoring of the 8-device
mesh, composed with everything the 1-D paths support (striping, GQA,
packed segment ids, key-padding masks, bidirectional KV streams, the
Pallas kernels).

The hop-count acceptance check reads the optimized HLO: the hybrid step's
ring ``collective-permute``s must stay within outer-axis groups (never
crossing the ulysses axis) and number ``ulysses_size`` x fewer than the
pure ring's at equal world size.
"""

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from ring_attention_tpu.models import RingAttention, RingTransformer
from ring_attention_tpu.ops import default_attention
from ring_attention_tpu.parallel import (
    create_mesh,
    hybrid_attention,
    seq_axes,
    seq_world,
    shard_batch,
)
from ring_attention_tpu.utils.compat import shard_map

ATOL = 2e-5
GRAD_ATOL = 5e-4

# (data, ulysses, ring) sizes of the 8 virtual devices; the mesh axis
# order itself is (data, ring, ulysses) — ulysses innermost/fastest
FACTORINGS = [(2, 2, 2), (1, 2, 4), (1, 4, 2)]


@pytest.fixture(scope="module")
def meshes():
    return {
        (d, u, r): create_mesh(ulysses_size=u, ring_size=r, data_size=d)
        for (d, u, r) in FACTORINGS
    }


def make_pair(mesh, **kw):
    """Hybrid module + single-device oracle sharing identical params."""
    common = {"dim": 32, "heads": 8, "dim_head": 8, "bucket_size": 4, **kw}
    hyb = RingAttention(
        use_ring=True, auto_shard=True, mesh=mesh,
        sequence_parallel="hybrid", **common,
    )
    ref = RingAttention(
        use_ring=False, force_regular_attn=True,
        **{k: v for k, v in common.items()
           if k not in ("striped", "ring_bidirectional", "use_pallas")},
    )
    return hyb, ref


# ----------------------------------------------------------------------
# Module parity across every factoring
# ----------------------------------------------------------------------


@pytest.mark.parametrize("factoring", FACTORINGS, ids=lambda f: "x".join(map(str, f)))
@pytest.mark.parametrize("striped", [False, True])
def test_hybrid_module_parity(rng, meshes, factoring, striped):
    """Causal parity on every mesh factoring, odd length (auto-shard pad),
    striped (outer-ring stripe factor) and contiguous layouts."""
    hyb, ref = make_pair(meshes[factoring], causal=True, striped=striped)
    x = jnp.asarray(rng.standard_normal((2, 31, 32)), jnp.float32)
    params = ref.init(jax.random.PRNGKey(0), x)
    np.testing.assert_allclose(
        hyb.apply(params, x), ref.apply(params, x), atol=ATOL
    )


@pytest.mark.parametrize("factoring", FACTORINGS, ids=lambda f: "x".join(map(str, f)))
def test_hybrid_input_grads(rng, meshes, factoring):
    hyb, ref = make_pair(meshes[factoring], causal=True, striped=True)
    x = jnp.asarray(rng.standard_normal((2, 31, 32)), jnp.float32)
    params = ref.init(jax.random.PRNGKey(0), x)
    g_ref = jax.grad(lambda x: (ref.apply(params, x) ** 2).sum())(x)
    g_out = jax.grad(lambda x: (hyb.apply(params, x) ** 2).sum())(x)
    np.testing.assert_allclose(g_out, g_ref, atol=GRAD_ATOL)


@pytest.mark.slow
def test_hybrid_param_grads(rng, meshes):
    """Param-gradient parity: dk/dv must sum correctly back through the
    all-to-all transpose AND the ring's circulating dkv accumulators."""
    hyb, ref = make_pair(meshes[(1, 2, 4)], causal=True)
    x = jnp.asarray(rng.standard_normal((2, 32, 32)), jnp.float32)
    params = ref.init(jax.random.PRNGKey(0), x)
    g_ref = jax.grad(lambda p: (ref.apply(p, x) ** 2).sum())(params)
    g_out = jax.grad(lambda p: (hyb.apply(p, x) ** 2).sum())(params)
    for a, b in zip(jax.tree.leaves(g_out), jax.tree.leaves(g_ref)):
        np.testing.assert_allclose(a, b, atol=GRAD_ATOL)


# ----------------------------------------------------------------------
# GQA: divisible, small-hk (hk < ulysses), and unaligned head groups
# ----------------------------------------------------------------------


def test_hybrid_gqa_divisible(rng, meshes):
    """hk % ulysses == 0: the plain kv all-to-all leg."""
    hyb, ref = make_pair(meshes[(2, 2, 2)], causal=True, kv_heads=4,
                         striped=True)
    x = jnp.asarray(rng.standard_normal((2, 31, 32)), jnp.float32)
    params = ref.init(jax.random.PRNGKey(0), x)
    np.testing.assert_allclose(
        hyb.apply(params, x), ref.apply(params, x), atol=ATOL
    )


def test_hybrid_gqa_small_hk(rng, meshes):
    """kv_heads < ulysses_size: the real heads transfer once (all-gather)
    and the ring circulates one deduplicated head per device — outputs and
    param grads (summed over the copies) match the oracle."""
    hyb, ref = make_pair(meshes[(1, 4, 2)], causal=True, kv_heads=2,
                         striped=True)
    x = jnp.asarray(rng.standard_normal((2, 31, 32)), jnp.float32)
    params = ref.init(jax.random.PRNGKey(0), x)
    np.testing.assert_allclose(
        hyb.apply(params, x), ref.apply(params, x), atol=ATOL
    )
    g_ref = jax.grad(lambda p: (ref.apply(p, x) ** 2).sum())(params)
    g_out = jax.grad(lambda p: (hyb.apply(p, x) ** 2).sum())(params)
    for a, b in zip(jax.tree.leaves(g_out), jax.tree.leaves(g_ref)):
        np.testing.assert_allclose(a, b, atol=GRAD_ATOL)


def test_hybrid_gqa_unaligned(rng, meshes):
    """hk neither divides the axis nor aligns with the per-device head
    block (12 q heads / 3 kv heads over a 4-way ulysses axis): the
    per-query-head local copy fallback."""
    hyb, ref = make_pair(meshes[(1, 4, 2)], causal=True, heads=12,
                         kv_heads=3, dim=48, dim_head=4)
    x = jnp.asarray(rng.standard_normal((2, 31, 48)), jnp.float32)
    params = ref.init(jax.random.PRNGKey(0), x)
    np.testing.assert_allclose(
        hyb.apply(params, x), ref.apply(params, x), atol=ATOL
    )


# ----------------------------------------------------------------------
# Masks, packing, bidirectional streams, Pallas kernels
# ----------------------------------------------------------------------


def test_hybrid_kv_mask_tail(rng, meshes):
    """Non-causal with a key-padding mask whose tail is fully masked: the
    mask all-gathers over ulysses and rides the ring per hop."""
    hyb, ref = make_pair(meshes[(1, 2, 4)], causal=False)
    x = jnp.asarray(rng.standard_normal((2, 31, 32)), jnp.float32)
    mask = jnp.asarray(rng.random((2, 31)) > 0.3).at[:, -7:].set(False)
    params = ref.init(jax.random.PRNGKey(0), x, mask)
    np.testing.assert_allclose(
        hyb.apply(params, x, mask), ref.apply(params, x, mask), atol=ATOL
    )


@pytest.mark.parametrize("factoring", [(1, 2, 4), (1, 4, 2)],
                         ids=lambda f: "x".join(map(str, f)))
def test_hybrid_packed_segments(rng, meshes, factoring):
    """Packed segment ids: cross-document masking must survive the
    all-to-all resharding and the per-hop kv-id circulation."""
    hyb, ref = make_pair(meshes[factoring], causal=True, striped=True)
    x = jnp.asarray(rng.standard_normal((2, 31, 32)), jnp.float32)
    seg = jnp.asarray(np.sort(rng.integers(0, 4, (2, 31)), axis=1), jnp.int32)
    params = ref.init(jax.random.PRNGKey(0), x)
    np.testing.assert_allclose(
        hyb.apply(params, x, None, seg),
        ref.apply(params, x, None, seg),
        atol=ATOL,
    )


def test_hybrid_bidirectional(rng, meshes):
    """ring_bidirectional composes with the hybrid outer ring: the two KV
    half-streams circulate the sub-axis in opposite directions."""
    hyb, ref = make_pair(meshes[(1, 2, 4)], causal=True,
                         ring_bidirectional=True)
    x = jnp.asarray(rng.standard_normal((2, 32, 32)), jnp.float32)
    params = ref.init(jax.random.PRNGKey(0), x)
    np.testing.assert_allclose(
        hyb.apply(params, x), ref.apply(params, x), atol=ATOL
    )
    g_ref = jax.grad(lambda x: (ref.apply(params, x) ** 2).sum())(x)
    g_out = jax.grad(lambda x: (hyb.apply(params, x) ** 2).sum())(x)
    np.testing.assert_allclose(g_out, g_ref, atol=GRAD_ATOL)


@pytest.mark.parametrize("striped", [False, True])
def test_hybrid_lookback_window(rng, meshes, striped):
    """Sliding-window bands on the ring sub-axis: every offset (contiguous
    hop skip arithmetic AND the striped window floor) must derive from the
    OUTER axis size, not the global device count — exact in both layouts."""
    hyb, ref = make_pair(meshes[(1, 2, 4)], causal=True, striped=striped,
                         max_lookback_seq_len=7)
    x = jnp.asarray(rng.standard_normal((2, 32, 32)), jnp.float32)
    params = ref.init(jax.random.PRNGKey(0), x)
    np.testing.assert_allclose(
        hyb.apply(params, x), ref.apply(params, x), atol=ATOL
    )


@pytest.mark.slow
def test_hybrid_pallas(rng, meshes):
    """The Pallas per-hop kernels (interpret mode on CPU) under the hybrid
    composition."""
    hyb, ref = make_pair(meshes[(1, 2, 4)], causal=True, use_pallas=True)
    x = jnp.asarray(rng.standard_normal((2, 32, 32)), jnp.float32)
    params = ref.init(jax.random.PRNGKey(0), x)
    np.testing.assert_allclose(
        hyb.apply(params, x), ref.apply(params, x), atol=ATOL
    )


# ----------------------------------------------------------------------
# Functional core (no flax): direct shard_map over the factored mesh
# ----------------------------------------------------------------------


def test_hybrid_functional_core(rng, meshes):
    mesh = meshes[(1, 2, 4)]
    q = jnp.asarray(rng.standard_normal((2, 8, 64, 16)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((2, 8, 64, 16)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((2, 8, 64, 16)), jnp.float32)
    spec = P("data", None, ("ring", "ulysses"), None)
    out = shard_map(
        partial(
            hybrid_attention, kv_mask=None, ulysses_axis="ulysses",
            ring_axis="ring", causal=True, bucket_size=8,
        ),
        mesh=mesh, in_specs=(spec,) * 3, out_specs=spec,
    )(q, k, v)
    ref = default_attention(q, k, v, causal=True)
    np.testing.assert_allclose(out, ref, atol=ATOL)


# ----------------------------------------------------------------------
# End-to-end transformer: loss + layout agreement (rotary, striping,
# packing, loss sharding all on the factored axis)
# ----------------------------------------------------------------------


@pytest.mark.slow
def test_hybrid_transformer_loss(rng, meshes):
    mesh = meshes[(2, 2, 2)]
    common = dict(num_tokens=64, dim=32, depth=2, heads=4, dim_head=8,
                  causal=True, striped=True, bucket_size=4)
    hyb = RingTransformer(mesh=mesh, sequence_parallel="hybrid", **common)
    ref = RingTransformer(use_ring=False, force_regular_attn=True, **common)
    tokens = jnp.asarray(rng.integers(0, 64, (2, 33)), jnp.int32)
    seg = jnp.asarray(np.sort(rng.integers(0, 3, (2, 33)), axis=1), jnp.int32)
    params = ref.init(jax.random.PRNGKey(0), tokens)

    loss_h = hyb.apply(params, tokens, return_loss=True, segment_ids=seg)
    loss_r = ref.apply(params, tokens, return_loss=True, segment_ids=seg)
    np.testing.assert_allclose(loss_h, loss_r, atol=ATOL)

    g_h = jax.grad(
        lambda p: hyb.apply(p, tokens, return_loss=True, segment_ids=seg)
    )(params)
    g_r = jax.grad(
        lambda p: ref.apply(p, tokens, return_loss=True, segment_ids=seg)
    )(params)
    for a, b in zip(jax.tree.leaves(g_h), jax.tree.leaves(g_r)):
        np.testing.assert_allclose(a, b, atol=GRAD_ATOL)


@pytest.mark.slow
def test_hybrid_transformer_chunked_ce(rng, meshes):
    """The chunked-CE path scans within the shards of the factored striped
    layout (labels permuted by the outer ring degree, shard axis over
    both mesh axes): loss must match the dense CE bit-for-bit in f32
    math."""
    mesh = meshes[(1, 2, 4)]
    common = dict(num_tokens=64, dim=32, depth=1, heads=8, dim_head=4,
                  causal=True, striped=True, bucket_size=4)
    dense = RingTransformer(mesh=mesh, sequence_parallel="hybrid", **common)
    chunked = RingTransformer(mesh=mesh, sequence_parallel="hybrid",
                              loss_chunk_size=8, **common)
    tokens = jnp.asarray(rng.integers(0, 64, (2, 33)), jnp.int32)
    params = dense.init(jax.random.PRNGKey(0), tokens)
    np.testing.assert_allclose(
        chunked.apply(params, tokens, return_loss=True),
        dense.apply(params, tokens, return_loss=True),
        atol=ATOL,
    )


# ----------------------------------------------------------------------
# Mesh helpers + strategy/mesh validation
# ----------------------------------------------------------------------


def test_factored_mesh_helpers(meshes):
    mesh = meshes[(1, 2, 4)]
    assert seq_axes(mesh) == ("ring", "ulysses")
    assert seq_world(mesh) == 8
    plain = create_mesh(ring_size=8)
    assert seq_axes(plain) == ("seq",)
    assert seq_world(plain) == 8


def test_shard_batch_factored(meshes):
    """shard_batch places (b, n) arrays ring-major / ulysses-minor: device
    (u, r) must hold subchunk u of contiguous ring chunk r."""
    mesh = meshes[(1, 2, 4)]
    batch = np.arange(2 * 16, dtype=np.int32).reshape(2, 16)
    arr = shard_batch(batch, mesh)
    np.testing.assert_array_equal(np.asarray(arr), batch)
    for shard in arr.addressable_shards:
        d, r, u = np.argwhere(
            np.vectorize(lambda dev: dev == shard.device)(mesh.devices)
        )[0]
        chunk = (r * mesh.shape["ulysses"] + u) * 2
        np.testing.assert_array_equal(
            np.asarray(shard.data), batch[:, chunk:chunk + 2]
        )


def test_hybrid_requires_factored_mesh(rng, meshes):
    x = jnp.asarray(rng.standard_normal((2, 32, 32)), jnp.float32)
    bad = RingAttention(dim=32, heads=8, dim_head=8, causal=True,
                        use_ring=True, auto_shard=True,
                        mesh=create_mesh(ring_size=8),
                        sequence_parallel="hybrid")
    with pytest.raises(ValueError, match="factored mesh"):
        bad.init(jax.random.PRNGKey(0), x)
    bad = RingAttention(dim=32, heads=8, dim_head=8, causal=True,
                        use_ring=True, auto_shard=True,
                        mesh=meshes[(1, 2, 4)], sequence_parallel="ring")
    with pytest.raises(ValueError, match="plain"):
        bad.init(jax.random.PRNGKey(0), x)
    # transformer-level mismatch must surface the same actionable error,
    # not a bare KeyError from the striped-layout factor derivation
    bad_t = RingTransformer(num_tokens=64, dim=32, depth=1, heads=8,
                            dim_head=4, causal=True, striped=True,
                            mesh=create_mesh(ring_size=8),
                            sequence_parallel="hybrid")
    with pytest.raises(ValueError, match="factored mesh"):
        bad_t.init(jax.random.PRNGKey(0),
                   jnp.zeros((2, 32), jnp.int32))


# ----------------------------------------------------------------------
# The acceptance check: ring hops shrink by the ulysses degree and never
# cross the ulysses axis
# ----------------------------------------------------------------------


def test_hybrid_hlo_hop_count(rng, meshes):
    """Optimized-HLO pin of the tentpole claim: at equal world size (8),
    the hybrid step's ring collective-permutes (the unrolled Pallas hop
    loop makes each hop a separate instruction) number ``ring_size - 1``
    — ulysses_size x fewer than the pure ring's ``world - 1`` — and every
    source->target pair keeps the ulysses coordinate fixed (the ring rides
    ONLY the outer axis; the inner axis sees all-to-alls, not permutes).

    Expectations and the pair-axis rule both come from the shared contract
    checker (``analysis/contracts.py``): this pin holds the *module-level*
    (flax, auto_shard) HLO to the same table the functional-core suite and
    ``tools/check_contracts.py`` enforce, so they cannot drift apart."""
    from ring_attention_tpu.analysis import contracts

    ulysses = 2
    hyb, _ = make_pair(meshes[(1, 2, 4)], causal=True, use_pallas=True,
                       bucket_size=8)
    ring = RingAttention(
        dim=32, heads=8, dim_head=8, bucket_size=8, causal=True,
        use_ring=True, auto_shard=True, use_pallas=True,
        mesh=create_mesh(ring_size=8), sequence_parallel="ring",
    )
    x = jnp.asarray(rng.standard_normal((1, 64, 32)), jnp.float32)
    params = ring.init(jax.random.PRNGKey(0), x)

    def compiled(mod):
        return jax.jit(
            lambda p, x: mod.apply(p, x)
        ).lower(params, x).compile().as_text()

    hops_hybrid = contracts.hlo_ppermute_pairs(compiled(hyb))
    hops_ring = contracts.hlo_ppermute_pairs(compiled(ring))

    # hop-count expectations from the ONE declarative table
    hyb_dims = {"data": 1, "ring": 4, "ulysses": ulysses, "world": 8,
                "passes": 4}
    ring_dims = {"data": 1, "ring": 8, "ulysses": 1, "world": 8, "passes": 8}
    want_hybrid = contracts.expected_counts(
        "hybrid", "fwd", hyb_dims)["collective-permute"]
    want_ring = contracts.expected_counts(
        "ring", "fwd", ring_dims)["collective-permute"]
    assert len(hops_ring) == want_ring == 8 - 1, len(hops_ring)
    assert len(hops_hybrid) == want_hybrid == (8 // ulysses) - 1, (
        len(hops_hybrid)
    )
    assert len(hops_hybrid) * ulysses < len(hops_ring) + ulysses

    # ring permutes must keep every non-ring mesh coordinate fixed — the
    # checker's axis rule on the (data=1, ring=4, ulysses=2) mesh
    violations = contracts.check_pairs_axis(
        hops_hybrid, mesh_shape=(1, 4, 2), axis_index=1, axis_name="ring",
    )
    assert not violations, "\n".join(violations)
