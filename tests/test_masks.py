"""Certified mask algebra (PR 11), tier-1.

Four layers:

  - **algebra semantics**: oracles and the exact tile classifier agree
    elementwise over fuzzed compositions; the mini-language round-trips
    and lists its registry on unknown names.
  - **certification**: certificates cache (memory + disk, keyed by
    mask x geometry), cap their elementwise proof at
    ``CERT_ELEMENTWISE_MAX``, and NEGATIVE toys prove the certifier is
    live — a corrupted lowering (window off by one tile) fails with a
    one-line diagnostic naming the mask, hop, and tile.
  - **execution**: ``mask=`` through ``ops.attention`` / RingAttention /
    RingTransformer matches the legacy knobs and the dense oracle on
    both kernel paths, including the in-kernel fallbacks (misaligned
    ``doc_starts``, non-divisor window) pinned bit-consistent with the
    oracle's masking decisions.
  - **scale**: the certified sliding-window grid at 262k is strictly
    smaller than causal.
"""


import jax
import jax.numpy as jnp
import numpy as np
import pytest

import ring_attention_tpu as rat
from ring_attention_tpu import masks as M
from ring_attention_tpu.analysis import coverage
from ring_attention_tpu.ops import attention, default_attention

ATOL = 3e-5


# ----------------------------------------------------------------------
# Algebra semantics
# ----------------------------------------------------------------------


def _rand_mask(rng, depth=0):
    roll = rng.random()
    if depth < 2 and roll < 0.35:
        kind = rng.integers(0, 3)
        if kind == 0:
            return M.And((_rand_mask(rng, depth + 1),
                          _rand_mask(rng, depth + 1)))
        if kind == 1:
            return M.Or((_rand_mask(rng, depth + 1),
                         _rand_mask(rng, depth + 1)))
        return M.Not(_rand_mask(rng, depth + 1))
    kind = rng.integers(0, 6)
    if kind == 0:
        return M.Causal()
    if kind == 1:
        return M.Full()
    if kind == 2:
        return M.SlidingWindow(int(rng.integers(1, 40)))
    if kind == 3:
        return M.PrefixLM(int(rng.integers(0, 40)))
    if kind == 4:
        s = int(rng.integers(1, 6))
        return M.Dilated(s, int(rng.integers(0, s)))
    cuts = sorted({0, *(int(x) for x in rng.integers(1, 64, 2))})
    return M.DocumentMask(tuple(cuts))


def test_tile_status_matches_oracle_fuzz():
    """The exact tile classifier (every lowering's source of truth) is
    held elementwise to the oracle over fuzzed masks x tiles."""
    rng = np.random.default_rng(0xA1)
    for _ in range(120):
        mask = _rand_mask(rng)
        qlo = int(rng.integers(0, 60))
        klo = int(rng.integers(0, 60))
        qhi = qlo + int(rng.integers(0, 12))
        khi = klo + int(rng.integers(0, 12))
        any_live, all_live = mask.tile_status(qlo, qhi, klo, khi)
        o = mask.oracle(np.arange(qlo, qhi + 1), np.arange(klo, khi + 1))
        assert (any_live, all_live) == (bool(o.any()), bool(o.all())), (
            mask.key, (qlo, qhi, klo, khi)
        )


def test_oracle_compositions():
    q = np.arange(16)
    cw = M.Causal() & M.SlidingWindow(4)
    o = cw.oracle(q, q)
    d = q[None, :] - q[:, None]
    np.testing.assert_array_equal(o, (d <= 0) & (d > -4))
    p = M.PrefixLM(5).oracle(q, q)
    np.testing.assert_array_equal(p, (q[None, :] < 5) | (d <= 0))
    ph = M.PerHead((M.Causal(), M.Full()))
    assert ph.per_head
    np.testing.assert_array_equal(ph.oracle(q, q, head=0), d <= 0)
    assert ph.oracle(q, q, head=1).all()
    assert ph.oracle(q, q, head=2).sum() == (d <= 0).sum()  # wraps


def test_parse_round_trip_and_registry():
    for expr in ("causal", "causal&window:512", "prefix:128|docs:0,64",
                 "causal&~window:8", "perhead(causal;causal&window:64)",
                 "(causal|full)&dilated:4+1", "segments&causal"):
        mask = M.parse_mask(expr)
        assert M.parse_mask(mask.key).key == mask.key, expr
    with pytest.raises(M.MaskParseError, match="registry"):
        M.parse_mask("bogus:3")
    with pytest.raises(M.MaskParseError, match="window needs"):
        M.parse_mask("window")
    with pytest.raises(M.MaskParseError):
        M.parse_mask("causal&&window:4")


def test_kernel_form_mapping():
    assert M.kernel_form(M.Causal()) == M.KernelForm(causal=True)
    assert M.kernel_form(M.Causal() & M.SlidingWindow(512)) == M.KernelForm(
        causal=True, window=512
    )
    assert M.kernel_form(M.Full()) == M.KernelForm()
    form = M.kernel_form(
        M.Causal() & M.DocumentMask((0, 16)) & M.Segments()
    )
    assert form.causal and form.doc_starts == (0, 16)
    assert form.needs_segment_ids
    for bad in (M.PrefixLM(8), M.Dilated(4), M.SlidingWindow(8),
                M.Causal() | M.Full(), ~M.Causal()):
        with pytest.raises(M.MaskLoweringError,
                           match="certifies and lowers to grids"):
            M.kernel_form(bad)


def test_band_form():
    assert M.band_form(M.Causal()) == (0, None)
    assert M.band_form(M.SlidingWindow(8)) == (7, -7)
    assert M.band_form(M.Causal() & M.SlidingWindow(8)) == (0, -7)
    assert M.band_form(M.PrefixLM(4)) is None
    assert M.band_form(M.Full()) == (None, None)


# ----------------------------------------------------------------------
# Certification: cache + negative toys
# ----------------------------------------------------------------------


def _ring_spec(**kw):
    base = dict(strategy="ring", ring=4, n_local=16, block_q=4, block_k=4)
    base.update(kw)
    return M.GridSpec(**base)


def test_certificate_memo_and_disk_cache(tmp_path, monkeypatch):
    mask = M.Causal() & M.SlidingWindow(24)
    spec = _ring_spec()
    monkeypatch.setenv("RING_ATTN_CERT_CACHE", str(tmp_path))
    M._CERT_MEMO.clear()
    c1 = M.certify(mask, spec)
    assert c1.ok and c1.tiles > 0
    files = list(tmp_path.glob("*.json"))
    assert len(files) == 1  # the proof landed on disk
    # a fresh process (cleared memo) loads the disk certificate
    M._CERT_MEMO.clear()
    c2 = M.certify(mask, spec)
    assert c2.ok and (c2.tiles, c2.work, c2.edge) == (
        c1.tiles, c1.work, c1.edge
    )
    # a corrupt cache entry is ignored, not fatal
    files[0].write_text("{broken")
    M._CERT_MEMO.clear()
    assert M.certify(mask, spec).ok


def test_certificate_elementwise_cap():
    """262k-scale certificates cap the elementwise proof and still run
    the closed-form-vs-enumeration accounting at the full shape."""
    spec = M.GridSpec(strategy="single", n_local=1 << 18, block_q=1024,
                      block_k=1024)
    cert = M.certify(M.Causal() & M.SlidingWindow(4096), spec,
                     use_cache=False)
    assert cert.ok and cert.proof_n == M.CERT_ELEMENTWISE_MAX


def test_corrupted_window_lowering_fails_naming_mask_hop_tile():
    """Acceptance negative toy: a window lowering off by one TILE (the
    band table built one block narrower than the mask) fails soundness
    with a one-line diagnostic naming the mask, hop, and tile."""
    from ring_attention_tpu.ops.pallas_flash import band_plan

    mask = M.Causal() & M.SlidingWindow(24)
    spec = _ring_spec()
    low = M.lower(mask, spec)
    # hop 1: the window's lower boundary cuts through the local span
    # (hop 0's window covers the whole span, so nothing would drop)
    hop = low.hops[1]
    hi, _, lo, _ = hop.plan.hint
    b = spec.block_q
    # off-by-one-tile: the table believes the window starts a block later
    bad = band_plan((spec.n_local, spec.n_local), (b, b),
                    (hi, hi, lo + b, lo + b), windowed=True)
    hop.plan = bad
    report = coverage.prove_mask_lowering(mask, spec, lowering=low)
    assert not report.ok
    line = report.violations[0]
    assert "\n" not in line
    assert mask.key in line and f"hop{hop.hop}" in line and "tile" in line
    assert "tile-coverage-sound" in line or "tile-count" in line


def test_widened_lowering_fails_tightness():
    """The dual toy: a table one block WIDER than the window visits dead
    tiles — flagged by the tightness rule, naming the tile."""
    from ring_attention_tpu.ops.pallas_flash import band_plan

    mask = M.Causal() & M.SlidingWindow(24)
    spec = M.GridSpec(strategy="single", n_local=64, block_q=8, block_k=8)
    low = M.lower(mask, spec)
    hop = low.hops[0]
    hi, _, lo, _ = hop.plan.hint
    b = spec.block_q
    wide = band_plan((64, 64), (b, b), (hi, hi, lo - 2 * b, lo - 2 * b),
                     windowed=True)
    hop.plan = hop.plan_kmajor = wide
    report = coverage.prove_mask_lowering(mask, spec, lowering=low)
    assert not report.ok
    assert any("tile-coverage-tight" in v and "tile" in v
               for v in report.violations)


def test_require_certified_raises_one_line(monkeypatch):
    mask = M.Causal() & M.SlidingWindow(24)
    spec = _ring_spec()
    real_lower = M.lower

    def corrupt_lower(m, s):
        from ring_attention_tpu.ops.pallas_flash import band_plan

        low = real_lower(m, s)
        hop = low.hops[1]  # see the corrupted-window toy above
        hi, _, lo, _ = hop.plan.hint
        b = s.block_q
        hop.plan = band_plan((s.n_local, s.n_local), (b, b),
                             (hi, hi, lo + b, lo + b), windowed=True)
        return low

    monkeypatch.setattr(M, "lower", corrupt_lower)
    with pytest.raises(M.MaskCertificationError) as e:
        M.require_certified(mask, spec, use_cache=False)
    assert "\n" not in str(e.value)
    assert mask.key in str(e.value)


def test_hop_pairing_disagreement_is_a_violation():
    """The certifier recomputes the hop schedule independently; a
    lowering that pairs the wrong origins is caught even when its own
    tables are self-consistent."""
    mask = M.Causal()
    spec = _ring_spec()
    low = M.lower(mask, spec)
    low.hops[2].ranks[1].kv_origin = (
        low.hops[2].ranks[1].kv_origin + 1
    ) % spec.ring
    report = coverage.prove_mask_lowering(mask, spec, lowering=low)
    assert any("pairing disagrees" in v for v in report.violations)


# ----------------------------------------------------------------------
# Execution: mask= through the entry points
# ----------------------------------------------------------------------


def _qkv(rng, b=1, h=4, n=64, d=8, hk=None):
    mk = lambda heads: jnp.asarray(
        rng.standard_normal((b, heads, n, d)), jnp.float32
    )
    return mk(h), mk(hk or h), mk(hk or h)


def _dense_reference(q, k, v, mask):
    """Independent dense oracle: materialize the mask's oracle and
    softmax in f32 — no shared code with the flash paths."""
    from ring_attention_tpu.ops.attention import MASK_VALUE

    b, h, n, d = q.shape
    hk = k.shape[1]
    g = h // hk
    keep = M.dense_mask(mask, n, n, heads=h)
    if keep.ndim == 2:
        keep = np.broadcast_to(keep, (h, n, n))
    s = jnp.einsum(
        "bhid,bhjd->bhij", q.astype(jnp.float32),
        jnp.repeat(k, g, axis=1).astype(jnp.float32),
    ) * (d ** -0.5)
    s = jnp.where(jnp.asarray(keep)[None], s, MASK_VALUE)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum(
        "bhij,bhjd->bhid", p, jnp.repeat(v, g, axis=1).astype(jnp.float32)
    ).astype(q.dtype)


def test_ops_attention_mask_matches_legacy_knobs():
    rng = np.random.default_rng(1)
    q, k, v = _qkv(rng)
    out_m = attention(q, k, v, mask=M.Causal() & M.SlidingWindow(16),
                      impl="xla", bucket_size=8)
    out_l = attention(q, k, v, causal=True, window=16, impl="xla",
                      bucket_size=8)
    np.testing.assert_allclose(out_m, out_l, atol=1e-6)
    np.testing.assert_allclose(
        out_m, _dense_reference(q, k, v, M.Causal() & M.SlidingWindow(16)),
        atol=ATOL,
    )


def test_ops_attention_mask_conflicts_and_unlowered():
    rng = np.random.default_rng(2)
    q, k, v = _qkv(rng, n=16)
    with pytest.raises(ValueError, match="subsumes"):
        attention(q, k, v, mask=M.Causal(), causal=True)
    with pytest.raises(M.MaskLoweringError, match="kernels speak"):
        attention(q, k, v, mask=M.PrefixLM(4))
    with pytest.raises(ValueError, match="segment_ids"):
        attention(q, k, v, mask=M.Causal() & M.Segments())
    with pytest.raises(ValueError, match="doc_starts"):
        attention(q, k, v, mask=M.Causal() & M.DocumentMask((0, 8)),
                  doc_starts=(0, 8))


def test_misaligned_docs_fallback_parity_both_paths():
    """Satellite pin: a mask whose lowering falls back to in-kernel
    masking (misaligned doc_starts) is bit-consistent with the dense
    oracle on BOTH paths — cross-document values cannot influence the
    output AT ALL (outputs bit-identical under cross-document value
    perturbation), and the kept attention matches the oracle."""
    rng = np.random.default_rng(3)
    q, k, v = _qkv(rng, n=64)
    mask = M.Causal() & M.DocumentMask((0, 13, 41))  # 13: misaligned
    ids = np.zeros(64, np.int32)
    ids[13:] = 1
    ids[41:] = 2
    for impl in ("xla", "pallas"):
        kw = dict(impl=impl, bucket_size=8)
        if impl == "pallas":
            kw["interpret"] = True
        out = attention(q, k, v, mask=mask, **kw)
        np.testing.assert_allclose(
            out, _dense_reference(q, k, v, mask), atol=ATOL,
            err_msg=impl,
        )
        # bit-consistency of the masking decision: scrambling every
        # OTHER document's k/v rows leaves document-0 queries untouched
        scr = np.asarray(v).copy()
        scr[:, :, 13:] = rng.standard_normal(scr[:, :, 13:].shape)
        k_scr = np.asarray(k).copy()
        k_scr[:, :, 13:] = rng.standard_normal(k_scr[:, :, 13:].shape)
        out_scr = attention(q, jnp.asarray(k_scr), jnp.asarray(scr),
                            mask=mask, **kw)
        np.testing.assert_array_equal(
            np.asarray(out)[:, :, :13], np.asarray(out_scr)[:, :, :13],
            err_msg=f"{impl}: cross-document leak",
        )


def test_nondivisor_window_fallback_parity_both_paths():
    """Satellite pin, window half: a window that divides neither the
    bucket nor the block (w=11 at bucket 8) masks in-kernel; both paths
    match the dense oracle."""
    rng = np.random.default_rng(4)
    q, k, v = _qkv(rng, n=48)
    mask = M.Causal() & M.SlidingWindow(11)
    ref = _dense_reference(q, k, v, mask)
    out_x = attention(q, k, v, mask=mask, impl="xla", bucket_size=8)
    np.testing.assert_allclose(out_x, ref, atol=ATOL)
    out_p = attention(q, k, v, mask=mask, impl="pallas", interpret=True)
    np.testing.assert_allclose(out_p, ref, atol=ATOL)


@pytest.fixture(scope="module")
def mesh():
    return rat.create_mesh(ring_size=8)


def test_ring_attention_mask_sugar(mesh):
    """causal=True is sugar for mask=Causal() across strategies, and a
    DocumentMask lowers onto the proven segment-id ring machinery."""
    rng = np.random.default_rng(5)
    h = 4
    common = dict(dim=h * 8, heads=h, dim_head=8, bucket_size=8)
    x = jnp.asarray(rng.standard_normal((1, 63, h * 8)), jnp.float32)
    legacy = rat.RingAttention(
        use_ring=True, auto_shard=True, mesh=mesh, causal=True,
        max_lookback_seq_len=16, **common,
    )
    params = legacy.init(jax.random.PRNGKey(0), x)
    sugar = rat.RingAttention(
        use_ring=True, auto_shard=True, mesh=mesh,
        mask=M.Causal() & M.SlidingWindow(16), **common,
    )
    np.testing.assert_allclose(
        sugar.apply(params, x), legacy.apply(params, x), atol=1e-6
    )
    # counter-rotated + striped geometry under mask=
    c_legacy = rat.RingAttention(
        use_ring=True, auto_shard=True, mesh=mesh, striped=True,
        ring_counter_rotate=True, causal=True, max_lookback_seq_len=24,
        **common,
    )
    c_sugar = rat.RingAttention(
        use_ring=True, auto_shard=True, mesh=mesh, striped=True,
        ring_counter_rotate=True,
        mask=M.Causal() & M.SlidingWindow(24), **common,
    )
    np.testing.assert_allclose(
        c_sugar.apply(params, x), c_legacy.apply(params, x), atol=1e-6
    )
    # document mask -> segment-id machinery, vs the per-document oracle
    doc = rat.RingAttention(
        use_ring=True, auto_shard=True, mesh=mesh,
        mask=M.Causal() & M.DocumentMask((0, 20, 41)), **common,
    )
    oracle = rat.RingAttention(
        use_ring=False, force_regular_attn=True, causal=True, **common,
    )
    ids = np.zeros(63, np.int32)
    ids[20:] = 1
    ids[41:] = 2
    seg = jnp.asarray(np.broadcast_to(ids, (1, 63)).copy())
    np.testing.assert_allclose(
        doc.apply(params, x), oracle.apply(params, x, None, seg),
        atol=ATOL,
    )


def test_ring_attention_mask_conflicts(mesh):
    rng = np.random.default_rng(6)
    h = 4
    common = dict(dim=h * 8, heads=h, dim_head=8, bucket_size=8)
    x = jnp.asarray(rng.standard_normal((1, 16, h * 8)), jnp.float32)
    oracle = rat.RingAttention(use_ring=False, causal=True, **common)
    params = oracle.init(jax.random.PRNGKey(0), x)
    with pytest.raises(ValueError, match="sugar for mask=Causal"):
        rat.RingAttention(
            use_ring=False, causal=True, mask=M.Causal(), **common
        ).apply(params, x)
    with pytest.raises(ValueError, match="SlidingWindow"):
        rat.RingAttention(
            use_ring=False, max_lookback_seq_len=8, mask=M.Causal(),
            **common,
        ).apply(params, x)
    with pytest.raises(M.MaskLoweringError):
        rat.RingAttention(
            use_ring=False, mask=M.Dilated(2), **common
        ).apply(params, x)
    with pytest.raises(ValueError, match="Segments"):
        rat.RingAttention(
            use_ring=False, mask=M.Causal() & M.Segments(), **common
        ).apply(params, x)


def test_transformer_mask_per_layer(mesh):
    """A per-layer mask tuple (local window below a global layer)
    matches the equivalent per-layer lookback tuple."""
    rng = np.random.default_rng(7)
    toks = jnp.asarray(rng.integers(0, 64, (1, 33)), jnp.int32)
    common = dict(num_tokens=64, dim=32, depth=2, heads=4, dim_head=8,
                  bucket_size=8, mesh=mesh)
    legacy = rat.RingTransformer(
        causal=True, max_lookback_seq_len=(16, None), **common
    )
    params = legacy.init(jax.random.PRNGKey(0), toks)
    sugar = rat.RingTransformer(
        mask=(M.Causal() & M.SlidingWindow(16), M.Causal()), **common
    )
    np.testing.assert_allclose(
        sugar.apply(params, toks), legacy.apply(params, toks), atol=1e-5
    )
    with pytest.raises(ValueError, match="mask tuple"):
        rat.RingTransformer(mask=(M.Causal(),), **common).init(
            jax.random.PRNGKey(0), toks
        )


# ----------------------------------------------------------------------
# Scale: the 262k certified tile accounting
# ----------------------------------------------------------------------


def test_window_262k_strictly_smaller_certified_grid():
    spec = M.GridSpec(strategy="single", n_local=1 << 18, block_q=1024,
                      block_k=1024)
    wmask = M.Causal() & M.SlidingWindow(4096)
    assert M.certify(wmask, spec, use_cache=False).ok
    assert M.certify(M.Causal(), spec, use_cache=False).ok
    w = sum(h.plan.work_tiles for h in M.lower(wmask, spec).hops)
    c = sum(h.plan.work_tiles for h in M.lower(M.Causal(), spec).hops)
    assert w < c  # the raw-speed claim, CPU-countable
    assert c / w > 10


def test_segments_mask_executes_and_certifies():
    """Review pin: the documented ``... & Segments()`` form works end to
    end — the runtime leaf drops out of the static grids
    (``static_mask``), certification proves the remaining conjunction,
    and execution masks through the segment_ids path."""
    assert M.static_mask(M.Causal() & M.Segments()).key == "causal"
    assert M.static_mask(M.Segments()).key == "full"
    cert = M.certify(M.Causal() & M.Segments(),
                     M.GridSpec(strategy="single", n_local=64,
                                block_q=8, block_k=8), use_cache=False)
    assert cert.ok
    rng = np.random.default_rng(11)
    q, k, v = _qkv(rng, n=48)
    ids = np.zeros(48, np.int32)
    ids[20:] = 1
    seg = jnp.asarray(np.broadcast_to(ids, (1, 48)).copy())
    out = attention(q, k, v, mask=M.Causal() & M.Segments(),
                    segment_ids=seg, impl="xla", bucket_size=8)
    ref = attention(q, k, v, causal=True, segment_ids=seg, impl="xla",
                    bucket_size=8)
    np.testing.assert_allclose(out, ref, atol=1e-6)


def test_perhead_inside_combinator_certifies_every_head():
    """Review pin: PerHead nested under a combinator enumerates EVERY
    distinct head variant (lcm period), not just head 0 — and the
    coverage row machinery accepts the composition."""
    mask = M.PerHead((M.Causal(), M.Full())) & M.SlidingWindow(8)
    assert mask.head_period == 2
    spec = M.GridSpec(strategy="single", n_local=32, block_q=8, block_k=8)
    cert = M.certify(mask, spec, use_cache=False)
    assert cert.ok
    # head variants genuinely differ, so proving both must cost more
    # tiles than proving either alone
    solo = M.certify(M.Causal() & M.SlidingWindow(8), spec,
                     use_cache=False)
    assert cert.tiles > solo.tiles
    report = coverage.prove_mask_case(coverage.MaskCoverageCase(
        name="toy", expr="perhead(causal;full)&window:8",
        n_local=32, block=8,
    ))
    assert report.ok, "\n".join(report.violations)


def test_malformed_inputs_raise_at_api_boundary_with_mask():
    """Review pin: a malformed q with a mask expression still gets the
    one-line check_attention_args ValueError, not an IndexError from
    mask resolution."""
    bad = jnp.zeros((2, 8, 4))  # 3-D
    with pytest.raises(ValueError, match="attention"):
        attention(bad, bad, bad, mask=M.Causal())


def test_spec_for_call_mapping():
    s = M.spec_for_call("ring", n=128, ring=8, striped=True)
    assert (s.strategy, s.layout, s.ring, s.n_local) == (
        "ring", "striped", 8, 16
    )
    assert M.spec_for_call("ulysses", n=128, ring=8).strategy == "single"
    assert M.spec_for_call("hybrid", n=128, ring=4).strategy == "ring"
    assert M.spec_for_call("ring", n=128, ring=1).strategy == "single"
    with pytest.raises(ValueError, match="unknown strategy"):
        M.spec_for_call("warp", n=128)
