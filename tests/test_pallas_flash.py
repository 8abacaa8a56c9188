"""Parity: Pallas flash kernels (interpret mode on CPU) vs the oracle.

The kernels are exercised through the same contract as the XLA blockwise
path: forward outputs, lse, partial merging, and the one-pass backward must
match ``default_attention`` and its autodiff gradients.  On CPU the kernels
run in Pallas interpreter mode; identical code compiles to Mosaic on TPU.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ring_attention_tpu.ops import default_attention
from ring_attention_tpu.ops.pallas_flash import (
    finalize_partials,
    merge_partials,
    pallas_flash_attention,
    pallas_flash_partials,
)

ATOL = 2e-5
GRAD_ATOL = 5e-4


def make_qkv(rng, b=2, h=4, hk=None, n=128, d=32):
    hk = hk or h
    q = jnp.asarray(rng.standard_normal((b, h, n, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, hk, n, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, hk, n, d)), jnp.float32)
    return q, k, v


@pytest.mark.parametrize("causal", [False, True])
def test_fwd_parity(rng, causal):
    q, k, v = make_qkv(rng)
    ref = default_attention(q, k, v, causal=causal)
    out = pallas_flash_attention(q, k, v, causal=causal, interpret=True)
    np.testing.assert_allclose(out, ref, atol=ATOL)


def test_fwd_gqa(rng):
    q, k, v = make_qkv(rng, h=4, hk=2)
    ref = default_attention(q, k, v, causal=True)
    out = pallas_flash_attention(q, k, v, causal=True, interpret=True)
    np.testing.assert_allclose(out, ref, atol=ATOL)


def test_head_chunked_launch_bit_exact(rng):
    """head_chunks splits the launch into per-head-group programs (a
    program-size escape hatch); heads are independent,
    so outputs AND grads must be bit-identical to the unsplit launch."""
    q, k, v = make_qkv(rng, h=8, hk=4)

    def loss(q, k, v, hc):
        out = pallas_flash_attention(
            q, k, v, causal=True, head_chunks=hc, interpret=True
        )
        return (out * out).sum(), out

    (ref_l, ref_out), ref_grads = jax.value_and_grad(
        loss, argnums=(0, 1, 2), has_aux=True)(q, k, v, None)
    (spl_l, spl_out), spl_grads = jax.value_and_grad(
        loss, argnums=(0, 1, 2), has_aux=True)(q, k, v, 4)
    np.testing.assert_array_equal(spl_out, ref_out)
    for g_ref, g_spl in zip(ref_grads, spl_grads):
        np.testing.assert_array_equal(g_spl, g_ref)

    with pytest.raises(ValueError):
        pallas_flash_attention(
            q, k, v, causal=True, head_chunks=3, interpret=True
        )


def test_fwd_mask(rng):
    q, k, v = make_qkv(rng)
    mask = jnp.asarray(rng.random((2, 128)) > 0.3)
    ref = default_attention(q, k, v, mask)
    out = pallas_flash_attention(q, k, v, mask, interpret=True)
    np.testing.assert_allclose(out, ref, atol=ATOL)


def test_fwd_softclamp(rng):
    q, k, v = make_qkv(rng)
    ref = default_attention(q, k, v, causal=True, softclamp_value=5.0)
    out = pallas_flash_attention(
        q, k, v, causal=True, softclamp_value=5.0, interpret=True
    )
    np.testing.assert_allclose(out, ref, atol=ATOL)


def test_fwd_window(rng):
    q, k, v = make_qkv(rng)
    n, w = 128, 48
    out = pallas_flash_attention(q, k, v, causal=True, window=w, interpret=True)
    i = jnp.arange(n)[:, None]
    j = jnp.arange(n)[None, :]
    band = (j <= i) & (j >= i - (w - 1))
    s = jnp.einsum("bhid,bhjd->bhij", q, k) * (q.shape[-1] ** -0.5)
    ref = jnp.einsum(
        "bhij,bhjd->bhid", jax.nn.softmax(jnp.where(band, s, -1e30), -1), v
    )
    np.testing.assert_allclose(out, ref, atol=ATOL)


def test_partials_merge(rng):
    """Two half-KV sweeps merged == one full sweep (the ring-hop contract)."""
    q, k, v = make_qkv(rng)
    scale = q.shape[-1] ** -0.5
    full = pallas_flash_partials(q, k, v, scale=scale, interpret=True)
    left = pallas_flash_partials(
        q, k[:, :, :64], v[:, :, :64], scale=scale, interpret=True
    )
    right = pallas_flash_partials(
        q, k[:, :, 64:], v[:, :, 64:], scale=scale, interpret=True
    )
    merged = merge_partials(left, right)
    out_full, lse_full = finalize_partials(full)
    out_merged, lse_merged = finalize_partials(merged)
    np.testing.assert_allclose(out_merged, out_full, atol=ATOL)
    np.testing.assert_allclose(lse_merged, lse_full, atol=ATOL)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("hk", [4, 2])
def test_grad_parity(rng, causal, hk):
    q, k, v = make_qkv(rng, hk=hk)

    g_ref = jax.grad(
        lambda *a: (default_attention(*a, causal=causal) ** 2).sum(), (0, 1, 2)
    )(q, k, v)
    g_out = jax.grad(
        lambda *a: (
            pallas_flash_attention(*a, causal=causal, interpret=True) ** 2
        ).sum(),
        (0, 1, 2),
    )(q, k, v)
    for a, b, name in zip(g_out, g_ref, "qkv"):
        np.testing.assert_allclose(a, b, atol=GRAD_ATOL, err_msg=f"d{name}")


def test_grad_softclamp_mask(rng):
    q, k, v = make_qkv(rng)
    mask = jnp.asarray(rng.random((2, 128)) > 0.3)

    g_ref = jax.grad(
        lambda *a: (default_attention(*a, softclamp_value=5.0) ** 2).sum(),
        (0, 1, 2),
    )(q, k, v, mask)
    g_out = jax.grad(
        lambda *a: (
            pallas_flash_attention(
                *a, softclamp_value=5.0, interpret=True
            )
            ** 2
        ).sum(),
        (0, 1, 2),
    )(q, k, v, mask)
    for a, b, name in zip(g_out, g_ref, "qkv"):
        np.testing.assert_allclose(a, b, atol=GRAD_ATOL, err_msg=f"d{name}")


def test_wide_head_dim(rng):
    """dim_head=128 (full lane width) through fwd and bwd kernels."""
    q, k, v = make_qkv(rng, h=2, n=256, d=128)
    ref = default_attention(q, k, v, causal=True)
    out = pallas_flash_attention(q, k, v, causal=True, interpret=True)
    np.testing.assert_allclose(out, ref, atol=ATOL)
    g_ref = jax.grad(lambda *a: (default_attention(*a, causal=True) ** 2).sum(), (0, 1, 2))(q, k, v)
    g_out = jax.grad(
        lambda *a: (pallas_flash_attention(*a, causal=True, interpret=True) ** 2).sum(),
        (0, 1, 2),
    )(q, k, v)
    for a, b, name in zip(g_out, g_ref, "qkv"):
        np.testing.assert_allclose(a, b, atol=1e-3, err_msg=f"d{name}")


# ---------------------------------------------------------------------------
# Compacted causal grid: with static band offsets the kernels run on a
# flattened grid of only the active tiles (scalar-prefetched tile tables);
# a traced offset keeps the rectangular grid.  The two grids must agree
# bit-for-bit on every band shape, including fully-empty rows.
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "co,wlo,masked",
    [
        (0, None, False),
        (-1, None, False),
        (0, -95, False),
        (-300, None, False),
        (0, None, True),
    ],
    ids=["causal", "striped-flip", "window", "all-empty", "kvmask"],
)
def test_compact_grid_matches_rectangular(rng, co, wlo, masked):
    q, k, v = make_qkv(rng, b=1, h=2, n=256, d=32)
    mask = jnp.asarray(rng.random((1, 256)) > 0.3) if masked else None
    scale = q.shape[-1] ** -0.5

    static = pallas_flash_partials(
        q, k, v, mask, scale=scale, causal_offset=co, window_lo=wlo,
        block_q=64, block_k=64, interpret=True,
    )
    traced = jax.jit(
        lambda q, k, v, o, w: pallas_flash_partials(
            q, k, v, mask, scale=scale, causal_offset=o,
            window_lo=w if wlo is not None else None,
            block_q=64, block_k=64, interpret=True,
        )
    )(q, k, v, jnp.int32(co), jnp.int32(wlo if wlo is not None else 0))
    for a, b, name in zip(static, traced, ("acc", "m", "l")):
        np.testing.assert_array_equal(a, b, err_msg=name)


@pytest.mark.parametrize(
    "co,wlo,masked",
    [(0, None, False), (0, -95, False), (0, None, True)],
    ids=["causal", "window", "kvmask"],
)
def test_compact_grid_backward_matches_rectangular(rng, co, wlo, masked):
    from ring_attention_tpu.ops.pallas_flash import pallas_flash_backward

    q, k, v = make_qkv(rng, b=1, h=4, hk=2, n=256, d=32)
    do = jnp.asarray(rng.standard_normal(q.shape), jnp.float32)
    mask = jnp.asarray(rng.random((1, 256)) > 0.3) if masked else None
    scale = q.shape[-1] ** -0.5
    parts = pallas_flash_partials(
        q, k, v, mask, scale=scale, causal_offset=co, window_lo=wlo,
        block_q=64, block_k=64, interpret=True,
    )
    out, lse = finalize_partials(parts)
    delta = (do * out).sum(-1)

    static = pallas_flash_backward(
        do, q, k, v, lse, delta, mask, scale=scale, causal_offset=co,
        window_lo=wlo, block_q=64, block_k=64, interpret=True,
    )
    traced = jax.jit(
        lambda o, w: pallas_flash_backward(
            do, q, k, v, lse, delta, mask, scale=scale, causal_offset=o,
            window_lo=w if wlo is not None else None,
            block_q=64, block_k=64, interpret=True,
        )
    )(jnp.int32(co), jnp.int32(wlo if wlo is not None else 0))
    for a, b, name in zip(static, traced, ("dq", "dk", "dv")):
        np.testing.assert_array_equal(a, b, err_msg=name)


@pytest.mark.parametrize("outer_is_q", [True, False])
@pytest.mark.parametrize(
    "hi,lo,windowed",
    [(0, 0, False), (-1, 0, False), (64, 0, False), (0, -95, True),
     (-256, 0, False), (0, -31, True)],
)
def test_band_tile_count_matches_tables(hi, lo, windowed, outer_is_q):
    """The closed-form count used for the SMEM cap must equal the real
    table length for every band shape (incl. empty/dummy rows)."""
    from ring_attention_tpu.ops.pallas_flash import (
        _band_tables,
        _band_tile_count,
    )

    args = (4, 4, 64, 64, (hi, hi, lo, lo), windowed, outer_is_q)
    assert _band_tile_count(*args) == _band_tables(*args)[0].shape[0]


def test_compact_table_cap_demotes_to_rectangular(rng, monkeypatch):
    """A static band whose tile tables exceed _MAX_COMPACT_TILES (SMEM
    scalar-prefetch budget) must take the rectangular grid, produce
    identical results fwd and bwd, and WARN about the lost compact grid
    (VERDICT r2 weak #5: the cliff must be observable) — with no warning
    when the compact grid engages."""
    import warnings as _warnings

    import ring_attention_tpu.ops.pallas_flash as pf

    q, k, v = make_qkv(rng, b=1, h=2, n=256, d=32)
    do = jnp.asarray(rng.standard_normal(q.shape), jnp.float32)
    scale = q.shape[-1] ** -0.5

    def run_all():
        parts = pf.pallas_flash_partials(
            q, k, v, scale=scale, causal_offset=0,
            block_q=64, block_k=64, interpret=True,
        )
        out, lse = finalize_partials(parts)
        delta = (do * out).sum(-1)
        grads = pf.pallas_flash_backward(
            do, q, k, v, lse, delta, scale=scale, causal_offset=0,
            block_q=64, block_k=64, interpret=True,
        )
        return (parts.acc, parts.m, parts.l, *grads)

    with _warnings.catch_warnings():
        _warnings.simplefilter("error")  # compact path: no demotion warning
        compact = run_all()
    monkeypatch.setattr(pf, "_MAX_COMPACT_TILES", 2)  # force demotion
    with pytest.warns(UserWarning, match="demoted to the rectangular grid"):
        demoted = run_all()
    for a, b, name in zip(compact, demoted,
                          ("acc", "m", "l", "dq", "dk", "dv")):
        np.testing.assert_array_equal(a, b, err_msg=name)


def test_fused_forward_matches_finalized_partials(rng):
    """pallas_flash_fused (normalization folded into the kernel's final
    write — ref triton_flash_attn.py:273-275) must equal
    finalize_partials(pallas_flash_partials(...)) on every mask variant."""
    from ring_attention_tpu.ops.pallas_flash import pallas_flash_fused

    q, k, v = make_qkv(rng, b=1, h=4, hk=2, n=256, d=32)
    mask = jnp.asarray(rng.random((1, 256)) > 0.3)
    scale = q.shape[-1] ** -0.5
    cases = [
        dict(causal_offset=0),
        dict(causal_offset=0, window_lo=-95),
        dict(kv_mask=mask, softclamp_value=5.0),
        dict(),
    ]
    for kw in cases:
        kv_mask = kw.pop("kv_mask", None)
        parts = pallas_flash_partials(
            q, k, v, kv_mask, scale=scale, block_q=64, block_k=64,
            interpret=True, **kw,
        )
        ref_out, ref_lse = finalize_partials(parts)
        out, lse = pallas_flash_fused(
            q, k, v, kv_mask, scale=scale, block_q=64, block_k=64,
            interpret=True, **kw,
        )
        assert out.dtype == q.dtype
        np.testing.assert_allclose(out, ref_out, atol=1e-6, err_msg=str(kw))
        np.testing.assert_allclose(lse, ref_lse, atol=1e-6, err_msg=str(kw))


def test_band_hint_compact_matches_static(rng):
    """A traced offset + exact band_hint must reproduce the static-offset
    compact grid bit-for-bit (the unrolled ring hop contract: contiguous
    hops have one exact per-hop offset, VERDICT r2 missing #1)."""
    q, k, v = make_qkv(rng, b=1, h=2, n=256, d=32)
    scale = q.shape[-1] ** -0.5
    for co in (0, 64, -64):
        static = pallas_flash_partials(
            q, k, v, scale=scale, causal_offset=co,
            block_q=64, block_k=64, interpret=True,
        )
        hinted = jax.jit(
            lambda o, co=co: pallas_flash_partials(
                q, k, v, scale=scale, causal_offset=o,
                band_hint=(co, co, 0, 0),
                block_q=64, block_k=64, interpret=True,
            )
        )(jnp.int32(co))
        for a, b, name in zip(static, hinted, ("acc", "m", "l")):
            np.testing.assert_array_equal(a, b, err_msg=f"co={co} {name}")


def test_band_hint_superset_merges_exactly(rng):
    """Striped-hop contract: offsets in {0, -1} under one superset hint
    (hi_work=0, hi_int=-1).  Superset-only tiles are masked at run time and
    any band-empty row's garbage is wiped by the online-softmax rescale in
    the ring merge — so the merged result must match merging the exact
    static-offset partials."""
    from ring_attention_tpu.ops.pallas_flash import pallas_flash_backward

    q, k, v = make_qkv(rng, b=1, h=2, n=256, d=32)
    scale = q.shape[-1] ** -0.5
    diag = pallas_flash_partials(  # "own block" hop: offset 0
        q, k, v, scale=scale, causal_offset=0,
        block_q=64, block_k=64, interpret=True,
    )
    hop_static = pallas_flash_partials(  # strict-diagonal hop: offset -1
        q, k, v, scale=scale, causal_offset=-1,
        block_q=64, block_k=64, interpret=True,
    )
    hop_hinted = jax.jit(
        lambda o: pallas_flash_partials(
            q, k, v, scale=scale, causal_offset=o,
            band_hint=(0, -1, 0, 0),
            block_q=64, block_k=64, interpret=True,
        )
    )(jnp.int32(-1))
    ref_out, ref_lse = finalize_partials(merge_partials(diag, hop_static))
    out, lse = finalize_partials(merge_partials(diag, hop_hinted))
    np.testing.assert_allclose(out, ref_out, atol=ATOL)
    np.testing.assert_allclose(lse, ref_lse, atol=ATOL)

    # backward: superset-only tiles contribute exact zeros (p masked to 0),
    # so grads match the static-offset grads directly
    do = jnp.asarray(rng.standard_normal(q.shape), jnp.float32)
    out_s, lse_s = finalize_partials(hop_static)
    delta = (do * out_s).sum(-1)
    g_static = pallas_flash_backward(
        do, q, k, v, lse_s, delta, scale=scale, causal_offset=-1,
        block_q=64, block_k=64, interpret=True,
    )
    g_hinted = jax.jit(
        lambda o: pallas_flash_backward(
            do, q, k, v, lse_s, delta, scale=scale, causal_offset=o,
            band_hint=(0, -1, 0, 0), block_q=64, block_k=64, interpret=True,
        )
    )(jnp.int32(-1))
    for a, b, name in zip(g_hinted, g_static, ("dq", "dk", "dv")):
        np.testing.assert_allclose(a, b, atol=1e-5, err_msg=name)


def _race_checked_interpret():
    """The TPU interpreter with its race detector on, and a function that
    says whether the launches since then raced (None where this jax has
    no such interpreter: the values are still compared)."""
    try:
        from jax._src.pallas.mosaic.interpret import (
            interpret_pallas_call as tpu_interpret,
        )
        from jax.experimental.pallas import tpu as pltpu

        params = pltpu.InterpretParams(detect_races=True,
                                       dma_execution_mode="on_wait")
        pltpu.reset_tpu_interpret_mode_state()  # an earlier test's verdict
    except (ImportError, AttributeError, TypeError):
        return True, lambda: None
    return params, lambda: tpu_interpret.races.races_found


# name: (shape kwargs, launch kwargs, options).  Shape: h, hk, nq, nk, d.
# Options: traced (causal_offset / window_lo passed as traced scalars),
# mask, seg (runtime segment ids), race (run under the race detector).
_BWD_CASES = {
    "unmasked-rect": (dict(), dict(), dict()),
    "causal-compact": (dict(), dict(causal_offset=0), dict()),
    "window-compact-g2": (dict(h=4, hk=2),
                          dict(causal_offset=0, window_lo=-23), dict()),
    "causal-rect-traced": (dict(), dict(causal_offset=0), dict(traced=True)),
    "window-rect-traced": (dict(), dict(causal_offset=0, window_lo=-23),
                           dict(traced=True)),
    "kv-mask": (dict(), dict(), dict(mask=True)),
    "kv-mask-causal-traced": (dict(h=4, hk=2), dict(causal_offset=0),
                              dict(mask=True, traced=True)),
    "segment-ids": (dict(), dict(causal_offset=0), dict(seg=True)),
    "doc-starts-aligned": (dict(), dict(causal_offset=0,
                                        doc_starts=(0, 16, 48)), dict()),
    "doc-starts-misaligned": (dict(), dict(causal_offset=0,
                                           doc_starts=(0, 24)), dict()),
    "softclamp": (dict(), dict(causal_offset=0, softclamp_value=2.0), dict()),
    "exp2": (dict(h=4, hk=2), dict(causal_offset=0, exp2=True), dict()),
    "exp2-softclamp": (dict(), dict(softclamp_value=2.0, exp2=True), dict()),
    "odd-scale": (dict(d=24), dict(causal_offset=0), dict()),
    "ring-hop-nq<nk": (dict(nq=32, nk=64),
                       dict(causal_offset=31, band_hint=(32, 30, 0, 0)),
                       dict(traced=True)),
    "ring-hop-striped": (dict(h=4, hk=2),
                         dict(causal_offset=-1, band_hint=(0, -1, 0, 0)),
                         dict(traced=True)),
    "rows-without-keys": (dict(nq=64, nk=32), dict(causal_offset=-32), dict()),
    "gqa-6": (dict(h=6, hk=1, nq=32, nk=32), dict(causal_offset=0), dict()),
    "gqa-12": (dict(h=12, hk=1, nq=32, nk=32), dict(causal_offset=0), dict()),
    "d128": (dict(h=1, nq=32, nk=32, d=128), dict(causal_offset=0), dict()),
    "hazard-one-q-block": (dict(nq=16, nk=64), dict(), dict(race=True)),
    "hazard-two-q-blocks": (dict(nq=32, nk=64), dict(), dict(race=True)),
    "hazard-window-tail": (dict(), dict(causal_offset=0, window_lo=-7),
                           dict(race=True)),
}


def test_backward_takes_lse_and_delta_as_rows():
    """PR 38: the k-major tiles use ``lse`` and ``delta`` as rows, so the
    launch passes them as ``(b·h, 1, n)`` rows; as ``(b·h, n, 1)`` columns
    XLA:TPU laid them out lane-padded, 128 times their bytes, live at the
    train step's memory peak."""
    from ring_attention_tpu.ops.pallas_flash import pallas_flash_backward

    b, h, n, d = 1, 2, 64, 16
    x = jnp.zeros((b, h, n, d), jnp.float32)
    vec = jnp.zeros((b, h, n), jnp.float32)
    jaxpr = jax.make_jaxpr(lambda do, q, k, v, lse, delta: pallas_flash_backward(
        do, q, k, v, lse, delta, scale=d ** -0.5, block_q=16, block_k=16,
        interpret=True))(x, x, x, x, vec, vec)
    calls = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "pallas_call"]
    assert len(calls) == 1
    shapes = [v.aval.shape for v in calls[0].invars]
    assert shapes.count((b * h, 1, n)) == 2 and (b * h, n, 1) not in shapes


@pytest.mark.parametrize("case", sorted(_BWD_CASES))
def test_one_pass_backward_parity(rng, case):
    """dq, dk, dv of the one-pass kernel against the XLA blockwise
    backward, over every variant the launcher takes: both grids, every
    mask, both score bases, GQA groups, a ring hop's traced offset under
    its hint, q rows no key reaches, and the tiles whose dq read would
    overtake an unfinished write (run under the race detector)."""
    from ring_attention_tpu.ops.flash import flash_backward_blocks
    from ring_attention_tpu.ops.pallas_flash import pallas_flash_backward

    shape, kw, opt = _BWD_CASES[case]
    h, hk = shape.get("h", 2), shape.get("hk", shape.get("h", 2))
    nq, nk, d = shape.get("nq", 64), shape.get("nk", 64), shape.get("d", 16)
    g, blk = h // hk, 16
    q = jnp.asarray(rng.standard_normal((1, h, nq, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((1, hk, nk, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((1, hk, nk, d)), jnp.float32)
    do = jnp.asarray(rng.standard_normal(q.shape), jnp.float32)
    mask = jnp.asarray(rng.random((1, nk)) > 0.3) if opt.get("mask") else None
    seg = None
    if opt.get("seg"):
        seg = jnp.asarray(np.arange(nq) // 24, jnp.int32)[None]
    elif "doc_starts" in kw:  # the reference takes the layout as ids
        seg = jnp.asarray(
            np.searchsorted(kw["doc_starts"], np.arange(nq), side="right"),
            jnp.int32)[None]
    scale = d ** -0.5
    co, wlo = kw.get("causal_offset"), kw.get("window_lo")
    clamp = kw.get("softclamp_value")

    parts = pallas_flash_partials(
        q, k, v, mask, scale=scale, causal_offset=co, window_lo=wlo,
        softclamp_value=clamp, segment_ids=seg, block_q=blk, block_k=blk,
        interpret=True,
    )
    out, lse = finalize_partials(parts)
    delta = (do * out).sum(-1)
    want = flash_backward_blocks(
        do, q, k, v, lse.reshape(1, hk, g, nq), delta.reshape(1, hk, g, nq),
        scale=scale, bucket_size=blk, causal_offset=co, window_lo=wlo,
        kv_mask=mask, softclamp_value=clamp, q_segment_ids=seg,
        kv_segment_ids=seg,
    )

    interpret, raced = (_race_checked_interpret() if opt.get("race")
                        else (True, lambda: None))
    static = {x: kw[x] for x in ("softclamp_value", "exp2", "doc_starts",
                                 "band_hint") if x in kw}
    if opt.get("seg"):
        static["segment_ids"] = seg

    def run(co, wlo):
        return pallas_flash_backward(
            do, q, k, v, lse, delta, mask, scale=scale, causal_offset=co,
            window_lo=wlo, block_q=blk, block_k=blk, interpret=interpret,
            **static)

    if opt.get("traced"):
        got = jax.jit(lambda c, w: run(c, w if wlo is not None else None))(
            jnp.int32(co), jnp.int32(wlo or 0))
    else:
        got = run(co, wlo)
    for a, b, name in zip(got, want, ("dq", "dk", "dv")):
        np.testing.assert_allclose(a, b, atol=2e-4, rtol=2e-4, err_msg=name)
    assert not raced(), "a dq copy raced with another"


def test_carry_resume_matches_merge(rng):
    """In-kernel accumulator resume (carry=...) must equal the XLA-side
    merge_partials of two independent sweeps — the LOAD_ACCUMULATED
    contract (ref triton_flash_attn.py:124-165) the ring hops rely on —
    and resuming into a fused final write must equal finalizing that
    merge (ref ring_flash_attention_cuda.py:134,182-186)."""
    from ring_attention_tpu.ops.pallas_flash import pallas_flash_fused

    q, k, v = make_qkv(rng, b=1, h=2, n=256, d=32)
    scale = q.shape[-1] ** -0.5
    left = pallas_flash_partials(
        q, k[:, :, :128], v[:, :, :128], scale=scale,
        block_q=64, block_k=64, interpret=True,
    )
    right = pallas_flash_partials(
        q, k[:, :, 128:], v[:, :, 128:], scale=scale,
        block_q=64, block_k=64, interpret=True,
    )
    merged = merge_partials(left, right)
    resumed = pallas_flash_partials(
        q, k[:, :, 128:], v[:, :, 128:], scale=scale,
        block_q=64, block_k=64, carry=left, interpret=True,
    )
    # resume rescales the carry tile-by-tile where merge rescales once:
    # same math, different summation order -> tiny float drift allowed
    for a, b, name in zip(resumed, merged, ("acc", "m", "l")):
        np.testing.assert_allclose(a, b, atol=1e-5, rtol=1e-5, err_msg=name)

    out_ref, lse_ref = finalize_partials(merged)
    out, lse = pallas_flash_fused(
        q, k[:, :, 128:], v[:, :, 128:], scale=scale,
        block_q=64, block_k=64, carry=left, interpret=True,
    )
    np.testing.assert_allclose(out, out_ref, atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(lse, lse_ref, atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("hk,nq", [(4, 1), (2, 1), (2, 3)])
def test_decode_kernel_parity(rng, hk, nq):
    """pallas_flash_decode (head group folded onto query rows, KV read once
    per kv head) vs the dense oracle: fused output + lse, and the raw
    FlashCarry-layout partials the tree merge consumes."""
    from ring_attention_tpu.ops.flash import FlashCarry, finalize, _ungroup
    from ring_attention_tpu.ops.pallas_flash import pallas_flash_decode

    b, h, n, d = 2, 4, 256, 32
    q = jnp.asarray(rng.standard_normal((b, h, nq, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, hk, n, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, hk, n, d)), jnp.float32)
    mask = jnp.asarray(rng.random((b, n)) < 0.8)
    ref = default_attention(q, k, v, mask)

    out, lse = pallas_flash_decode(q, k, v, mask, block_k=64, interpret=True)
    assert out.shape == q.shape and lse.shape == (b, h, nq)
    np.testing.assert_allclose(out, ref, atol=ATOL)

    acc, m, l = pallas_flash_decode(
        q, k, v, mask, block_k=64, fused=False, interpret=True
    )
    assert acc.shape == (b, hk, h // hk, nq, d)
    o2, _ = finalize(FlashCarry(acc, m, l))
    np.testing.assert_allclose(_ungroup(o2), ref, atol=ATOL)


def test_decode_kernel_softclamp(rng):
    from ring_attention_tpu.ops.pallas_flash import pallas_flash_decode

    b, h, hk, n, d = 1, 4, 2, 128, 32
    q, k, v = make_qkv(rng, b=b, h=h, hk=hk, n=n, d=d)
    q = q[:, :, :1]
    ref = default_attention(q, k, v, softclamp_value=15.0)
    out, _ = pallas_flash_decode(
        q, k, v, softclamp_value=15.0, block_k=32, interpret=True
    )
    np.testing.assert_allclose(out, ref, atol=ATOL)


@pytest.mark.parametrize("hk,nq,masked", [(2, 1, False), (2, 1, True),
                                          (4, 2, False), (1, 1, False)])
def test_decode_q8_kernel_parity(rng, hk, nq, masked):
    """Kernel correctness isolated from quantization error: the q8 decode
    against a quantized cache must match the dense oracle run on the
    DEQUANTIZED cache to float tolerance."""
    from ring_attention_tpu.ops.pallas_flash import (
        pallas_flash_decode_q8,
        quantize_kv_cache,
    )

    b, h, n, d = 2, 4, 256, 32
    q = jnp.asarray(rng.standard_normal((b, h, nq, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, hk, n, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, hk, n, d)), jnp.float32)
    mask = jnp.asarray(rng.random((b, n)) > 0.25) if masked else None
    kv = quantize_kv_cache(k, v)
    k_deq = kv.k_q.astype(jnp.float32) * kv.k_scale[..., None]
    v_deq = kv.v_q.astype(jnp.float32) * kv.v_scale[..., None]
    ref = default_attention(q, k_deq, v_deq, mask)
    out, lse = pallas_flash_decode_q8(q, kv, mask, block_k=64, interpret=True)
    assert out.shape == (b, h, nq, d) and lse.shape == (b, h, nq)
    np.testing.assert_allclose(out, ref, atol=3e-5)

    # end-to-end quantized accuracy vs the unquantized oracle: per-token
    # absmax int8 stays within ~2% on gaussian activations
    full = default_attention(q, k, v, mask)
    err = jnp.abs(out - full).max() / jnp.abs(full).max()
    assert float(err) < 0.02, float(err)


def test_decode_q8_partials_merge(rng):
    """fused=False partials from the q8 kernel must finalize to the fused
    output (the tree-decode cross-device merge contract)."""
    from ring_attention_tpu.ops.pallas_flash import (
        pallas_flash_decode_q8,
        quantize_kv_cache,
    )

    b, h, hk, n, d = 1, 4, 2, 128, 32
    q = jnp.asarray(rng.standard_normal((b, h, 1, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, hk, n, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, hk, n, d)), jnp.float32)
    kv = quantize_kv_cache(k, v)
    out, lse = pallas_flash_decode_q8(q, kv, block_k=32, interpret=True)
    acc, m, l = pallas_flash_decode_q8(
        q, kv, block_k=32, fused=False, interpret=True
    )
    g = h // hk
    assert acc.shape == (b, hk, g, 1, d)
    fin = acc / jnp.maximum(l, 1e-10)[..., None]
    np.testing.assert_allclose(
        fin.reshape(b, h, 1, d), out, atol=2e-5
    )
    np.testing.assert_allclose(
        (m + jnp.log(jnp.maximum(l, 1e-10))).reshape(b, h, 1), lse, atol=2e-5
    )


@pytest.mark.parametrize("dtype,atol", [
    (jnp.bfloat16, 2e-2),  # itemsize 2 -> sublane tile 16 rows
    (jnp.float16, 2e-2),   # itemsize 2 -> 16 (the pre-ADVICE code padded 8)
    (jnp.float32, 1e-5),   # itemsize 4 -> 8
])
def test_decode_kernel_row_padding(rng, dtype, atol):
    """Decode pads query rows to a full sublane tile, keyed on dtype
    itemsize (ADVICE r3: an exact-bf16 check under-padded f16); results
    must be unchanged and pad rows invisible."""
    from ring_attention_tpu.ops.pallas_flash import pallas_flash_decode

    b, h, hk, n, d = 1, 2, 2, 128, 32  # rows = g*nq = 1 -> pad to tile
    q = jnp.asarray(rng.standard_normal((b, h, 1, d)), dtype)
    k = jnp.asarray(rng.standard_normal((b, hk, n, d)), dtype)
    v = jnp.asarray(rng.standard_normal((b, hk, n, d)), dtype)
    ref = default_attention(q, k, v)
    out, lse = pallas_flash_decode(q, k, v, block_k=32, interpret=True)
    assert out.shape == (b, h, 1, d) and lse.shape == (b, h, 1)
    np.testing.assert_allclose(
        out.astype(jnp.float32), ref.astype(jnp.float32), atol=atol
    )


def test_exp2_log2_space_parity(rng, monkeypatch):
    """RING_ATTN_EXP2=1 (log2-space scoring, ROADMAP S3) is
    value-identical at the kernel boundary: fwd outputs
    AND grads match the natural-basis oracle, including softclamp + mask
    + GQA, and the emitted lse stays in natural units."""
    monkeypatch.setenv("RING_ATTN_EXP2", "1")
    q, k, v = make_qkv(rng, hk=2, n=128, d=32)
    mask = jnp.broadcast_to(jnp.arange(128)[None, :] < 100, (2, 128))
    ref = default_attention(q, k, v, mask, causal=True, softclamp_value=15.0)
    out = pallas_flash_attention(
        q, k, v, mask, causal=True, softclamp_value=15.0, interpret=True
    )
    np.testing.assert_allclose(out, ref, atol=2e-5)

    def loss_p(q, k, v):
        return (pallas_flash_attention(
            q, k, v, mask, causal=True, softclamp_value=15.0, interpret=True
        ) ** 2).sum()

    def loss_o(q, k, v):
        return (default_attention(
            q, k, v, mask, causal=True, softclamp_value=15.0
        ) ** 2).sum()

    gp = jax.grad(loss_p, argnums=(0, 1, 2))(q, k, v)
    go = jax.grad(loss_o, argnums=(0, 1, 2))(q, k, v)
    for name, a, b in zip("qkv", gp, go):
        np.testing.assert_allclose(a, b, atol=3e-5, err_msg=f"d{name}")

    # partials keep the natural-units contract (ring merging / carry interop)
    from ring_attention_tpu.ops.pallas_flash import pallas_flash_partials

    monkeypatch.setenv("RING_ATTN_EXP2", "0")
    nat = pallas_flash_partials(q, k, v, scale=32**-0.5, causal_offset=0,
                                interpret=True)
    monkeypatch.setenv("RING_ATTN_EXP2", "1")
    l2 = pallas_flash_partials(q, k, v, scale=32**-0.5, causal_offset=0,
                               interpret=True)
    # rtol covers rows whose l (a sum of up to n exponentials) is large:
    # the bases legitimately differ by ~1 ulp per accumulation step
    np.testing.assert_allclose(l2.m, nat.m, atol=2e-5, rtol=1e-5)
    np.testing.assert_allclose(l2.l, nat.l, atol=2e-5, rtol=1e-5)
    np.testing.assert_allclose(l2.acc, nat.acc, atol=2e-5, rtol=1e-5)

    # the explicit keyword (ADVICE.md: the env var is captured at trace
    # time, so in-process A/B passes exp2= instead) matches the env path
    monkeypatch.setenv("RING_ATTN_EXP2", "0")
    l2kw = pallas_flash_partials(q, k, v, scale=32**-0.5, causal_offset=0,
                                 interpret=True, exp2=True)
    np.testing.assert_allclose(l2kw.m, l2.m, atol=0)
    np.testing.assert_allclose(l2kw.l, l2.l, atol=0)
    np.testing.assert_allclose(l2kw.acc, l2.acc, atol=0)
    out_kw = pallas_flash_attention(
        q, k, v, mask, causal=True, softclamp_value=15.0, interpret=True,
        exp2=True,
    )
    np.testing.assert_allclose(out_kw, ref, atol=2e-5)


def test_exp2_carry_resume_parity(rng, monkeypatch):
    """Ring-hop carry resume under RING_ATTN_EXP2=1: the carry crosses the
    kernel boundary in natural units and converts on load (the subtlest
    line of the log2-space feature), so a partials hop + fused carry hop
    must equal the single full sweep — including when the two hops run in
    DIFFERENT bases (one kernel natural, the next log2)."""
    from ring_attention_tpu.ops.pallas_flash import (
        pallas_flash_fused,
        pallas_flash_partials,
    )

    q, k, v = make_qkv(rng, hk=2, n=128, d=32)
    scale = 32**-0.5
    ref = default_attention(q, k, v)

    def two_hop(basis_hop0, basis_hop1):
        monkeypatch.setenv("RING_ATTN_EXP2", basis_hop0)
        carry = pallas_flash_partials(
            q, k[:, :, :64], v[:, :, :64], scale=scale, interpret=True
        )
        monkeypatch.setenv("RING_ATTN_EXP2", basis_hop1)
        out, lse = pallas_flash_fused(
            q, k[:, :, 64:], v[:, :, 64:], scale=scale, carry=carry,
            interpret=True,
        )
        return out, lse

    out_nat, lse_nat = two_hop("0", "0")
    for hops in (("1", "1"), ("0", "1"), ("1", "0")):
        out, lse = two_hop(*hops)
        np.testing.assert_allclose(out, ref, atol=2e-5, err_msg=hops)
        np.testing.assert_allclose(lse, lse_nat, atol=2e-5, err_msg=hops)
