"""Memory-axis scale: blockwise FFN, named remat policies, host offload.

Pins the ISSUE-7 claims (docs/memory.md): the chunked feedforward is
value-identical to the dense block and never materializes the full
``(b, n, mult*dim)`` intermediate, each named remat policy has a
machine-checkable recompute signature, host offload degrades to a no-op
on backends without a host memory space, and the memory audits
(``analysis/recompile.py``) catch the silent failure modes.

Lean by design — tier-1 sits near its time cap: the fast tier pins one
configuration per claim with shared params/compiled fns; the full
policy x chunk-size x strategy sweep lives in the slow tier.
"""

import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from ring_attention_tpu.analysis.recompile import (
    assert_compiles_once,
    audit_donation,
    audit_host_offload,
    audit_remat_residuals,
)
from ring_attention_tpu.models import (
    REMAT_POLICIES,
    FeedForward,
    RingTransformer,
    resolve_remat_policy,
)
from ring_attention_tpu.parallel import create_mesh
from ring_attention_tpu.utils import compat, make_train_step
from ring_attention_tpu.utils.telemetry import (
    compiled_memory,
    train_memory_estimate,
)

VOCAB = 64
D, MULT = 16, 4


# ----------------------------------------------------------------------
# Blockwise feedforward
# ----------------------------------------------------------------------


@pytest.fixture(scope="module")
def ffn_case():
    """One dense/chunked FeedForward pair sharing params, with a sequence
    length (33) that exercises the pad path at chunk 8."""
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((2, 33, D)), jnp.float32)
    dense = FeedForward(D, MULT)
    params = dense.init(jax.random.PRNGKey(0), x)
    return dense, params, x


def test_ffn_chunk_parity_fwd_and_grads(ffn_case):
    """Chunked vs dense: forward and all weight grads, including a chunk
    that does not divide the sequence (pad path)."""
    dense, params, x = ffn_case
    chunked = FeedForward(D, MULT, chunk_size=8)
    np.testing.assert_allclose(
        chunked.apply(params, x), dense.apply(params, x), atol=1e-6
    )
    gd = jax.grad(lambda p: dense.apply(p, x).sum())(params)
    gc = jax.grad(lambda p: chunked.apply(p, x).sum())(params)
    for a, b in zip(jax.tree.leaves(gd), jax.tree.leaves(gc)):
        np.testing.assert_allclose(a, b, atol=1e-5)


def test_ffn_chunk_clamp_falls_back_to_dense(ffn_case):
    """chunk >= sequence length takes the dense path bit-identically
    (padding UP would make memory strictly worse — the loss_chunk_size
    clamp rule)."""
    dense, params, x = ffn_case
    big = FeedForward(D, MULT, chunk_size=64)
    np.testing.assert_array_equal(
        np.asarray(big.apply(params, x)), np.asarray(dense.apply(params, x))
    )
    # a shape that cannot split shard-aligned (decode steps: n=1) also
    # falls back rather than erroring
    short = FeedForward(D, MULT, chunk_size=8, seq_shards=4)
    y = short.apply(params, x[:, :1])
    np.testing.assert_array_equal(
        np.asarray(y), np.asarray(dense.apply(params, x[:, :1]))
    )


def test_ffn_chunk_never_materializes_full_intermediate(ffn_case):
    """The whole point: no (b, n, mult*dim) array exists anywhere in the
    grad program — forward or backward."""
    _, params, _ = ffn_case
    n = 64
    x = jnp.zeros((1, n, D), jnp.float32)
    chunked = FeedForward(D, MULT, chunk_size=16)
    jaxpr = jax.make_jaxpr(
        jax.grad(lambda p: chunked.apply(p, x).sum())
    )(params)
    full = f"1,{n},{MULT * D}"
    assert full not in str(jaxpr), f"found full FFN intermediate ({full})"


def test_ffn_chunk_residual_audit_clean(ffn_case):
    """The remat-residual audit agrees: nothing of full (b, n, mult*dim)
    extent is saved across the chunked scan's fwd/bwd boundary."""
    _, params, x = ffn_case
    chunked = FeedForward(D, MULT, chunk_size=8)
    b, n, _ = x.shape
    assert audit_remat_residuals(
        lambda p: chunked.apply(p, x).sum(), params,
        forbidden=[(b, n, MULT * D)], label="chunked_ffn",
    ) == []


def test_ffn_chunk_scan_compiles_once(ffn_case):
    """CompileCounter pin: the chunked scan is ONE compilation across a
    steady-state loop, not a retrace per step."""
    _, params, x = ffn_case
    chunked = FeedForward(D, MULT, chunk_size=8)
    fn = compat.jit(lambda p, x: chunked.apply(p, x).sum())
    assert assert_compiles_once(
        fn, lambda step: (params, x + step), label="chunked_ffn",
    ) <= 1


def test_transformer_ff_chunked_parity_on_mesh(rng):
    """End-to-end: ff_chunk_size through the striped-ring transformer —
    loss and every grad leaf match the dense-FFN model (chunks split
    per shard; the scan crosses no device boundary)."""
    mesh = create_mesh(ring_size=8)
    kw = dict(num_tokens=VOCAB, dim=32, depth=2, heads=4, dim_head=8,
              bucket_size=4, causal=True, striped=True, mesh=mesh)
    m_d = RingTransformer(**kw)
    m_c = RingTransformer(ff_chunk_size=4, **kw)
    tokens = jnp.asarray(rng.integers(0, VOCAB, (2, 63)), jnp.int32)
    params = m_d.init(jax.random.PRNGKey(0), tokens)
    ld, gd = jax.jit(jax.value_and_grad(
        lambda p: m_d.apply(p, tokens, return_loss=True)))(params)
    lc, gc = jax.jit(jax.value_and_grad(
        lambda p: m_c.apply(p, tokens, return_loss=True)))(params)
    np.testing.assert_allclose(lc, ld, atol=1e-6)
    for a, b in zip(jax.tree.leaves(gd), jax.tree.leaves(gc)):
        np.testing.assert_allclose(a, b, atol=1e-5)


def test_blockwise_ffn_contract_row(devices):
    """The PR-5 contract-table row: the chunked scan adds ZERO collectives
    — none at all forward, exactly the dense FFN's two weight-grad
    all-reduces backward — verified from compiled HLO on the 8-device
    mesh (any undeclared collective kind fails the row)."""
    from ring_attention_tpu.analysis import contracts

    reports = contracts.check_strategy("blockwise_ffn")
    bad = [v for r in reports for v in r.violations]
    assert not bad, "\n".join(bad)
    fwd = next(r for r in reports if r.direction == "fwd")
    assert fwd.counts == {}, fwd.counts  # zero collectives, literally


# ----------------------------------------------------------------------
# Named remat policies
# ----------------------------------------------------------------------


def test_remat_policy_validation_lists_names():
    """Unknown policy -> ValueError naming every valid policy (the old
    assert vanished under -O); bad ff_chunk_size -> the loss_chunk_size-
    style ValueError; tuple length must match depth."""
    kw = dict(num_tokens=VOCAB, dim=16, depth=2, heads=2, dim_head=8,
              bucket_size=8, causal=True, use_ring=False)
    tokens = jnp.zeros((1, 9), jnp.int32)
    with pytest.raises(ValueError) as e:
        RingTransformer(remat=True, remat_policy="bogus", **kw).init(
            jax.random.PRNGKey(0), tokens)
    msg = str(e.value)
    assert "save_attn" in msg and "nothing_saveable" in msg
    assert "offload_attn" in msg
    with pytest.raises(ValueError, match="ff_chunk_size"):
        RingTransformer(ff_chunk_size=0, **kw).init(
            jax.random.PRNGKey(0), tokens)
    with pytest.raises(ValueError, match="3 entries for depth 2"):
        RingTransformer(
            remat=True, remat_policy=("save_attn",) * 3, depth=2,
            **{k: v for k, v in kw.items() if k != "depth"},
        ).init(jax.random.PRNGKey(0), tokens)
    with pytest.raises(ValueError, match="valid policies"):
        resolve_remat_policy("nope")
    assert resolve_remat_policy(None) is None
    assert set(REMAT_POLICIES) >= {
        "nothing_saveable", "everything_saveable", "checkpoint_dots",
        "save_attn", "save_ffn_inputs", "offload_attn",
    }


@pytest.fixture(scope="module")
def policy_model_case():
    """One tiny local transformer + params + the no-remat baseline
    (loss, grads), shared across the policy tests."""
    kw = dict(num_tokens=VOCAB, dim=16, depth=2, heads=2, dim_head=8,
              bucket_size=8, causal=True, use_ring=False)
    rng = np.random.default_rng(1)
    tokens = jnp.asarray(rng.integers(0, VOCAB, (1, 33)), jnp.int32)
    base = RingTransformer(**kw)
    params = base.init(jax.random.PRNGKey(0), tokens)
    l0, g0 = jax.jit(jax.value_and_grad(
        lambda p: base.apply(p, tokens, return_loss=True)))(params)
    return kw, tokens, params, l0, g0


def _policy_loss_grads(kw, tokens, params, policy):
    model = RingTransformer(remat=True, remat_policy=policy, **kw)
    return jax.jit(jax.value_and_grad(
        lambda p: model.apply(p, tokens, return_loss=True)))(params)


@pytest.mark.parametrize("policy", ["nothing_saveable", "save_ffn_inputs"])
def test_remat_policy_parity_fast(policy_model_case, policy):
    """Every policy changes memory/recompute only, never values — fast
    tier pins the two ends; the full registry sweep is in the slow tier."""
    kw, tokens, params, l0, g0 = policy_model_case
    loss, grads = _policy_loss_grads(kw, tokens, params, policy)
    np.testing.assert_allclose(loss, l0, atol=1e-6)
    for a, b in zip(jax.tree.leaves(g0), jax.tree.leaves(grads)):
        np.testing.assert_allclose(a, b, atol=1e-5)


@pytest.mark.slow
@pytest.mark.parametrize("policy", sorted(
    set(REMAT_POLICIES) - {"nothing_saveable", "save_ffn_inputs"}
))
def test_remat_policy_parity_full(policy_model_case, policy):
    kw, tokens, params, l0, g0 = policy_model_case
    loss, grads = _policy_loss_grads(kw, tokens, params, policy)
    np.testing.assert_allclose(loss, l0, atol=1e-6)
    for a, b in zip(jax.tree.leaves(g0), jax.tree.leaves(grads)):
        np.testing.assert_allclose(a, b, atol=1e-5)


def test_remat_policy_per_layer_tuple(policy_model_case):
    """A per-layer policy tuple (mirroring max_lookback_seq_len) is
    value-identical too."""
    kw, tokens, params, l0, g0 = policy_model_case
    loss, grads = _policy_loss_grads(
        kw, tokens, params, ("save_attn", None))
    np.testing.assert_allclose(loss, l0, atol=1e-6)
    for a, b in zip(jax.tree.leaves(g0), jax.tree.leaves(grads)):
        np.testing.assert_allclose(a, b, atol=1e-5)


def _policy_dots(kw, tokens, params, policy):
    """Dot ops in the compiled train grad — the recompute-size signature
    (scan bodies count once; see test_transformer._train_dots)."""
    model = RingTransformer(remat=True, remat_policy=policy, **kw)
    fn = compat.jit(jax.value_and_grad(
        lambda p: model.apply(p, tokens, return_loss=True)))
    return fn.lower(params).compile().as_text().count("dot(")


def test_remat_policy_recompute_signatures(policy_model_case):
    """HLO-verified recompute signatures: what a policy SAVES must vanish
    from the backward recompute — everything_saveable elides the whole
    recompute (fewest dots), checkpoint_dots elides the matmul recompute,
    nothing_saveable recomputes it all (most dots).  save_attn's elision
    is pinned separately (test_transformer.py)."""
    kw, tokens, params, _, _ = policy_model_case
    dots = {
        p: _policy_dots(kw, tokens, params, p)
        for p in ("nothing_saveable", "checkpoint_dots",
                  "everything_saveable")
    }
    # checkpoint_dots saves every dot output, so its backward recompute
    # carries no extra dots either — at this all-dots-and-elementwise
    # model it meets everything_saveable's floor; nothing_saveable pays
    # the full recompute
    assert dots["everything_saveable"] <= dots["checkpoint_dots"], dots
    assert dots["checkpoint_dots"] < dots["nothing_saveable"], dots


def test_remat_residual_audit_catches_policy_leak(policy_model_case):
    """The negative toy: a remat that keeps the (b, n, mult*dim) FFN
    intermediate under an everything_saveable policy must be flagged by
    the residual audit with a one-line diagnostic; the honest
    nothing_saveable program is clean."""
    b, n, d, mult = 1, 64, 16, 4
    w1, w2 = jnp.ones((d, mult * d)), jnp.ones((mult * d, d))
    x = jnp.ones((b, n, d))

    def blk(x):
        return ((jax.nn.gelu(x @ w1)) @ w2).sum()

    forbidden = [(b, n, mult * d)]
    bad = jax.checkpoint(
        blk, policy=jax.checkpoint_policies.everything_saveable)
    violations = audit_remat_residuals(
        bad, x, forbidden=forbidden, label="toy")
    assert len(violations) == 1, violations  # ONE line, deduped
    assert "remat-residual" in violations[0]
    assert str((b, n, mult * d)) in violations[0]
    good = jax.checkpoint(
        blk, policy=jax.checkpoint_policies.nothing_saveable)
    assert audit_remat_residuals(
        good, x, forbidden=forbidden, label="toy") == []


# ----------------------------------------------------------------------
# Host offload + donation / memory audits
# ----------------------------------------------------------------------


@pytest.fixture(scope="module")
def tiny_step_case():
    """One tiny chunked train step shared by the offload/donation tests."""
    model = RingTransformer(
        num_tokens=VOCAB, dim=16, depth=1, heads=2, dim_head=8,
        bucket_size=8, causal=True, use_ring=False, remat=True,
        remat_policy="nothing_saveable", ff_chunk_size=8,
        loss_chunk_size=8,
    )
    tokens = jnp.zeros((1, 33), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), tokens)
    opt = optax.adam(1e-3)

    def loss_fn(p, t):
        return model.apply(p, t, return_loss=True)

    return loss_fn, opt, params, opt.init(params), tokens


def test_host_offload_degrades_to_noop_on_cpu(tiny_step_case):
    """The CPU backend lists a pinned_host space but compiles in-graph
    host placement away, so the compat probe reports no host space there:
    host_device_put is the identity, and the offloaded step is
    bit-identical to the plain one — offload must never change values,
    with or without a host space."""
    kinds = {m.kind for d in jax.local_devices()
             for m in d.addressable_memories()}
    assert "pinned_host" in kinds  # listed by jax 0.9.0 ...
    assert compat.host_memory_kind() is None  # ... but not a placement target
    tree = {"a": jnp.ones(3)}
    assert compat.host_device_put(tree)["a"] is tree["a"]

    loss_fn, opt, params, opt_state, tokens = tiny_step_case
    base = make_train_step(loss_fn, opt)
    off = make_train_step(loss_fn, opt, offload_opt_state=True)
    pb, ob, lb = base(params, opt_state, tokens)
    po, oo, lo = off(params, opt_state, tokens)
    assert float(lb) == float(lo)
    for a, b in zip(jax.tree.leaves(pb), jax.tree.leaves(po)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_donation_audit_on_chunked_step(tiny_step_case):
    """The composed chunked step with jit_donate: every donated byte
    (params + opt state) aliases in the compiled executable — and the
    host-offload placement audit passes (vacuously here: no host space)."""
    loss_fn, opt, params, opt_state, tokens = tiny_step_case
    step = make_train_step(loss_fn, opt, jit_donate=True)
    assert audit_donation(
        step, params, opt_state, tokens, label="step") == []
    assert audit_host_offload(
        step, params, opt_state, tokens, label="step") == []


def test_chunked_step_temp_bytes_below_dense(tiny_step_case):
    """The compiler's own accounting proves the memory claim: the chunked
    (FFN + CE) train program's peak scratch bytes sit strictly below the
    dense program's at equal shape."""
    loss_fn, opt, params, opt_state, tokens = tiny_step_case
    dense_model = RingTransformer(
        num_tokens=VOCAB, dim=16, depth=1, heads=2, dim_head=8,
        bucket_size=8, causal=True, use_ring=False, remat=True,
        remat_policy="nothing_saveable",
    )

    def temp(loss):
        fn = compat.jit(jax.value_and_grad(loss))
        mem = compiled_memory(fn.lower(params, tokens).compile())
        assert "temp_bytes" in mem, mem
        return mem["temp_bytes"]

    t_chunk = temp(loss_fn)
    t_dense = temp(lambda p, t: dense_model.apply(p, t, return_loss=True))
    assert t_chunk < t_dense, (t_chunk, t_dense)


def test_train_memory_estimate_tracks_knobs():
    """The analytic peak-HBM model: chunking shrinks the transient term,
    save_attn grows the saved term, offload drops the optimizer term —
    and a 1M-token config at dim 512 fits a 16 GB chip."""
    kw = dict(seq_len=1 << 20, dim=512, depth=2, heads=8, vocab=256,
              n_params=28_000_000, dtype_bytes=2)
    chunked = train_memory_estimate(
        ff_chunk_size=2048, loss_chunk_size=2048, remat_policy="save_attn",
        **kw)
    dense = train_memory_estimate(remat_policy="save_attn", **kw)
    assert chunked["peak_hbm_bytes"] < dense["peak_hbm_bytes"]
    assert chunked["peak_hbm_gb"] < 16.0, chunked
    off = train_memory_estimate(
        ff_chunk_size=2048, loss_chunk_size=2048,
        remat_policy="save_attn", offload_opt_state=True, **kw)
    assert off["peak_hbm_bytes"] < chunked["peak_hbm_bytes"]
    saved_light = train_memory_estimate(
        ff_chunk_size=2048, loss_chunk_size=2048,
        remat_policy="nothing_saveable", **kw)
    assert (saved_light["saved_activation_bytes"]
            < chunked["saved_activation_bytes"])


# ----------------------------------------------------------------------
# Slow tier: CLI + the full sweeps
# ----------------------------------------------------------------------


@pytest.mark.slow
def test_check_contracts_memory_cli():
    """tools/check_contracts.py --memory: 6/6 checks hold, exit 0."""
    proc = subprocess.run(
        [sys.executable, "tools/check_contracts.py", "--memory"],
        capture_output=True, text=True, timeout=600,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "memory checks hold" in proc.stdout
    assert "FAIL" not in proc.stdout


@pytest.mark.slow
@pytest.mark.parametrize("layout", ["zigzag", "hybrid"])
def test_transformer_ff_chunked_other_layouts(rng, layout):
    """ff_chunk_size under the other sequence-parallel layouts (the fast
    tier pins striped ring)."""
    if layout == "hybrid":
        mesh = create_mesh(ulysses_size=2, ring_size=4)
        kw = dict(sequence_parallel="hybrid", heads=4)
    else:
        mesh = create_mesh(ring_size=8)
        kw = dict(sequence_parallel="zigzag", heads=4)
    common = dict(num_tokens=VOCAB, dim=32, depth=2, dim_head=8,
                  bucket_size=4, causal=True, mesh=mesh, **kw)
    m_d = RingTransformer(**common)
    m_c = RingTransformer(ff_chunk_size=2, **common)
    tokens = jnp.asarray(rng.integers(0, VOCAB, (2, 64)), jnp.int32)
    params = m_d.init(jax.random.PRNGKey(0), tokens)
    ld, gd = jax.jit(jax.value_and_grad(
        lambda p: m_d.apply(p, tokens, return_loss=True)))(params)
    lc, gc = jax.jit(jax.value_and_grad(
        lambda p: m_c.apply(p, tokens, return_loss=True)))(params)
    np.testing.assert_allclose(lc, ld, atol=1e-6)
    for a, b in zip(jax.tree.leaves(gd), jax.tree.leaves(gc)):
        np.testing.assert_allclose(a, b, atol=1e-5)


@pytest.mark.slow
def test_train_example_memory_flags(tmp_path):
    """examples/train.py with the whole memory-axis flag set: loss falls,
    metrics carry the compiled peak-memory fields."""
    import json as _json

    proc = subprocess.run(
        [sys.executable, "examples/train.py", "--fake-devices", "8",
         "--steps", "6", "--seq-len", "128", "--remat-policy", "save_attn",
         "--ff-chunk-size", "8", "--loss-chunk-size", "32",
         "--offload-opt-state", "--metrics-dir", str(tmp_path),
         "--log-every", "2"],
        capture_output=True, text=True, timeout=900,
    )
    assert proc.returncode == 0, proc.stderr[-800:]
    rows = [
        _json.loads(line)
        for line in (tmp_path / "metrics.jsonl").read_text().splitlines()
        if line.strip()
    ]
    assert rows and "temp_bytes" in rows[-1], rows[-1].keys()
