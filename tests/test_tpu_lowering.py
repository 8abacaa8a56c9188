"""Cross-lower every Pallas entry point for the TPU, on the CPU.

``jax.export.export(jax.jit(f), platforms=["tpu"])`` runs the
Pallas-to-Mosaic LOWERING (block-shape legality, layouts, primitive
support) without a chip.  It does not run the Mosaic compiler: whatever
this refuses the chip refuses too, and whatever it accepts the chip may
still refuse (VMEM, tiling of hand-written DMA slices) — ``chip_smoke.py``
owns that half.  This is the tier that would have caught the ``(1, block)``
per-row block specs and the 1x1 tile an odd sequence length produced.
"""

import json
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from ring_attention_tpu import RingTransformer, create_mesh
from ring_attention_tpu.ops import pallas_flash as pf
from ring_attention_tpu.ops import pallas_ring as pr
from ring_attention_tpu.parallel import ring as ring_mod
from ring_attention_tpu.utils import compat, make_train_step

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
N = 2048  # two default tiles per side: exercises real (non-full) blocks


def tpu_lower(fn, *args):
    """Lower ``fn(*args)`` for the TPU; raises what the lowering raises."""
    return jax.export.export(jax.jit(fn), platforms=["tpu"])(*args)


def sds(*shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype)


def qkv(b=1, h=8, hk=8, d=64, nq=N, nk=N):
    return sds(b, h, nq, d), sds(b, hk, nk, d), sds(b, hk, nk, d)


SEG = jnp.asarray(np.arange(N) // (N // 4), jnp.int32)
VARIANTS = {
    "plain": (dict(), dict()),
    "window": (dict(window=512), dict()),
    "segment_ids_batch2": (dict(), dict(b=2)),  # ids attached in the test
    "doc_starts": (dict(doc_starts=(0, N // 4, N // 2)), dict()),
    "gqa8/2": (dict(), dict(hk=2)),
    "gqa32/4": (dict(), dict(h=32, hk=4)),
    "d128": (dict(), dict(d=128)),
}


@pytest.mark.parametrize("variant", sorted(VARIANTS))
def test_flash_fwd_and_bwd_lower(variant):
    """flash_fwd_tile + flash_bwd_dkv_dq (the one-pass backward, with its
    hand-started dq copies), per mask variant.  ``segment_ids`` at batch
    2: a 2-D ``(b, n)`` operand blocked ``(1, block)`` is legal only at
    batch 1, which is how it hid."""
    kw, shape = VARIANTS[variant]
    q, k, v = qkv(**shape)
    if variant.startswith("segment_ids"):
        kw = dict(kw, segment_ids=jnp.stack([SEG, SEG]))

    def loss(q, k, v):
        return pf.pallas_flash_attention(
            q, k, v, causal=True, interpret=False, **kw
        ).astype(jnp.float32).sum()

    lowered = tpu_lower(jax.grad(loss, argnums=(0, 1, 2)), q, k, v)
    kernels = set(re.findall(r'kernel_name = "(\w+)"', lowered.mlir_module()))
    assert kernels == {"flash_fwd_tile", "flash_bwd_dkv_dq"}


@pytest.mark.parametrize("sessions, capacity", [(4, 131072), (1, 4096)])
def test_latent_decode_lowers_at_the_published_widths(sessions, capacity):
    """flash_decode_latent at dots.vlm1's widths (128 heads, a 512-wide
    latent, 64 rotary dimensions), the timed program's shapes and the
    check's: lane-aligned blocks of both cache arrays and the mask row."""
    from ring_attention_tpu.ops.pallas_latent import pallas_flash_decode_latent

    lowered = tpu_lower(
        lambda ql, qr, c, kr, m: pallas_flash_decode_latent(
            ql, qr, c, kr, m, interpret=False),
        sds(sessions, 128, 512), sds(sessions, 128, 64),
        sds(sessions, 1, capacity, 512), sds(sessions, 1, 64, capacity),
        sds(sessions, capacity, dtype=jnp.bool_))
    kernels = re.findall(r'kernel_name = "(\w+)"', lowered.mlir_module())
    assert kernels == ["flash_decode_latent"]


@pytest.mark.parametrize("sessions", [4, 1])
def test_ssm_decode_step_lowers_at_the_published_widths(sessions):
    """ssm_decode_step at granite-4.0-h-small's widths (128 heads of a 64 x
    128 float32 state), the timed program's sessions and the check's: the
    decays in SMEM, the heads' dt x transposed, the state aliased."""
    from ring_attention_tpu.ops.pallas_ssm import pallas_ssm_decode_step

    lowered = tpu_lower(
        lambda s, x, b, c, dt, a, d: pallas_ssm_decode_step(
            s, x, b, c, dt, a, d, interpret=False),
        sds(sessions, 128, 64, 128, dtype=jnp.float32), sds(sessions, 128, 64),
        sds(sessions, 128), sds(sessions, 128),
        sds(sessions, 128, dtype=jnp.float32), sds(128, dtype=jnp.float32),
        sds(128, dtype=jnp.float32))
    module = lowered.mlir_module()
    assert re.findall(r'kernel_name = "(\w+)"', module) == ["ssm_decode_step"]
    assert "output_operand_aliases" in module or "operand_aliases" in module


def test_flash_padding_mask_lowers_at_batch_2():
    """Non-causal attention with a key-padding mask: the mask rides the
    same per-token layout as the segment ids, fwd and bwd."""
    q, k, v = qkv(b=2)
    mask = jnp.ones((2, N), jnp.bool_)

    def loss(q, k, v):
        return pf.pallas_flash_attention(
            q, k, v, mask, interpret=False
        ).astype(jnp.float32).sum()

    tpu_lower(jax.grad(loss, argnums=(0, 1, 2)), q, k, v)


@pytest.mark.parametrize("resume", [False, True])
def test_flash_partials_lower(resume):
    """flash_partials_tile (ring hop form), fresh and carry-resuming."""
    q, k, v = qkv()

    def f(q, k, v):
        carry = pf.init_partials(1, 8, N, 64) if resume else None
        return pf.pallas_flash_partials(
            q, k, v, scale=0.125, causal_offset=0, carry=carry,
            interpret=False)

    tpu_lower(f, q, k, v)


def test_odd_length_pads_to_real_tiles():
    """A power-of-two batch after the label shift (n - 1 tokens) must not
    halve the tile down to 1x1: the span is padded to a lane multiple."""
    assert pf._block_sizes(N - 1, N - 1, None, None) == (1, 1)  # the defect
    assert pf._tileable_len(N - 1, pf.DEFAULT_BLOCK_Q) == N
    q, k, v = qkv(nq=N - 1, nk=N - 1)
    tpu_lower(
        lambda q, k, v: pf.pallas_flash_attention(
            q, k, v, causal=True, interpret=False), q, k, v)


def test_int8_forward_lowers():
    """flash_fwd_tile_q8 and flash_partials_tile_q8 (compute_dtype int8)."""
    q, k, v = qkv()
    tpu_lower(
        lambda q, k, v: pf.pallas_flash_attention(
            q, k, v, causal=True, interpret=False, compute_dtype="int8"),
        q, k, v)
    tpu_lower(
        lambda q, k, v: pf.pallas_flash_partials(
            q, k, v, scale=0.125, causal_offset=0, interpret=False,
            compute_dtype="int8"),
        q, k, v)


def test_decode_kernels_lower():
    """flash_decode and flash_decode_q8 against a two-block GQA cache."""
    nk = 2 * pf.DEFAULT_BLOCK_DECODE
    q, k, v = qkv(hk=2, nq=1, nk=nk)
    mask = jnp.ones((1, nk), jnp.bool_)
    tpu_lower(
        lambda q, k, v: pf.pallas_flash_decode(
            q, k, v, mask, interpret=False), q, k, v)
    kv = pf.QuantizedKV(
        sds(1, 2, nk, 64, dtype=jnp.int8), sds(1, 2, nk, dtype=jnp.float32),
        sds(1, 2, nk, 64, dtype=jnp.int8), sds(1, 2, nk, dtype=jnp.float32))
    tpu_lower(
        lambda q, kv: pf.pallas_flash_decode_q8(
            q, kv, mask, interpret=False), q, kv)


def _one_hop_tables():
    return dict(origins=jnp.zeros((1,), jnp.int32),
                his=jnp.zeros((1,), jnp.int32),
                los=jnp.full((1,), -N, jnp.int32),
                works=jnp.ones((1,), jnp.int32))


def test_fused_ring_local_lowers():
    q, k, v = qkv()
    tpu_lower(
        lambda q, k, v: pr.fused_ring_local(
            q, k, v, n_local=N, scale=0.125, interpret=False,
            **_one_hop_tables()), q, k, v)


def test_fused_ring_remote_lowers():
    """The in-kernel-DMA tier on a one-device ring, as
    ``resilience._probe_fused_remote`` launches it.  The lowering accepts
    it; the Mosaic COMPILER on the chip does not (its HBM slices are not
    tile-aligned), which is why it raises on a TPU backend."""
    tables = _one_hop_tables()
    tables.pop("origins")

    def core(q, k, v):
        return pr.fused_ring_remote(
            q, k, v, nbr_coords=jnp.zeros((2, 1), jnp.int32), scale=0.125,
            **tables)[0]

    mesh = Mesh(np.array(jax.devices()[:1]), ("ring",))
    fn = compat.shard_map(core, mesh=mesh, in_specs=(P(),) * 3,
                          out_specs=P(), check_vma=False)
    tpu_lower(fn, *qkv())


def _flagship(mesh, seq):
    """The flagship width at depth 1 (width is what the lowering sees)."""
    ring = 1 if mesh is None else mesh.shape["seq"]
    return RingTransformer(
        num_tokens=256, dim=512, depth=1, heads=8, dim_head=64, causal=True,
        striped=True, bucket_size=seq // ring, mesh=mesh,
        use_ring=mesh is not None, use_pallas=True, remat=True,
        remat_policy="save_attn", dtype=jnp.bfloat16)


@pytest.mark.parametrize("ring", [1, 4])
def test_flagship_train_step_lowers(devices, ring):
    """The train step ``examples/train.py`` builds — a power-of-two token
    batch, shard-sized ``bucket_size`` — on one device and on a 4-device
    striped ring, and the tile every launch asked for."""
    import logging

    import optax

    seq = 8192
    mesh = create_mesh(ring_size=ring, devices=devices[:ring]) if ring > 1 else None
    model = _flagship(mesh, seq)
    tokens = jnp.zeros((1, seq), jnp.int32)
    params = jax.eval_shape(
        model.init, jax.random.PRNGKey(0), jnp.zeros((1, 128 * ring), jnp.int32))
    opt = optax.adamw(3e-4)
    opt_state = jax.eval_shape(opt.init, params)
    step = make_train_step(
        lambda p, t: model.apply(p, t, return_loss=True), opt)

    records = []

    class Collect(logging.Handler):
        def emit(self, record):
            records.append(record.getMessage())

    log = logging.getLogger("ring_attention_tpu.ops.pallas_flash")
    handler, level = Collect(), log.level
    log.addHandler(handler)
    log.setLevel(logging.INFO)
    try:
        # kernels default to interpret mode on this backend; lower the
        # compiled form
        orig = pf._interpret_default
        pf._interpret_default = lambda: False
        pr._interpret_default = pf._interpret_default
        tpu_lower(step, params, opt_state, tokens)
    finally:
        pf._interpret_default = orig
        pr._interpret_default = orig
        log.removeHandler(handler)
        log.setLevel(level)
    tiles = {r.split("tile=")[1].split()[0] for r in records
             if r.startswith("flash_") and f"q={seq // ring} " in r}
    assert tiles == {"1024x1024"}, records


def test_ring_tile_never_exceeds_one_chip_default():
    """``bucket_size`` is the XLA scan bucket (train.py sizes it to the
    shard); the ring's Pallas tile is capped at the kernels' default."""
    assert ring_mod._pallas_blocks(65536, 65536, 65536) == (
        pf.DEFAULT_BLOCK_Q, pf.DEFAULT_BLOCK_K)
    assert ring_mod._q8_block(65536, 65536, 65536) == pf.DEFAULT_BLOCK_K
    assert ring_mod._pallas_blocks(512, 65536, 65536) == (512, 512)


def test_chip_smoke_refuses_cpu():
    """``python chip_smoke.py`` on a machine without a TPU: non-zero exit,
    one line naming the platform it found, and no result on stdout."""
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        capture_output=True, text=True, timeout=300, cwd=REPO,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert proc.returncode != 0
    lines = [ln for ln in proc.stderr.splitlines()
             if ln.startswith("chip_smoke:")]
    assert len(lines) == 1 and "'cpu'" in lines[0], proc.stderr[-2000:]
    assert proc.stdout.strip() == ""


_STUB_SMOKE = """
import sys, jax, chip_smoke
from ring_attention_tpu.utils.telemetry import PEAK_TFLOPS
jax.default_backend = lambda: "tpu"
PEAK_TFLOPS[jax.devices()[0].device_kind] = 1.0
def stage(say, n_dev, launches):
    say("census", "stub row")
    if sys.argv[1] == "fail":
        raise chip_smoke.SmokeFailure("stub failed")
    return {"row": "ok"}
chip_smoke._STAGE_FNS["census"] = stage
sys.exit(chip_smoke.main(["census"]))
"""


@pytest.mark.parametrize("outcome", ["pass", "fail"])
def test_chip_smoke_verdict_line(outcome, tmp_path):
    """The last stdout line is the verdict and nothing else: exactly
    ``ok`` and ``device`` = ``platform``/``kind``/``count``.  Stages are
    stubbed and the backend name faked; the real stages need the chip."""
    proc = subprocess.run(
        [sys.executable, "-c", _STUB_SMOKE, outcome],
        capture_output=True, text=True, timeout=300, cwd=REPO,
        env={**os.environ, "JAX_PLATFORMS": "cpu",
             "JAX_COMPILATION_CACHE_DIR": str(tmp_path)},
    )
    assert (proc.returncode == 0) == (outcome == "pass"), proc.stderr[-2000:]
    verdict = json.loads(proc.stdout.splitlines()[-1])
    assert set(verdict) == {"ok", "device"}
    assert verdict["ok"] is (outcome == "pass")
    device = verdict["device"]
    assert set(device) == {"platform", "kind", "count"}
    assert isinstance(device["platform"], str)
    assert isinstance(device["kind"], str)
    assert type(device["count"]) is int and device["count"] >= 1
