"""Chip smoke: the quickest proof that the system still starts on the TPU.

One process that holds every local chip for its whole life.  It drives the
real trainer (``examples.train.main``) and the real decoder
(``examples.generate.main``) in-process at the flagship width — dim 512,
depth 2, 8 heads x 64, rotary, bf16, causal, Pallas kernels, save_attn
remat, vocab 256 — and compiles every Pallas entry point once, checking
each against the repo's own XLA reference.  Any failed stage ends the run
with a non-zero exit; so does a machine where jax finds no TPU.  Numbers
printed here are set-up facts (did it start, how long did compiling take),
not performance claims.

    python chip_smoke.py                 # every stage
    python chip_smoke.py census decode   # a subset, while debugging

Stages: device, train1 (one chip, seq 262144, 4 steps), ring (all chips:
seq-8192 loss parity against one chip, then global seq 262144), decode
(16 tokens against a 2^20-token GQA 8/2 cache; ring-sharded too on N
chips), census (every Pallas kernel, n = 4096).  What each stage measured
goes out as one ``[summary]`` line; the last line of stdout is the verdict
alone, ``{"ok": true, "device": {"platform": ..., "kind": ..., "count":
...}}`` with exactly those keys (``"ok": false`` and a non-zero exit when
a stage failed; no verdict line at all without a TPU).
"""

from __future__ import annotations

import contextlib
import io
import json
import logging
import math
import sys
import time

STAGES = ("train1", "ring", "decode", "census")

FLAGSHIP = ["--dim", "512", "--depth", "2", "--heads", "8", "--dim-head", "64",
            "--bf16", "--use-pallas"]
TRAIN = FLAGSHIP + ["--remat-policy", "save_attn", "--batch", "1",
                    "--log-every", "1"]
DECODE = FLAGSHIP + ["--kv-heads", "2", "--max-len", "1048576",
                     "--prompt-len", "4096", "--steps", "16"]
SEQ = 262144
PARITY_SEQ = 8192
PARITY_RTOL = 2e-2  # bf16 compute, f32 loss
CENSUS_N = 4096

# Census rows whose option raises the library's own one-line error on the
# TPU instead of reaching Mosaic: row -> text the error must contain.  A
# row listed here that compiles after all fails the smoke (stale entry).
KNOWN_REFUSED: dict[str, str] = {
    # the in-kernel-DMA ring: its hand-written HBM slices are not aligned
    # to Mosaic's (8, 128) tiling at any head width (ops/pallas_ring.py)
    "fused_ring_remote": "fused_ring_remote: refused by Mosaic on TPU",
}


class SmokeFailure(Exception):
    """A stage's check did not hold."""


def main(argv: list[str]) -> int:
    stages = tuple(argv) or STAGES
    unknown = set(stages) - set(STAGES)
    if unknown:
        print(f"chip_smoke: unknown stage(s) {sorted(unknown)}; "
              f"stages are {STAGES}", file=sys.stderr)
        return 2

    import jax

    # never sets jax_platforms: the backend is whatever the machine has
    backend = jax.default_backend()
    if backend != "tpu":
        print(f"chip_smoke: no TPU: jax.default_backend() is {backend!r} "
              f"({len(jax.devices())} x {jax.devices()[0].device_kind!r})",
              file=sys.stderr)
        return 1

    import jaxlib

    from ring_attention_tpu.utils import enable_compile_cache, resilience
    from ring_attention_tpu.utils.telemetry import PEAK_TFLOPS

    cache_dir = enable_compile_cache()
    dev0 = jax.devices()[0]
    n_dev = len(jax.devices())
    device = {"platform": dev0.platform, "kind": dev0.device_kind,
              "count": n_dev}
    tag = (f"platform={dev0.platform} device_kind={dev0.device_kind!r} "
           f"device_count={n_dev}")

    stdout = sys.stdout  # the real one: example runs redirect sys.stdout

    def say(stage: str, msg: str) -> None:
        print(f"[{stage}] {tag} | {msg}", file=stdout, flush=True)

    try:
        import libtpu

        libtpu_version = getattr(libtpu, "__version__", "unknown")
    except ImportError:
        libtpu_version = "not importable"
    say("device", f"jax {jax.__version__} jaxlib {jaxlib.__version__} "
                  f"libtpu {libtpu_version} compile_cache={cache_dir}")
    for d in jax.devices():
        say("device", f"id={d.id} process={d.process_index} "
                      f"coords={getattr(d, 'coords', None)} "
                      f"core_on_chip={getattr(d, 'core_on_chip', None)}")
    if dev0.device_kind not in PEAK_TFLOPS:
        print(f"chip_smoke: device_kind {dev0.device_kind!r} is not a key "
              f"of the peak table {sorted(PEAK_TFLOPS)}", file=sys.stderr)
        return 1

    launches = _capture_kernel_launches()
    summary: dict = {"stages": list(stages)}
    t_start = time.perf_counter()
    try:
        for stage in stages:
            if stage == "ring" and n_dev == 1:
                say(stage, "skipped: one device")
                continue
            t0 = time.perf_counter()
            launches.clear()
            summary[stage] = _STAGE_FNS[stage](say, n_dev, launches)
            events = resilience.degradation.events()
            if events:
                raise SmokeFailure(
                    f"{stage}: kernel degradation recorded: {events}")
            say(stage, f"stage ok in {time.perf_counter() - t0:.1f} s")
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        print(json.dumps({"ok": False, "device": device}), flush=True)
        return 1
    summary["seconds"] = round(time.perf_counter() - t_start, 1)
    summary["claim"] = None
    say("summary", json.dumps(summary))
    # the verdict, alone on the last line: exactly these keys
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


# ----------------------------------------------------------------------
# helpers
# ----------------------------------------------------------------------


class _Tagged(io.TextIOBase):
    """stdout for an example run in-process: every line the trainer or
    decoder prints comes out through ``say``, so it names the device."""

    def __init__(self, say, stage: str) -> None:
        self._say, self._stage, self._pending = say, stage, ""

    def write(self, text: str) -> int:
        *lines, self._pending = (self._pending + text).split("\n")
        for line in lines:
            self._say(self._stage, line)
        return len(text)


def _run_example(say, stage: str, main, argv: list[str]) -> dict:
    with contextlib.redirect_stdout(_Tagged(say, stage)):
        return main(argv)


def _capture_kernel_launches() -> list[str]:
    """Collect the kernels' trace-time launch records (name, tile, grid) —
    the tile a path actually asked Mosaic for, not a re-derivation."""
    records: list[str] = []

    class Collect(logging.Handler):
        def emit(self, record: logging.LogRecord) -> None:
            records.append(record.getMessage())

    log = logging.getLogger("ring_attention_tpu.ops.pallas_flash")
    log.setLevel(logging.INFO)
    log.addHandler(Collect())
    return records


def _tiles(launches: list[str], prefix: str) -> set[tuple[int, int]]:
    """(block_q, block_k) of the ``prefix`` kernels' launches at the
    longest query span seen — the step's own kernels, not the one-tile
    launch the jitted ``model.init`` makes."""
    seen = []
    for line in launches:
        if line.startswith(prefix):
            q = int(line.split("q=")[1].split()[0])
            bq, bk = line.split("tile=")[1].split()[0].split("x")
            seen.append((q, int(bq), int(bk)))
    longest = max((q for q, _, _ in seen), default=0)
    return {(bq, bk) for q, bq, bk in seen if q == longest}


def _check_train(say, stage: str, result: dict, launches: list[str],
                 steps: int) -> dict:
    """Finite, non-rising loss; 512..1024-class flash tiles; and whether
    ``block_until_ready`` waited for the step (the value fetch after it
    should find the value already there)."""
    losses = result["losses"]
    if len(losses) != steps or not all(math.isfinite(x) for x in losses):
        raise SmokeFailure(f"{stage}: losses {losses} (want {steps} finite)")
    if steps > 1 and losses[-1] > losses[0]:
        raise SmokeFailure(f"{stage}: loss rose: {losses}")
    for line in sorted(set(launches)):
        say(stage, f"kernel {line}")
    tiles = _tiles(launches, "flash_")
    from ring_attention_tpu.ops.pallas_flash import (
        DEFAULT_BLOCK_K,
        DEFAULT_BLOCK_Q,
    )

    if not tiles or any(
        min(t) < 512 or t[0] > DEFAULT_BLOCK_Q or t[1] > DEFAULT_BLOCK_K
        for t in tiles
    ):
        raise SmokeFailure(
            f"{stage}: flash tiles {sorted(tiles)} outside 512.."
            f"{DEFAULT_BLOCK_Q}x{DEFAULT_BLOCK_K}")
    steady = result["step_seconds"][1:] or result["step_seconds"]
    fetch = result["fetch_seconds"][1:] or result["fetch_seconds"]
    blocks = sum(fetch) < 0.05 * sum(steady)
    say(stage, f"compile {result['compile_seconds']:.1f} s | step seconds "
               f"(dispatch -> block_until_ready) "
               f"{[round(x, 3) for x in result['step_seconds']]} | loss "
               f"{[round(x, 4) for x in losses]}")
    say(stage, f"sync: value fetch after block_until_ready waited "
               f"{[round(x, 4) for x in result['fetch_seconds']]} s -> "
               f"block_until_ready {'blocks' if blocks else 'DOES NOT block'}")
    return {"compile_seconds": round(result["compile_seconds"], 1),
            "step_seconds": [round(x, 3) for x in result["step_seconds"]],
            "losses": [round(x, 4) for x in losses],
            "tiles": sorted(tiles),
            "block_until_ready_blocks": blocks}


def _spans(shardings: list[str], n_dev: int, what: str, stage: str) -> None:
    """A mesh-placed array's sharding must name a mesh of all n devices."""
    for s in shardings:
        if "NamedSharding" not in s or f"'seq': {n_dev}" not in s:
            raise SmokeFailure(
                f"{stage}: {what} sharding {s} does not span the "
                f"{n_dev}-device ring mesh")


# ----------------------------------------------------------------------
# stages
# ----------------------------------------------------------------------


def stage_train1(say, n_dev, launches) -> dict:
    from examples import train

    steps = 4
    result = _run_example(say, "train1", train.main, TRAIN + [
        "--devices", "1", "--seq-len", str(SEQ), "--steps", str(steps)])
    return _check_train(say, "train1", result, launches, steps)


def stage_ring(say, n_dev, launches) -> dict:
    from examples import train

    # placement + parity first, at a size where a wrong ring is cheap: a
    # mis-ordered ring on real torus coordinates still gives a finite
    # loss, and only the comparison with one chip catches it
    one = _run_example(say, "ring", train.main, TRAIN + [
        "--devices", "1", "--seq-len", str(PARITY_SEQ), "--steps", "1"])
    launches.clear()
    ring = _run_example(say, "ring", train.main, TRAIN + [
        "--seq-len", str(PARITY_SEQ), "--steps", "1"])
    a, b = one["losses"][0], ring["losses"][0]
    rel = abs(a - b) / abs(a)
    mesh = ring["mesh"]
    order = [(d.id, getattr(d, "coords", None)) for d in mesh.devices.flat]
    say("ring", f"seq {PARITY_SEQ} parity: one-chip loss {a:.5f} ring loss "
                f"{b:.5f} rel {rel:.2e} (tolerance {PARITY_RTOL}) | mesh "
                f"{dict(mesh.shape)} ring order (id, coords) {order}")
    if not rel <= PARITY_RTOL:
        raise SmokeFailure(f"ring: seq-{PARITY_SEQ} ring loss {b} != "
                           f"one-chip loss {a} (rel {rel:.2e})")
    launches.clear()
    steps = 4
    result = _run_example(say, "ring", train.main, TRAIN + [
        "--seq-len", str(SEQ), "--steps", str(steps)])
    out = _check_train(say, "ring", result, launches, steps)
    for name in ("params", "opt_state", "batch"):
        _spans(result["shardings"][name], n_dev, name, "ring")
    if not all("'seq'" in s.split("spec=")[1]
               for s in result["shardings"]["batch"]):
        raise SmokeFailure("ring: batch is not sharded over the seq axis: "
                           f"{result['shardings']['batch']}")
    out["parity"] = {"one_chip_loss": a, "ring_loss": b, "rel": rel}
    return out


def stage_decode(say, n_dev, launches) -> dict:
    from examples import generate

    out = {}
    runs = [("one chip", ["--devices", "1"])]
    if n_dev > 1:
        runs.append((f"cache sharded over {n_dev} chips", []))
    for label, extra in runs:
        launches.clear()
        r = _run_example(say, "decode", generate.main, DECODE + extra)
        toks = r["tokens"]
        if len(toks) != 16 or not all(0 <= t < 256 for t in toks):
            raise SmokeFailure(f"decode ({label}): tokens {toks}")
        if extra == []:
            _spans(r["cache_shardings"], n_dev, "decode cache", "decode")
        gaps = sorted(r["token_gaps"])
        p50 = gaps[len(gaps) // 2]
        p95 = gaps[min(len(gaps) - 1, math.ceil(0.95 * len(gaps)) - 1)]
        for line in sorted(set(launches)):
            say("decode", f"{label}: kernel {line}")
        if not _tiles(launches, "flash_decode"):
            raise SmokeFailure(f"decode ({label}): no flash_decode launch")
        say("decode", f"{label}: compile {r['compile_seconds']:.1f} s | "
                      f"prefill {r['prefill_seconds']:.3f} s | token gap p50 "
                      f"{p50 * 1e3:.2f} ms p95 {p95 * 1e3:.2f} ms over "
                      f"{len(gaps)} tokens | cache "
                      f"{'; '.join(r['cache_shardings'])} | tokens {toks}")
        out[label] = {"compile_seconds": round(r["compile_seconds"], 1),
                      "prefill_seconds": round(r["prefill_seconds"], 3),
                      "gap_ms_p50": round(p50 * 1e3, 2),
                      "gap_ms_p95": round(p95 * 1e3, 2)}
    return out


def stage_census(say, n_dev, launches) -> dict:
    """Compile and run every Pallas entry point once at flagship width,
    n = 4096, against the XLA flash reference.  Non-interpret: the
    kernels' interpret default is off on the TPU backend ``main`` has
    already required."""
    rows = _census_rows(n_dev)
    table = {}
    failed = []
    for name, fn in rows:
        want = KNOWN_REFUSED.get(name)
        try:
            detail = fn()
            verdict = f"ok ({detail})"
            if want is not None:
                failed.append(f"{name}: listed as refused but compiled")
        except Exception as e:  # noqa: BLE001 — the census reports every row
            first = str(e).strip().splitlines()[0][:300] if str(e) else repr(e)
            verdict = f"refused: {type(e).__name__}: {first}"
            if want is None or want not in str(e):
                failed.append(f"{name}: {verdict}")
        table[name] = verdict
        say("census", f"{name:34s} {verdict}")
    if failed:
        raise SmokeFailure("census: " + " || ".join(failed))
    return table


def _census_rows(n_dev):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import lax
    from jax.sharding import Mesh, PartitionSpec as P

    from ring_attention_tpu.ops import pallas_flash as pf
    from ring_attention_tpu.ops import pallas_ring as pr
    from ring_attention_tpu.ops.attention import default_attention
    from ring_attention_tpu.ops.flash import flash_attention
    from ring_attention_tpu.parallel import create_mesh
    from ring_attention_tpu.parallel import ring as ring_mod
    from ring_attention_tpu.utils import compat

    n = CENSUS_N
    rng = np.random.default_rng(0)

    def qkv(h=8, hk=8, d=64, nq=n, nk=n):
        def mk(heads, length):
            return jnp.asarray(
                rng.standard_normal((1, heads, length, d)), jnp.bfloat16)

        return mk(h, nq), mk(hk, nk), mk(hk, nk)

    def close(got, want, what, tol=2e-2):
        got = np.asarray(got, np.float32)
        want = np.asarray(want, np.float32)
        if got.shape != want.shape or not np.isfinite(got).all():
            raise SmokeFailure(f"{what}: shape {got.shape} vs {want.shape} "
                               f"or non-finite values")
        rel = float(np.linalg.norm(got - want)
                    / max(np.linalg.norm(want), 1e-30))
        if rel > tol:
            raise SmokeFailure(f"{what}: rel-L2 {rel:.3e} > {tol}")
        return f"rel-L2 {rel:.1e}"

    docs = (0, n // 4, 3 * n // 4)
    window = n // 4
    seg = jnp.asarray(
        np.searchsorted(docs, np.arange(n), side="right") - 1, jnp.int32
    )[None, :]
    variants = {
        "plain": (dict(), dict(), {}),
        "window": (dict(window=window), dict(window=window), {}),
        "segment_ids": (dict(segment_ids=seg), dict(segment_ids=seg), {}),
        "doc_starts": (dict(doc_starts=docs), dict(segment_ids=seg), {}),
        "gqa8/2": (dict(), dict(), dict(hk=2)),
        "d128": (dict(), dict(), dict(d=128)),
    }
    rows = []

    def loss(fn, **kw):
        def f(q, k, v):
            out = fn(q, k, v, causal=True, **kw).astype(jnp.float32)
            # a fixed non-uniform cotangent: every gradient is non-trivial
            return (out * jnp.cos(jnp.arange(out.shape[-1]))).sum()

        return f

    for vname, (pkw, xkw, shape) in variants.items():
        q, k, v = qkv(**shape)

        def fwd(pkw=pkw, xkw=xkw, q=q, k=k, v=v):
            got = jax.jit(lambda q, k, v: pf.pallas_flash_attention(
                q, k, v, causal=True, **pkw))(q, k, v)
            want = jax.jit(lambda q, k, v: flash_attention(
                q, k, v, causal=True, bucket_size=512, **xkw))(q, k, v)
            return close(got, want, "fwd")

        def bwd(pkw=pkw, xkw=xkw, q=q, k=k, v=v):
            got = jax.jit(jax.grad(loss(
                pf.pallas_flash_attention, **pkw
            ), argnums=(0, 1, 2)))(q, k, v)
            want = jax.jit(jax.grad(loss(
                flash_attention, bucket_size=512, **xkw
            ), argnums=(0, 1, 2)))(q, k, v)
            return ", ".join(
                f"d{x} " + close(g, w, f"d{x}", tol=4e-2)
                for x, g, w in zip("qkv", got, want))

        def partials(pkw=pkw, xkw=xkw, q=q, k=k, v=v):
            kw = dict(pkw)
            window = kw.pop("window", None)
            parts = jax.jit(lambda q, k, v: pf.pallas_flash_partials(
                q, k, v, scale=q.shape[-1] ** -0.5, causal_offset=0,
                window_lo=None if window is None else -(window - 1),
                **kw))(q, k, v)
            got, _ = pf.finalize_partials(parts)
            want = jax.jit(lambda q, k, v: flash_attention(
                q, k, v, causal=True, bucket_size=512, **xkw))(q, k, v)
            return close(got, want, "partials")

        rows += [(f"flash_fwd_tile/{vname}", fwd),
                 (f"flash_bwd_dkv_dq/{vname}", bwd),
                 (f"flash_partials_tile/{vname}", partials)]

    # decode kernels: one query token against a 2-block GQA 8/2 cache
    nk = 2 * pf.DEFAULT_BLOCK_DECODE
    qd, kd, vd = qkv(hk=2, nq=1, nk=nk)
    valid = jnp.arange(nk)[None, :] < nk - 100

    def decode():
        got, _ = jax.jit(lambda q, k, v, m: pf.pallas_flash_decode(
            q, k, v, m))(qd, kd, vd, valid)
        return close(got, default_attention(qd, kd, vd, valid), "decode")

    def decode_q8():
        kvq = pf.quantize_kv_cache(kd, vd)
        got, _ = jax.jit(lambda q, kv, m: pf.pallas_flash_decode_q8(
            q, kv, m))(qd, kvq, valid)
        kdq, vdq = pf.dequantize_kv_cache(kvq, qd.dtype)
        return close(got, default_attention(qd, kdq, vdq, valid), "decode_q8")

    q, k, v = qkv()

    def fwd_q8():
        got = jax.jit(lambda q, k, v: pf.pallas_flash_attention(
            q, k, v, causal=True, compute_dtype="int8"))(q, k, v)
        want = jax.jit(lambda q, k, v: flash_attention(
            q, k, v, causal=True, bucket_size=512))(q, k, v)
        return close(got, want, "fwd_q8", tol=5e-2)

    def partials_q8():
        parts = jax.jit(lambda q, k, v: pf.pallas_flash_partials(
            q, k, v, scale=q.shape[-1] ** -0.5, causal_offset=0,
            compute_dtype="int8"))(q, k, v)
        got, _ = pf.finalize_partials(parts)
        want = jax.jit(lambda q, k, v: flash_attention(
            q, k, v, causal=True, bucket_size=512))(q, k, v)
        return close(got, want, "partials_q8", tol=5e-2)

    rows += [("flash_decode", decode), ("flash_decode_q8", decode_q8),
             ("flash_fwd_tile_q8", fwd_q8),
             ("flash_partials_tile_q8", partials_q8)]

    scale = 64 ** -0.5

    def reference(q, k, v):
        return jax.jit(lambda q, k, v: flash_attention(
            q, k, v, causal=True, bucket_size=512))(q, k, v)

    def fused_local():
        got, _ = jax.jit(lambda q, k, v: pr.fused_ring_local(
            q, k, v,
            origins=jnp.zeros((1,), jnp.int32),
            his=jnp.zeros((1,), jnp.int32),
            los=jnp.full((1,), -n, jnp.int32),
            works=jnp.ones((1,), jnp.int32),
            n_local=n, scale=scale))(q, k, v)
        return close(got, reference(q, k, v), "fused_ring_local")

    def fused_remote():
        """The remote tier on a ring of every device: contiguous causal
        layout, real MESH neighbour coordinates, against the one-chip
        reference (one device: ``_probe_fused_remote``'s self-ring)."""
        devices = jax.devices()
        ring = len(devices)
        mesh = (Mesh(np.array(devices), ("seq",)) if ring == 1
                else create_mesh(ring_size=ring, devices=devices))
        n_local = n // ring
        spec = (P(None, None, "seq", None) if ring == 1
                else P("data", None, "seq", None))

        def core(q, k, v):
            rank = lax.axis_index("seq")
            _, his, los, works = ring_mod._fused_tables(
                rank, ring, n_local, True, False, None, ring)
            coords = pr.neighbor_mesh_coords("seq", ring)
            return pr.fused_ring_remote(
                q, k, v, his=his, los=los, works=works, nbr_coords=coords,
                scale=q.shape[-1] ** -0.5)[0]

        fn = compat.shard_map(core, mesh=mesh, in_specs=(spec,) * 3,
                              out_specs=spec, check_vma=False)
        return close(jax.jit(fn)(q, k, v), reference(q, k, v),
                     f"fused_ring_remote x{ring}")

    rows += [("fused_ring_local", fused_local),
             ("fused_ring_remote", fused_remote)]
    return rows


_STAGE_FNS = {"train1": stage_train1, "ring": stage_ring,
              "decode": stage_decode, "census": stage_census}


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
