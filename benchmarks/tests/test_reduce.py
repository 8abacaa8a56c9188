"""The yardstick's arithmetic on hand-worked shapes, every reducer on a
hand-built trace whose answers are plain, and the reader and reducers on a
small trace recorded on the chip (``data/``, see ``data/README.md``)."""

import json
import os

import pytest

from benchmarks import reduce
from benchmarks.reduce import Op, Trace

HERE = os.path.dirname(os.path.abspath(__file__))
SHAPE_3B = {"hidden": 3072, "ffn": 12288, "heads": 24, "kv_heads": 2,
            "dim_head": 128, "vocab": 49152, "depth": 2, "chips": 1,
            "batch": 1, "seq": 65536}


def test_attention_flops_64k_causal():
    # 2 matmuls x 2 x 65536^2 x 128 x 24 heads / 2 (causal) = 26.4 TFLOP
    got = reduce.attention_flops(65536, 24, 128, reduce.FWD_MATMULS)
    assert got == pytest.approx(26.39e12, rel=1e-3)
    assert reduce.flash_fwd_flops_per_step(SHAPE_3B) == 2 * got
    assert reduce.flash_bwd_flops_per_step(SHAPE_3B) == 4 * got
    ring = dict(SHAPE_3B, chips=4)
    assert reduce.flash_fwd_flops_per_step(ring) == got / 2


def test_cache_bytes_512k():
    # 524,288 positions x 4 kv heads x 128 x bf16 x (k and v) = 1.07 GB
    assert reduce.kv_cache_bytes(524288, 4, 128) == 1073741824
    shape = {"depth": 4, "decode_start": 520192, "kv_heads": 4,
             "dim_head": 128}
    assert reduce.decode_cache_bytes_per_token(shape) == pytest.approx(
        4 * 1.0654e9, rel=1e-3)


def test_matmul_params_and_flops_per_token():
    # a layer: q 9.44 M, k+v 1.57 M, o 9.44 M, ffn 75.5 M = 95.9 M; head 151 M
    assert reduce.matmul_params(dict(SHAPE_3B, depth=1)) - 3072 * 49152 \
        == pytest.approx(95.9e6, rel=1e-3)
    assert reduce.matmul_params(SHAPE_3B) == pytest.approx(342.8e6, rel=1e-3)
    short = dict(SHAPE_3B, seq=4096, batch=16)
    # 6 x 342.8 M + 2 layers x 6 matmuls x 2 x (4096 / 2) x 24 x 128
    assert reduce.train_flops_per_token(short) == pytest.approx(
        6 * 342.8e6 + 2 * 6 * 2 * 2048 * 24 * 128, rel=1e-3)


def test_unknown_device_kind_is_an_error():
    assert reduce.peaks("TPU v5 lite")["flops"] == 197e12
    with pytest.raises(ValueError, match="no peaks for device_kind 'TPU v9'"):
        reduce.peaks("TPU v9")


def test_names_and_nesting():
    assert reduce.op_name("%fusion.12 = bf16[8]{0} fusion(...)") == "fusion"
    assert reduce.op_name("flash_fwd_tile.3.1") == "flash_fwd_tile"
    ops = reduce.nest([("while.1", 0.0, 10.0), ("a.1", 1.0, 2.0),
                       ("b", 4.0, 3.0), ("c", 12.0, 1.0)])
    by = {o.name: o for o in ops}
    assert by["while"].self_s == 5.0 and not by["while"].leaf
    assert by["a"].self_s == 2.0 and by["a"].leaf and by["c"].leaf


def test_percentile_is_nearest_rank():
    values = list(range(1, 101))
    assert reduce.percentile(values, 50) == 50
    assert reduce.percentile(values, 95) == 95
    assert reduce.percentile([3.0], 95) == 3.0


@pytest.fixture
def trace():
    """Two steps of 10 s on one chip.  Step 1: kernel 0-4, matmul 4-6,
    collective 6-7 alone, collective 7-8 under a matmul, idle 8-10.
    Step 2 the same, 10 s later; the fetch span covers each idle tail."""
    events, spans = [], []
    for t in (0.0, 10.0):
        events += [("flash_fwd_tile.1", t, 4.0), ("fusion.2", t + 4, 2.0),
                   ("collective-permute-done.1", t + 6, 1.0),
                   ("collective-permute-start.1", t + 7, 1.0),
                   ("fusion.3", t + 7, 1.0)]
        spans += [("bench/step", t, t + 8.5), ("bench/fetch", t + 8.5, t + 10)]
    return Trace(devices=[[Op(reduce.op_name(n), s, s + d, d)
                           for n, s, d in events]], spans=spans)


RUN = {"units": {"step": 2}, "series": {"gaps": [1.0, 2.0, 3.0, 4.0]},
       "rates": {"train_tokens_per_s": 1000.0}, "device_kind": "TPU v5 lite",
       "shape": SHAPE_3B}
STEPS = ["bench/step", "bench/fetch"]
COLLECTIVE = "^collective-permute"


def test_reducers_on_a_hand_built_trace(trace):
    r = reduce.REDUCERS
    assert r["op_time_ms"](trace, RUN, name_regex="^flash_", per="step",
                           spans=STEPS) == pytest.approx(4000.0)
    assert r["op_time_ms"](trace, RUN, name_regex=".", per="step",
                           exclude_regex="^flash_|" + COLLECTIVE,
                           spans=STEPS) == pytest.approx(3000.0)
    assert r["op_share"](trace, RUN, name_regex="^flash_",
                         spans=STEPS) == pytest.approx(40.0)
    assert r["idle_share"](trace, RUN, spans=STEPS) == pytest.approx(20.0)
    assert r["host_gap_ms"](trace, RUN, spans=STEPS,
                            per="step") == pytest.approx(2000.0)
    assert r["busy_time_ms"](trace, RUN,
                             spans=["bench/step"]) == pytest.approx(16000.0)
    assert r["op_time_ms"](trace, RUN, name_regex=COLLECTIVE, per="step",
                           spans=STEPS) == pytest.approx(2000.0)
    assert r["exposed_time_ms"](trace, RUN, name_regex=COLLECTIVE,
                                per="step",
                                spans=STEPS) == pytest.approx(1000.0)
    assert r["host_percentile"](trace, RUN, series="gaps", q=50) == 2.0
    # 52.8 TFLOP of forward attention in 4 s against 197 TFLOP/s
    assert r["roofline_share"](
        trace, RUN, name_regex="^flash_fwd", work="flash_fwd_flops_per_step",
        bound="flops", per="step", spans=STEPS) == pytest.approx(
            100 * 2 * 26.39e12 / 197e12 / 4.0, rel=1e-3)
    assert r["mfu"](trace, RUN, work="train_flops_per_token",
                    rate="train_tokens_per_s") == pytest.approx(
        100 * reduce.train_flops_per_token(SHAPE_3B) * 1000.0 / 197e12)


def test_summary_and_breakdown(trace):
    busy_s, window_s = reduce.device_summary(trace)
    assert (busy_s, window_s) == (pytest.approx(16.0), pytest.approx(20.0))
    out = reduce.breakdown(trace)
    assert out["device_ops"][0] == ["flash_fwd_tile", pytest.approx(8.0)]
    gaps = dict(map(tuple, out["idle_gaps"]))
    assert gaps["bench/fetch"] == pytest.approx(3.0)
    assert gaps["bench/step"] == pytest.approx(1.0)


def test_nothing_to_read_returns_nothing():
    empty = Trace()
    for name, fn in reduce.REDUCERS.items():
        args = {"op_time_ms": {"name_regex": "x"},
                "op_share": {"name_regex": "x"},
                "roofline_share": {"name_regex": "x", "bound": "flops",
                                   "work": "flash_fwd_flops_per_step",
                                   "per": "step"},
                "exposed_time_ms": {"name_regex": "x"},
                "host_percentile": {"series": "absent", "q": 50},
                "mfu": {"work": "train_flops_per_token", "rate": "absent"},
                }.get(name, {})
        assert fn(empty, RUN, **args) is None, name
    assert reduce.device_summary(empty) is None
    assert reduce.breakdown(empty) is None


def test_every_metric_file_names_a_reducer_and_its_arguments():
    import inspect

    root = os.path.join(os.path.dirname(HERE), "metrics")
    names = sorted(os.listdir(root))
    assert names
    for name in names:
        with open(os.path.join(root, name)) as f:
            spec = json.load(f)
        assert spec["name"] + ".json" == name
        fn = reduce.REDUCERS[spec["reducer"]]
        accepted = set(inspect.signature(fn).parameters) - {"trace", "run"}
        assert set(spec["arguments"]) <= accepted, name
        if "work" in spec["arguments"]:
            assert spec["arguments"]["work"] in reduce.WORK


RECORDED = os.path.join(HERE, "data", "toy.serve.xplane.pb.gz")
TOKEN_SPANS = ["bench/token", "bench/fetch"]


@pytest.fixture(scope="module")
def recorded():
    return reduce.load_trace(RECORDED)


def test_recorded_trace_reads(recorded):
    # one chip; prefill, then tokens, each a dispatch span and a fetch span
    assert len(recorded.devices) == 1 and len(recorded.devices[0]) > 100
    names = [s[0] for s in recorded.spans]
    assert names.count("bench/prefill") == 1
    assert names.count("bench/token") == names.count("bench/fetch") > 10
    ops = recorded.devices[0]
    assert all(o.self_s >= -1e-12 for o in ops)
    # self times add up to the union of the intervals: nothing counted twice
    lo = min(o.start for o in ops)
    hi = max(o.end for o in ops)
    assert sum(o.self_s for o in ops) == pytest.approx(
        reduce.total(reduce.busy(ops, lo, hi)), rel=1e-3)
    assert any(o.name == "flash_decode" for o in ops)


def test_reducers_on_the_recorded_trace(recorded):
    tokens = [s[0] for s in recorded.spans].count("bench/token")
    run = dict(RUN, units={"token": tokens}, shape={
        "depth": 2, "decode_start": 1024, "kv_heads": 2, "dim_head": 64})
    r = reduce.REDUCERS
    kernel = r["op_time_ms"](recorded, run, name_regex="^flash_decode",
                             per="token", spans=TOKEN_SPANS)
    rest = r["op_time_ms"](recorded, run, name_regex=".", per="token",
                           exclude_regex="^flash_decode", spans=TOKEN_SPANS)
    idle = r["host_gap_ms"](recorded, run, spans=TOKEN_SPANS, per="token")
    busy = r["busy_time_ms"](recorded, run, spans=TOKEN_SPANS, per="token")
    share = r["idle_share"](recorded, run, spans=TOKEN_SPANS)
    lo, hi = reduce.window(recorded, TOKEN_SPANS)
    gap = 1e3 * (hi - lo) / tokens
    assert 0 < kernel < busy < gap
    assert kernel + rest == pytest.approx(busy, rel=1e-3)
    assert busy + idle == pytest.approx(gap, rel=1e-6)
    assert share == pytest.approx(100 * idle / gap, rel=1e-6)
    assert 0 < r["op_share"](recorded, run, name_regex="^flash_decode",
                             spans=TOKEN_SPANS) < 100
    roof = r["roofline_share"](
        recorded, run, name_regex="^flash_decode", per="token",
        work="decode_cache_bytes_per_token", bound="hbm_bytes",
        spans=TOKEN_SPANS)
    assert 0 < roof < 100
    assert r["busy_time_ms"](recorded, run, spans=["bench/prefill"]) > 0
    # no collective on one chip: nothing to read
    assert r["exposed_time_ms"](recorded, run, name_regex=COLLECTIVE,
                                spans=TOKEN_SPANS) is None
    busy_s, window_s = reduce.device_summary(recorded)
    assert 0 < busy_s < window_s
    out = reduce.breakdown(recorded)
    assert 1 <= len(out["device_ops"]) <= 10
    assert out["idle_gaps"][0][0] in {"bench/fetch", "bench/token"}
