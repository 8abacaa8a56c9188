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


# ----------------------------------------------------------------------
# scopes: the reader's join and the two reducers that read it
# ----------------------------------------------------------------------

MANIFEST = os.path.join(os.path.dirname(os.path.dirname(HERE)),
                        "BENCHMARK.json")
METRICS = os.path.join(os.path.dirname(HERE), "metrics")
FF = "jit(step)/jvp(M)/ff_layers_0/Dense_0/dot_general"
FF_BWD = "jit(step)/transpose(jvp(M))/ff_layers_0/Dense_0/dot_general"
FF_AGAIN = ("jit(step)/transpose(jvp(M))/rematted_computation/ff_layers_0/"
            "Dense_0/dot_general")
ATTN = "jit(step)/jvp(M)/attn_layers_0/to_qkv/dot_general"
MOE = "jit(step)/jvp(M)/ff_layers_1/moe/dispatch/gather"
LOOP = "jit(step)/jvp(M)/ff_layers_1/_routed/while"


@pytest.fixture
def scoped():
    """One step of 20 s on one chip, the fetch span over its idle tail:
    feed-forward 0-2, the attention's product 2-3 and its kernel 3-5 under
    the module's path, a routed layer's loop 5-10 whose body holds a
    dispatch 6-7 and a grouped product 7-9, the feed-forward's recompute
    10-13 and backward 13-17, idle 17-20."""
    events = [("fusion.1", 0.0, 2.0, FF), ("fusion.2", 2.0, 1.0, ATTN),
              ("flash_fwd_tile.1", 3.0, 2.0, ATTN),
              ("while.1", 5.0, 5.0, LOOP), ("gather.1", 6.0, 1.0, MOE),
              ("ragged-dot-none.1", 7.0, 2.0, MOE),
              ("fusion.3", 10.0, 3.0, FF_AGAIN),
              ("fusion.4", 13.0, 4.0, FF_BWD)]
    return Trace(devices=[reduce.nest(events)],
                 spans=[("bench/step", 0.0, 17.0),
                        ("bench/fetch", 17.0, 20.0)])


def test_the_pass_is_what_jax_wrote_into_the_path(scoped):
    by = {(o.name, o.start): o for o in scoped.devices[0]}
    assert by["fusion", 0.0].scope == FF
    assert [by["fusion", t].pass_ for t in (0.0, 10.0, 13.0)] == [
        "forward", "recompute", "backward"]
    assert reduce.pass_of("jit(f)/transpose(jvp(g))/mul") == "backward"
    assert reduce.Op("x", 0.0, 1.0, 1.0).scope == ""
    assert reduce.Op("x", 0.0, 1.0, 1.0).pass_ == "forward"


@pytest.mark.parametrize("arguments, seconds", [
    ({"scopes": ["ff_layers_"]}, 14.0),
    ({"scopes": ["ff_layers_0"]}, 9.0),
    ({"scopes": ["ff_layers_"], "exclude_scopes": ["moe/"]}, 11.0),
    ({"scopes": ["ff_layers_"], "exclude_scopes": ["moe/", "_routed"]}, 9.0),
    ({"scopes": ["moe/"], "exclude_regex": "^ragged-dot"}, 1.0),
    ({"scopes": ["moe/"], "name_regex": "^ragged-dot"}, 2.0),
    ({"scopes": ["attn_layers_"]}, 3.0),
    ({"scopes": ["attn_layers_"], "exclude_regex": "^flash_"}, 1.0),
    ({"scopes": ["ff_layers_0"], "passes": ["forward"]}, 2.0),
    ({"scopes": ["ff_layers_0"], "passes": ["recompute"]}, 3.0),
    ({"passes": ["recompute", "backward"]}, 7.0),
    ({"scopes": ["_routed"]}, 2.0),  # the loop keeps what its body leaves
    ({"scopes": ["attn_layers_", "moe/"]}, 6.0),
    ({}, 17.0),  # any path, any name, any pass
], ids=["scopes", "one-module", "exclude_scopes", "exclude-two",
        "exclude_regex", "name_regex", "kernel-in-its-scope",
        "kernel-by-name", "forward", "recompute", "passes-alone",
        "while-self-time", "two-needles", "anything"])
def test_scope_time_on_a_hand_built_trace(scoped, arguments, seconds):
    run = {"units": {"step": 1}}
    assert reduce.scope_time_ms(scoped, run, **arguments) == pytest.approx(
        1e3 * seconds)


def test_scope_time_per_unit_and_inside_the_named_spans(scoped):
    run = {"units": {"step": 4}}
    assert reduce.scope_time_ms(scoped, run, scopes=["ff_layers_0"],
                                per="step") == pytest.approx(9e3 / 4)
    # only what starts inside the span counts
    scoped.spans.append(("bench/prefill", 0.0, 11.0))
    assert reduce.scope_time_ms(scoped, run, scopes=["ff_layers_0"],
                                spans=["bench/prefill"]) == pytest.approx(5e3)
    assert reduce.scope_time_ms(scoped, run, scopes=["ff_layers_0"],
                                spans=["bench/absent"]) is None


def test_scope_time_reads_the_chip_that_spent_most(scoped):
    second = reduce.nest([("fusion.1", 0.0, 6.0, FF)])
    both = Trace(devices=[scoped.devices[0], second], spans=scoped.spans)
    run = {"units": {"step": 1}}
    assert reduce.scope_time_ms(both, run, scopes=["ff_layers_0"],
                                passes=["forward"]) == pytest.approx(6e3)


def test_a_trace_without_scopes_gives_a_scope_metric_nothing(trace):
    assert all(o.scope == "" and o.pass_ == "forward"
               for o in trace.devices[0])
    assert reduce.scope_time_ms(trace, RUN, scopes=["ff_layers_"],
                                spans=STEPS) is None
    assert reduce.scope_time_ms(trace, RUN, passes=["recompute"],
                                spans=STEPS) is None
    # with no needle it is op_time_ms
    assert reduce.scope_time_ms(
        trace, RUN, name_regex="^flash_", per="step",
        spans=STEPS) == reduce.op_time_ms(
            trace, RUN, name_regex="^flash_", per="step", spans=STEPS)


def test_host_activity_is_host_time_on_the_hosts_clock(trace):
    """Each step of 10 s: the launch is open 8-8.5 on one thread and its
    enqueue 8.4-8.7 on another, the fetch 8.2-9.5 with a transfer inside
    it, a poll 9.5-9.8, and nothing the last 0.2 s."""
    for t in (0.0, 10.0):
        trace.host += [
            ("PjitFunction(jit(step))", t + 8.0, t + 8.5),
            ("EnqueueProgram", t + 8.4, t + 8.7),
            ("np.asarray(jax.Array)", t + 8.2, t + 9.5),
            ("tpu::System::TransferFromDevice", t + 8.6, t + 9.0),
            ("ReadSyncFlag", t + 9.5, t + 9.8)]
    dispatch, fetch = ["PjitFunction", "EnqueueProgram"], ["np.asarray",
                                                           "TransferFrom"]
    args = {"spans": STEPS, "per": "step"}
    r = reduce.REDUCERS["host_activity_ms"]
    # the union over threads, not the sum
    assert r(trace, RUN, activity=dispatch, **args) == pytest.approx(700.0)
    assert r(trace, RUN, activity=fetch, **args) == pytest.approx(1300.0)
    # a launch that is open wins
    assert r(trace, RUN, activity=fetch, exclude=dispatch,
             **args) == pytest.approx(800.0)
    assert r(trace, RUN, activity=["TransferFrom"], exclude=dispatch,
             **args) == pytest.approx(300.0)
    # the rest of the window, and the part of it with an event open
    assert r(trace, RUN, exclude=dispatch + fetch,
             **args) == pytest.approx(8500.0)
    assert r(trace, RUN, activity=[""], exclude=dispatch + fetch,
             **args) == pytest.approx(300.0)
    assert r(trace, RUN, **args) == pytest.approx(10000.0)
    assert r(trace, RUN, activity=dispatch, spans=["bench/fetch"],
             per="step") == pytest.approx(450.0)  # the extent 8.5-20
    assert r(trace, RUN, activity=["absent"], **args) is None
    # nothing of it reads the device: the same with no chip in the trace
    assert r(Trace(spans=trace.spans, host=trace.host), RUN,
             activity=dispatch, **args) == pytest.approx(700.0)


def _metric_files():
    return sorted(n[:-len(".json")] for n in os.listdir(METRICS))


@pytest.mark.parametrize("name", _metric_files())
def test_metric_file_binds_and_lists_cells_that_report_what_it_moves(name):
    import inspect

    with open(os.path.join(METRICS, name + ".json")) as f:
        spec = json.load(f)
    with open(MANIFEST) as f:
        manifest = json.load(f)
    assert spec["name"] == name and spec["note"]
    fn = reduce.REDUCERS[spec["reducer"]]
    inspect.signature(fn).bind(None, None, **spec["arguments"])
    entry = {m["name"]: m for m in manifest["per_layer"]}[name]
    for key in ("layer", "unit", "moves", "source"):
        assert entry[key] == spec[key], key
    cells = {w["name"] for w in manifest["workloads"]}
    moved = {m["name"]: m for m in manifest["end_to_end"]}[spec["moves"]]
    assert entry["workloads"] and set(entry["workloads"]) <= cells
    assert set(entry["workloads"]) <= set(moved.get("workloads", cells))
    if spec["reducer"] in ("scope_time_ms", "host_activity_ms"):
        # a scope or host metric is its needles: the note names them
        for key in ("scopes", "activity"):
            for needle in spec["arguments"].get(key, ()):
                assert f"`{needle}`" in spec["note"], needle


def test_every_per_layer_metric_of_the_manifest_has_its_file():
    with open(MANIFEST) as f:
        names = [m["name"] for m in json.load(f)["per_layer"]]
    assert sorted(names) == _metric_files()


# The recorded v5e capture again: two readers, one capture.  The program's
# reader (utils/profiling.py) is what tools/trace_report.py prints from;
# the yardstick's own copy has to give every operation the same path, and a
# layer's needles the layer's row.

AHEAD = ["ring/", "kv_head_reshard", "ulysses/", "hybrid/", "zigzag/",
         "tree_decode/"]
KERNELS = "^flash_|^fused_ring|^ssm_decode_step"
PREFILL = ["bench/prefill"]


def _arguments(name):
    with open(os.path.join(METRICS, name + ".json")) as f:
        arguments = json.load(f)["arguments"]
    return {k: v for k, v in arguments.items() if k not in ("per", "spans")}


def _layer_needles(layer):
    """What reads a layer of the program's table: the metric file that is
    for it, or the table's rows for a layer no metric reads yet."""
    files = {"attention projections": "model.prefill_projection_ms",
             "feed-forward": "model.prefill_feed_forward_ms",
             "loss and head": "step.loss_ms"}
    if layer in files:
        return _arguments(files[layer])
    if layer == "flash kernels":
        return {"name_regex": "^flash_|^fused_ring"}
    if layer == "xla flash":
        return {"scopes": ["flash/fwd", "flash/bwd"], "exclude_scopes": AHEAD,
                "exclude_regex": KERNELS}
    assert layer == "embed", layer
    everything_ahead = sorted({
        s for n in ("model.prefill_projection_ms",
                    "model.prefill_feed_forward_ms")
        for k in ("scopes", "exclude_scopes") for s in _arguments(n)[k]})
    return {"scopes": ["embed"], "exclude_scopes": everything_ahead,
            "exclude_regex": KERNELS}


@pytest.fixture(scope="module")
def capture():
    from ring_attention_tpu.utils import profiling

    return profiling, profiling.read_capture(RECORDED)


def test_every_operation_has_the_path_the_programs_reader_gives_it(
        recorded, capture):
    _, theirs = capture
    mine = sorted((o.start, o.name, o.scope, o.pass_)
                  for o in recorded.devices[0])
    want = sorted((e.start_ns * 1e-9, reduce.op_name(e.name), e.scope,
                   reduce.pass_of(e.scope)) for e in theirs.ops)
    assert len(mine) == len(want) > 1000
    assert [m[1:] for m in mine] == [w[1:] for w in want]
    assert sum(1 for m in mine if m[2]) > 0.95 * len(mine)
    assert {m[3] for m in mine} == {"forward"}  # a serve cell
    # every host event with a duration, the spans among them
    assert len(recorded.host) == len(theirs.host)
    assert set(recorded.spans) <= set(recorded.host)


@pytest.mark.parametrize("spans, per", [(PREFILL, None),
                                        (TOKEN_SPANS, "bench/token")],
                         ids=["prefill", "decode"])
@pytest.mark.parametrize("layer", [
    "attention projections", "feed-forward", "loss and head", "xla flash",
    "flash kernels", "embed"])
def test_a_layers_needles_read_the_layers_row(recorded, capture, layer,
                                              spans, per):
    profiling, theirs = capture
    table = profiling.layer_breakdown(theirs, window=spans, per=per)
    run = {"units": {"unit": table["units"]}}
    rows = [r for r in table["rows"] if r["layer"] == layer]
    if layer == "xla flash" and per:
        assert not rows  # a decode step runs the kernel
    for row in rows:
        got = reduce.scope_time_ms(recorded, run, per="unit", spans=spans,
                                   passes=[row["pass"]],
                                   **_layer_needles(layer))
        assert got == pytest.approx(row["ms"], rel=2e-3), row
    # and nothing of another layer: with the other rows they are the window
    rest = sum(r["ms"] for r in table["rows"]
               if r["layer"] not in ("idle", layer))
    mine = reduce.scope_time_ms(recorded, run, per="unit", spans=spans,
                                **_layer_needles(layer)) or 0.0
    assert mine + rest == pytest.approx(
        reduce.busy_time_ms(recorded, run, spans=spans, per="unit"),
        rel=2e-3)


def test_the_accepted_reducers_read_what_the_parents_read(recorded):
    """Pinned to ``git show a61e665:benchmarks/reduce.py`` on this capture
    (PR 36): the reader gained scopes and host events, and no accepted
    metric may move by a digit for it."""
    run = dict(RUN, units={"token": 12}, shape={
        "depth": 2, "decode_start": 1024, "kv_heads": 2, "dim_head": 64})
    decode = {"name_regex": "^flash_decode", "spans": TOKEN_SPANS}
    pinned = {
        "op_time_ms": (dict(decode, per="token"), 0.0034345833333333333),
        "op_share": (decode, 0.24367258303580033),
        "roofline_share": (dict(
            decode, per="token", work="decode_cache_bytes_per_token",
            bound="hbm_bytes"), 37.27708580796048),
        "idle_share": ({"spans": TOKEN_SPANS}, 98.32761325026236),
        "host_gap_ms": ({"spans": TOKEN_SPANS, "per": "token"},
                        1.3859350833334416),
        "busy_time_ms": ({"spans": PREFILL}, 0.030659999999953225),
        "exposed_time_ms": ({"name_regex": "fusion", "per": "token",
                             "spans": TOKEN_SPANS}, 0.008354833333298547),
        "host_percentile": ({"series": "gaps", "q": 50}, 2.0),
    }
    assert set(pinned) | {"mfu", "scope_time_ms",
                          "host_activity_ms"} == set(reduce.REDUCERS)
    for name, (arguments, value) in pinned.items():
        assert reduce.REDUCERS[name](recorded, run, **arguments) == value, name
    assert reduce.mfu(recorded, RUN, work="train_flops_per_token",
                      rate="train_tokens_per_s") == 2.2706726984771572
    assert reduce.device_summary(recorded) == (0.00031352899999865375,
                                               0.01860374)
    assert reduce.breakdown(recorded) == {
        "device_ops": [
            ["copy", 0.00011688599999999999],
            ["multiply_reduce_fusion", 5.346299999999997e-05],
            ["fusion", 4.743999999999998e-05],
            ["flash_decode", 4.1215e-05],
            ["copy-done", 1.4770999999999962e-05],
            ["reshape", 1.2128999999999992e-05],
            ["iota_reduce_fusion", 6.058e-06],
            ["is-finite_reduce_fusion", 5.885e-06],
            ["slice_negate_fusion", 4.432000000000003e-06],
            ["convolution_bitcast_fusion", 1.958e-06]],
        "idle_gaps": [
            ["bench/fetch", 0.01236948500000011],
            ["bench/token", 0.004172286000001184],
            ["bench/prefill", 0.0016533400000000448],
            ["(no span)", 9.510000000000768e-05]]}


def test_dispatch_fetch_and_the_rest_are_the_window(recorded, capture):
    profiling, _ = capture
    run = {"units": {"token": 12}}
    where = {"spans": TOKEN_SPANS, "per": "token"}
    dispatch = _arguments("entry.dispatch_ms_per_token")
    fetch = _arguments("entry.fetch_ms_per_token")
    assert fetch["exclude"] == dispatch["activity"]
    theirs = dict(profiling.HOST_ACTIVITIES)
    assert tuple(dispatch["activity"]) == theirs["dispatch"]
    # the program's fetch needles less the call that waits for the device
    assert fetch["activity"] == [n for n in theirs["fetch"]
                                 if n != "np.asarray"]
    r = reduce.host_activity_ms
    parts = [r(recorded, run, **dispatch, **where),
             r(recorded, run, **fetch, **where),
             r(recorded, run, **where,
               exclude=dispatch["activity"] + fetch["activity"])]
    lo, hi = reduce.window(recorded, TOKEN_SPANS)
    assert all(p > 0 for p in parts)
    assert sum(parts) == pytest.approx(1e3 * (hi - lo) / 12, abs=1e-9)
    # a closed loop launches and fetches while the device waits
    assert parts[0] + parts[1] < reduce.host_gap_ms(recorded, run, **where)
    # the launch is the dispatch span's, give or take the call's own edges
    token = r(recorded, run, activity=["bench/token"], **where)
    assert parts[0] == pytest.approx(token, rel=0.1)


def test_the_toy_metrics_read_the_recorded_capture(recorded):
    toy = os.path.join(HERE, "toy", "metrics")
    run = {"units": {"token": 12}}
    for name in ("toy.feed_forward_ms", "toy.dispatch_ms"):
        with open(os.path.join(toy, name + ".json")) as f:
            spec = json.load(f)
        assert reduce.REDUCERS[spec["reducer"]](
            recorded, run, **spec["arguments"]) > 0, name


def test_describe_prints_scopes_and_host_events(capsys):
    reduce.describe(RECORDED)
    out = capsys.readouterr().out
    assert "plane '/device:TPU:0'" in out
    assert "scope paths by self time" in out
    assert "self time without a scope: 0.0%" in out
    assert "forward   ff_layers_0._block/Dense_1/dot_general" in out
    assert "host events by the time they are open" in out
    assert "np.asarray(jax.Array)" in out
