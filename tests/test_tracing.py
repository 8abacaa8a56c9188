"""Span tracing layer (utils/tracing.py + tools/cluster_timeline.py).

The acceptance property of PR 19: from per-process span files ALONE, the
merger reconstructs what the whole cluster was doing — who died, what
fault window killed it, and which survivor sat in a barrier watching.
Pinned here at three levels:

* the span file format itself — O_APPEND JSONL round-trip, torn-final-
  line tolerance (a chaos kill mid-write), nested span parentage, error
  stamping, flushed-open rows on abort paths;
* the merge — the shared-rendezvous clock-offset model (an exact
  synthetic pin: a +5 s skewed process comes back into alignment),
  Chrome trace-event golden output, and the incident reconstruction
  naming victim / fault window / straggler from synthetic rows;
* the real thing — a two-process ChaosWorker cluster with one worker
  killed pre-commit, whose merged timeline must name the victim, the
  armed fault window, and the survivor's barrier wait (slow tier);

plus the :class:`LatencyHistogram` contracts (pinned bucket geometry,
merge associativity, deterministic integer percentiles, codec
round-trip, cross-scale rejection) and the HLO pin
that a CONFIGURED tracer adds zero collectives to the compiled train
step (host-side spans only — PR-4 style).
"""

import json
import os
import subprocess
import sys

import pytest

from ring_attention_tpu.utils import tracing
from ring_attention_tpu.utils.tracing import LatencyHistogram

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WORKER = os.path.join(REPO, "tests", "elastic_worker.py")
TIMELINE = os.path.join(REPO, "tools", "cluster_timeline.py")


@pytest.fixture(autouse=True)
def _no_global_tracer():
    """Every test starts and ends with the null tracer installed."""
    tracing.shutdown()
    yield
    tracing.shutdown()


# ----------------------------------------------------------------------
# Span file round-trip
# ----------------------------------------------------------------------


def test_span_roundtrip_nesting_and_schema(tmp_path):
    t = tracing.Tracer(tmp_path, process=0, trace_id="t" * 16)
    with t.span("outer", step=3) as outer:
        with t.span("inner") as inner:
            assert inner.parent_id == outer.span_id
        t.instant("mark", value=1)
    t.rendezvous("b0")
    t.close()

    rows = tracing.read_spans(t.path)
    assert [r["kind"] for r in rows] == [
        "process", "span", "instant", "span", "rendezvous"
    ]
    by_name = {r["name"]: r for r in rows}
    # inner closes before outer, so it lands first; parentage survives
    assert by_name["inner"]["parent"] == by_name["outer"]["span"]
    assert by_name["mark"]["parent"] == by_name["outer"]["span"]
    assert by_name["outer"]["parent"] is None
    assert by_name["outer"]["attrs"] == {"step": 3}
    assert by_name["outer"]["dur"] >= by_name["inner"]["dur"] >= 0
    for r in rows:
        assert r["schema"] == tracing.TRACE_SCHEMA_VERSION
        assert r["trace"] == "t" * 16
        assert r["proc"] == 0
        assert {"mono", "wall", "span"} <= set(r)


def test_torn_final_line_and_unknown_schema_skipped(tmp_path):
    t = tracing.Tracer(tmp_path, process=0)
    t.instant("good")
    t.close()
    with open(t.path, "a") as fh:
        fh.write(json.dumps({"schema": 99, "kind": "instant",
                             "name": "future", "wall": 0.0}) + "\n")
        fh.write('{"schema": 1, "kind": "inst')  # killed mid-write
    rows = tracing.read_spans(t.path)
    assert [r["name"] for r in rows] == ["process", "good"]


def test_span_error_stamp_and_flush_open(tmp_path):
    t = tracing.Tracer(tmp_path, process=0)
    with pytest.raises(RuntimeError):
        with t.span("barrier/wait", barrier="b1"):
            raise RuntimeError("peer died")
    # an abort path flushes whatever is still open, durably
    with t.span("ckpt/save", step=2):
        t.flush_open("chaos_kill")
        recent = t.last_spans()
        assert any(r["kind"] == "open" and r["name"] == "ckpt/save"
                   for r in recent)
    t.close()
    rows = tracing.read_spans(t.path)
    by = {(r["kind"], r["name"]): r for r in rows}
    assert by[("span", "barrier/wait")]["attrs"]["error"] == "RuntimeError"
    flushed = by[("open", "ckpt/save")]
    assert flushed["attrs"] == {"step": 2, "flush": "chaos_kill"}
    assert flushed["dur"] >= 0


def test_registry_env_opt_in_and_null_default(tmp_path, monkeypatch):
    assert tracing.get_tracer() is tracing.NULL
    # no env -> no tracer, nothing installed
    monkeypatch.delenv(tracing.TRACE_DIR_ENV, raising=False)
    assert tracing.configure_from_env() is None
    assert tracing.get_tracer() is tracing.NULL
    monkeypatch.setenv(tracing.TRACE_DIR_ENV, str(tmp_path))
    monkeypatch.setenv("RING_ATTN_TRACE_PROC", "7")
    t = tracing.configure_from_env()
    assert t is tracing.get_tracer() and t.process == 7
    assert os.path.basename(t.path) == "spans_p00007.jsonl"
    tracing.shutdown()
    assert tracing.get_tracer() is tracing.NULL


# ----------------------------------------------------------------------
# Merge: the clock-offset model
# ----------------------------------------------------------------------


def _row(proc, kind, name, wall, *, dur=None, attrs=None, span=1):
    r = {"schema": tracing.TRACE_SCHEMA_VERSION, "trace": "t",
         "proc": proc, "kind": kind, "name": name, "span": span,
         "parent": None, "mono": wall, "wall": wall,
         "attrs": attrs or {}}
    if dur is not None:
        r["dur"] = dur
    return r


def test_clock_offset_correction_exact_pin():
    # process 1's wall clock runs 5 s AHEAD; both stamp two shared
    # barrier rendezvous.  The merger must subtract the skew exactly.
    by_proc = {
        0: [_row(0, "rendezvous", "rendezvous", 100.0,
                 attrs={"tag": "s0"}),
            _row(0, "rendezvous", "rendezvous", 110.0,
                 attrs={"tag": "s1"}),
            _row(0, "span", "train/step", 100.5, dur=1.0)],
        1: [_row(1, "rendezvous", "rendezvous", 105.0,
                 attrs={"tag": "s0"}),
            _row(1, "rendezvous", "rendezvous", 115.0,
                 attrs={"tag": "s1"}),
            _row(1, "span", "train/step", 105.5, dur=1.0)],
    }
    merged = tracing.merge_spans(by_proc)
    assert merged["offsets"] == {0: 0.0, 1: -5.0}
    steps = [r for r in merged["spans"] if r["name"] == "train/step"]
    # after correction the two processes' steps coincide
    assert [round(r["t"], 6) for r in steps] == [100.5, 100.5]
    assert [round(r["t_end"], 6) for r in steps] == [101.5, 101.5]
    # no shared rendezvous -> offset stays 0.0 (same-host assumption)
    lonely = {0: by_proc[0], 2: [_row(2, "span", "x", 50.0, dur=0.1)]}
    assert tracing.merge_spans(lonely)["offsets"][2] == 0.0


def test_chrome_trace_golden():
    by_proc = {
        0: [_row(0, "span", "train/step", 10.0, dur=0.5,
                 attrs={"step": 1}, span=2),
            _row(0, "instant", "chaos/kill", 10.75,
                 attrs={"fault": "kill_pre_commit"}, span=3)],
    }
    got = tracing.to_chrome_trace(tracing.merge_spans(by_proc))
    assert got == {
        "traceEvents": [
            {"ph": "M", "name": "process_name", "pid": 0, "tid": 0,
             "args": {"name": "process 0"}},
            {"name": "train/step", "cat": "span", "pid": 0, "tid": 0,
             "ts": 0, "args": {"step": 1}, "ph": "X", "dur": 500000},
            {"name": "chaos/kill", "cat": "instant", "pid": 0, "tid": 0,
             "ts": 750000, "args": {"fault": "kill_pre_commit"},
             "ph": "i", "s": "p"},
        ],
        "displayTimeUnit": "ms",
    }


def test_incident_reconstruction_synthetic():
    by_proc = {
        0: [_row(0, "instant", "chaos/armed", 10.0,
                 attrs={"faults": "kill_pre_commit"}, span=2),
            _row(0, "instant", "chaos/kill", 12.0,
                 attrs={"fault": "kill_pre_commit", "exit_code": 113},
                 span=3)],
        1: [_row(1, "span", "barrier/wait", 11.5, dur=3.0,
                 attrs={"barrier": "elastic:ck:s1:committed",
                        "error": "BarrierTimeout"}, span=2)],
    }
    report = tracing.reconstruct_incident(tracing.merge_spans(by_proc))
    assert report is not None
    assert "chaos/kill on process 0" in report
    assert "fault window: armed at" in report and "2.0000s armed" in report
    assert "STRAGGLER WATCH: process 1 barrier/wait" in report
    assert "BarrierTimeout" in report
    # no anchor -> no incident
    calm = {0: [_row(0, "span", "train/step", 1.0, dur=0.1)]}
    assert tracing.reconstruct_incident(tracing.merge_spans(calm)) is None


# ----------------------------------------------------------------------
# LatencyHistogram
# ----------------------------------------------------------------------


def test_histogram_percentiles_are_deterministic_bucket_edges():
    # the bucket geometry is part of every recorded latency: a changed
    # edge or rank rule silently re-scales p50/p95/p99 across runs, so
    # the table and a fixed sample's read-off are pinned as exact values
    assert tracing.HIST_SCALE == "ns-pow2half-64"
    assert tracing.HIST_BUCKETS == len(tracing.BUCKET_BOUNDS_NS) == 64
    assert sum(tracing.BUCKET_BOUNDS_NS) == 10368968293527
    h, x = LatencyHistogram(), 1
    for _ in range(1000):
        x = (x * 48271) % 2147483647  # minstd LCG: portable, seedless
        h.record_ns(1_000 + x % 50_000_000)  # 1 us .. 50 ms spread
    assert [h.percentile_ns(q) for q in (50, 95, 99)] == [
        32768000, 65536000, 65536000,
    ]

    h = LatencyHistogram()
    for ms in (1, 1, 2, 4, 8, 100):
        h.record(ms / 1e3)
    # every percentile is the UPPER edge of the covering bucket — an
    # integer from the fixed table, never an interpolated float
    for q in (50, 95, 99):
        assert h.percentile_ns(q) in (
            tracing.BUCKET_BOUNDS_NS + (tracing.OVERFLOW_EDGE_NS,)
        )
    assert h.percentile_ns(50) <= h.percentile_ns(95) <= h.percentile_ns(99)
    assert LatencyHistogram().percentile_ns(50) == 0
    # overflow: something absurd still lands (and reports the edge)
    h.record(10_000.0)
    assert h.percentile_ns(100) == tracing.OVERFLOW_EDGE_NS


def test_histogram_merge_associative_and_order_free():
    samples = [[0.001, 0.002], [0.004, 0.5], [0.032, 0.001, 7.0]]

    def hist(vals):
        h = LatencyHistogram()
        for v in vals:
            h.record(v)
        return h

    a, b, c = (hist(s) for s in samples)
    left = hist(samples[0]).merge(b).merge(c)          # (a+b)+c
    right = hist(samples[1]).merge(c).merge(a)          # (b+c)+a
    assert left.counts == right.counts
    assert left.n == right.n == 7
    assert left.sum_ns == right.sum_ns
    one = hist([v for s in samples for v in s])         # single-process
    assert one.counts == left.counts


def test_histogram_codec_roundtrip_and_scale_rejection():
    h = LatencyHistogram()
    for v in (0.001, 0.016, 2.5):
        h.record(v)
    back = LatencyHistogram.from_dict(json.loads(json.dumps(h.to_dict())))
    assert back.counts == h.counts
    assert back.n == h.n and back.sum_ns == h.sum_ns
    assert back.percentile_ns(95) == h.percentile_ns(95)
    with pytest.raises(ValueError, match="scale"):
        LatencyHistogram.from_dict({"scale": "ns-linear-10", "counts": {}})


# ----------------------------------------------------------------------
# The compiled step is untouched by instrumentation (PR-4 style HLO pin)
# ----------------------------------------------------------------------


def test_tracer_adds_zero_collectives_to_train_step(tmp_path, monkeypatch):
    """Spans are host-side only: the train step compiled with a live
    tracer installed must issue the byte-identical collective sequence
    as the uninstrumented one."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from ring_attention_tpu import RingTransformer, create_mesh
    from ring_attention_tpu.analysis.contracts import hlo_collective_sequence
    from ring_attention_tpu.utils import make_train_step

    mesh = create_mesh(ring_size=4)
    model = RingTransformer(
        num_tokens=64, dim=32, depth=1, heads=4, dim_head=8, causal=True,
        striped=True, bucket_size=8, mesh=mesh, use_ring=True,
    )
    toks = jnp.asarray(
        np.random.default_rng(0).integers(0, 64, (2, 64)), jnp.int32
    )
    params = model.init(jax.random.PRNGKey(0), toks, return_loss=True)
    opt = optax.adam(1e-3)
    opt_state = opt.init(params)

    step = make_train_step(
        lambda p, t: model.apply(p, t, return_loss=True), opt
    )
    args = (params, opt_state, toks)

    txt_base = jax.jit(step).lower(*args).compile().as_text()
    tracing.configure(tmp_path, process=0)
    with tracing.get_tracer().span("train/step", step=0):
        txt_traced = jax.jit(step).lower(*args).compile().as_text()
    seq_base = hlo_collective_sequence(txt_base)
    assert seq_base, "expected ring collectives in the train step"
    assert hlo_collective_sequence(txt_traced) == seq_base


# ----------------------------------------------------------------------
# FlightRecorder carries the span window (telemetry satellite)
# ----------------------------------------------------------------------


def test_flight_dump_carries_active_tracer_spans(tmp_path):
    from ring_attention_tpu.utils import FlightRecorder, read_flight_dump

    tracing.configure(tmp_path / "trace", process=0)
    rec = FlightRecorder(tmp_path / "flight", window=8)
    with tracing.get_tracer().span("ckpt/save", step=4):
        path = rec.dump("chaos", step=4)
    dump = read_flight_dump(path)
    names = {s["name"] for s in dump["spans"]}
    assert "ckpt/save" in names, dump["spans"]
    open_rows = [s for s in dump["spans"] if s["kind"] == "open"]
    assert open_rows and open_rows[-1]["attrs"] == {"step": 4}


# ----------------------------------------------------------------------
# The real thing: two processes, one violent death, one merged timeline
# ----------------------------------------------------------------------


@pytest.mark.slow
def test_cluster_kill_one_worker_merged_timeline(tmp_path):
    """PR 19's acceptance run: a two-process cluster where chaos kills
    worker 1 mid-shard-write.  From the per-process span files ALONE,
    the merged timeline must name the victim (chaos/kill instant on
    process 1), the fault window (chaos/armed -> kill), and the
    survivor's errored barrier wait — and tools/cluster_timeline.py
    renders it.  (The victim is process 1, not 0: process 0 hosts the
    jax.distributed coordinator, and killing the coordinator takes the
    survivor down by heartbeat loss before its barrier wait can even
    time out — the straggler evidence this test pins would never be
    written.)"""
    from ring_attention_tpu.elastic import chaos

    trace = tmp_path / "trace"
    w = chaos.ChaosWorker(
        [sys.executable, WORKER,
         "--ckpt-dir", str(tmp_path / "ck"),
         "--loss-log", str(tmp_path / "loss.jsonl"),
         "--steps", "4", "--save-every", "2", "--sync-save",
         "--barrier-timeout", "15"],
        cwd=REPO, timeout=300,
    )
    rs = w.run_cluster(
        processes=2, devices_per_process=2,
        chaos=[chaos.KILL_MID_SHARD], chaos_process=1,
        extra_env={tracing.TRACE_DIR_ENV: str(trace)},
    )
    assert rs[1].returncode == chaos.CHAOS_EXIT_CODE, (
        rs[1].stdout + rs[1].stderr
    )

    files = sorted(os.listdir(trace))
    assert files == ["spans_p00000.jsonl", "spans_p00001.jsonl"], files
    merged = tracing.merge_trace_dir(trace)
    by_proc_kind = {
        (r["proc"], r.get("kind"), r.get("name")) for r in merged["spans"]
    }
    # victim: the kill instant is durable despite os._exit
    assert (1, "instant", "chaos/kill") in by_proc_kind
    assert (1, "instant", "chaos/armed") in by_proc_kind
    # the survivor's save stalls on the dead peer's barrier: a wait
    # span that ends in an error (BarrierTimeout, or the distributed
    # runtime's own peer-death conversion) is the straggler evidence
    waits = [r for r in merged["spans"]
             if r["proc"] == 0 and r["name"] == "barrier/wait"]
    assert waits, [r["name"] for r in merged["spans"] if r["proc"] == 0]
    assert any((r.get("attrs") or {}).get("error") for r in waits), waits
    # both processes traced real work before the death
    assert (0, "span", "train/step") in by_proc_kind
    assert (1, "span", "train/step") in by_proc_kind

    # the incident reconstruction names all three from the files alone
    report = tracing.reconstruct_incident(merged)
    assert report is not None
    assert "chaos/kill on process 1" in report
    assert "fault window: armed at" in report
    assert "process 0 barrier/wait" in report

    # and the CLI renders the same story
    r = subprocess.run(
        [sys.executable, TIMELINE, str(trace), "--incident"],
        capture_output=True, text=True, timeout=60,
    )
    assert r.returncode == 0, r.stderr
    assert "chaos/kill on process 1" in r.stdout
