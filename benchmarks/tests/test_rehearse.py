"""``run.py --rehearse`` end to end on the CPU at toy widths.

The toy cells under ``toy/`` were added exactly as a later PR adds a cell:
one file each under ``configs/``, ``workloads/`` and ``metrics/`` and an
entry in a manifest, with no edit to the harness.  Not collected by the
repo's tier-1 run (``pytest tests/``); run with
``python3 -m pytest benchmarks/tests -q``.
"""

import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
RUN = os.path.join(ROOT, "benchmarks", "run.py")
TOY = os.path.join(HERE, "toy", "BENCHMARK.json")
KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def run(*args, manifest=TOY):
    return subprocess.run(
        [sys.executable, RUN, "--manifest", manifest, *args],
        cwd=ROOT, capture_output=True, text=True, timeout=600)


def last_line(proc):
    assert proc.returncode == 0, proc.stderr[-2000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("cell", ["toy.train", "toy.serve"])
def test_rehearsal_prints_the_contract(cell):
    out = last_line(run("--rehearse", "--workload", cell, "--seed",
                        str(2**31 + 5), "--seconds", "1", "--trace", "0"))
    assert set(out) == KEYS
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] >= 1
    assert out["device"]["platform"] == "cpu"
    # a CPU run gives no time, rate or share worth a name
    assert set(out["metrics"]) == {"setup_s"}
    assert set(out["metrics"]["setup_s"]) == {"value", "unit"}


def test_traced_rehearsal_leaves_out_what_it_cannot_read():
    out = last_line(run("--rehearse", "--workload", "toy.serve", "--seed",
                        "1", "--trace", "1"))
    assert set(out) == KEYS and out["metrics"] == {}


def test_same_seed_same_work():
    lines = []
    for _ in range(2):
        proc = run("--rehearse", "--workload", "toy.serve", "--seed", "7",
                   "--seconds", "1", "--trace", "0")
        assert proc.returncode == 0, proc.stderr[-2000:]
        lines.append(next(line for line in proc.stdout.splitlines()
                          if line.startswith("check ")))
    assert lines[0] == lines[1]


def one_line_error(proc, *words):
    assert proc.returncode != 0 and proc.stdout == ""
    error = [line for line in proc.stderr.splitlines()
             if line.startswith("run.py:")]
    assert len(error) == 1
    for word in words:
        assert word in error[0]


def test_unknown_workload_is_a_one_line_error():
    one_line_error(run("--rehearse", "--workload", "toy.nope"),
                   "unknown workload", "toy.train")


def test_no_tpu_without_rehearse_fails():
    one_line_error(run("--workload", "toy.train"), "no TPU")


def edited(tmp_path, edit):
    """A copy of the toy directory with one change to its files."""
    import shutil

    toy = tmp_path / "toy"
    shutil.copytree(os.path.join(HERE, "toy"), toy)
    edit(toy)
    return str(toy / "BENCHMARK.json")


def test_unknown_metric_is_a_one_line_error(tmp_path):
    def edit(toy):
        manifest = json.loads((toy / "BENCHMARK.json").read_text())
        manifest["per_layer"][0]["name"] = "toy.missing"
        (toy / "BENCHMARK.json").write_text(json.dumps(manifest))

    one_line_error(run("--rehearse", "--workload", "toy.serve",
                       manifest=edited(tmp_path, edit)),
                   "no metric file", "toy.missing.json")


def test_unknown_reducer_is_a_one_line_error(tmp_path):
    def edit(toy):
        path = toy / "metrics" / "toy.gap_ms_p50.json"
        spec = json.loads(path.read_text())
        spec["reducer"] = "guess"
        path.write_text(json.dumps(spec))

    one_line_error(run("--rehearse", "--workload", "toy.serve",
                       manifest=edited(tmp_path, edit)),
                   "unknown reducer", "guess")


def test_unknown_kind_is_a_one_line_error(tmp_path):
    def edit(toy):
        path = toy / "workloads" / "toy.serve.json"
        spec = json.loads(path.read_text())
        spec["kind"] = "replay"
        path.write_text(json.dumps(spec))

    one_line_error(run("--rehearse", "--workload", "toy.serve",
                       manifest=edited(tmp_path, edit)),
                   "unknown kind", "replay")
