"""The benchmark's one command.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

It holds no cell's numbers.  Everything about a cell is found by name:
the cell and its configuration in the manifest (``BENCHMARK.json``), its
traffic in ``workloads/<cell>.json``, its sizes in the configuration's
file, its traffic driver in ``kinds/<kind>.py`` and, for a traced run, each
per-layer metric in ``metrics/<name>.json``, which names a reducer of
``reduce.py``.  A name that cannot be found is a one-line error.

The last line of standard output is the contract's JSON object and
nothing else rides on it; the set-up's parts, the correctness check, the
losses or tokens and the compile counts go on earlier lines.  Set-up runs
from the first statement below (jax's import is inside it) to the first
timed instant, less the time the TPU runtime itself took to start.

Without a TPU the command fails.  ``--rehearse`` runs the same path on
the CPU to find faults before a chip run: it reports ``device.platform =
"cpu"`` and no metric but ``setup_s``, because a CPU run gives no time,
rate or share worth a name.
"""

import time

_T0 = time.perf_counter()

import argparse  # noqa: E402
import glob  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
PACKAGE = os.path.basename(HERE)
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_MISS_EVENT = "/jax/compilation_cache/cache_misses"


def die(message: str):
    print(f"run.py: {message}", file=sys.stderr)
    raise SystemExit(2)


def load(path: str, what: str) -> dict:
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError:
        die(f"no {what}: {os.path.relpath(path, ROOT)} does not exist")
    except json.JSONDecodeError as e:
        die(f"{what} {os.path.relpath(path, ROOT)} is not JSON: {e}")


def named(entries: list, name: str, what: str, where: str) -> dict:
    for entry in entries:
        if entry["name"] == name:
            return entry
    die(f"unknown {what} {name!r}: {where} lists "
        f"{', '.join(e['name'] for e in entries)}")


def applies(metric: dict, cell: str) -> bool:
    return cell in metric.get("workloads", [cell])


class Ctx:
    """What a traffic driver is given, and the set-up's parts it marks."""

    def __init__(self, config, workload, args, chips):
        self.config, self.workload = config, workload
        self.seed, self.seconds = args.seed, args.seconds
        self.trace, self.chips = bool(args.trace), chips
        self.parts: dict[str, float] = {}
        self._last = _T0

    def part(self, name: str) -> None:
        now = time.perf_counter()
        self.parts[name] = now - self._last
        self._last = now


class Compiles:
    """Counts every request for an executable (compiled or loaded from the
    persistent cache: either one inside the window is a fault) and the
    cache's misses."""

    requests = 0
    misses = 0

    def install(self, monitoring) -> None:
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def _duration(self, name, secs, **_):
        self.requests += name == COMPILE_EVENT

    def _event(self, name, **_):
        self.misses += name == CACHE_MISS_EVENT


def main(argv: list[str]) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="run on the CPU to find faults; no device metric")
    ap.add_argument("--manifest", default=os.path.join(ROOT, "BENCHMARK.json"),
                    help="a manifest other than the repo's (the tests' toy)")
    args = ap.parse_args(argv)

    manifest = load(args.manifest, "manifest")
    base = os.path.dirname(os.path.abspath(args.manifest))
    data = os.path.join(base, manifest["paths"][0])
    cell = named(manifest["workloads"], args.workload, "workload",
                 os.path.relpath(args.manifest, ROOT))
    entry = named(manifest["configs"], cell["config"], "configuration",
                  os.path.relpath(args.manifest, ROOT))
    config = load(os.path.join(base, entry["file"]), "configuration file")
    workload = load(os.path.join(data, "workloads", cell["name"] + ".json"),
                    "workload file")
    if args.seconds is None:
        args.seconds = float(manifest["run_seconds"])
    kind = workload["kind"]
    if not os.path.exists(os.path.join(HERE, "kinds", kind + ".py")):
        die(f"unknown kind {kind!r} in workload {cell['name']!r}: no "
            f"{PACKAGE}/kinds/{kind}.py")

    sys.path[0] = ROOT  # the program, and this directory as a package
    reduce = importlib.import_module(f"{PACKAGE}.reduce")
    per_layer = []
    for metric in manifest["per_layer"]:
        if applies(metric, cell["name"]):
            spec = load(os.path.join(data, "metrics", metric["name"] + ".json"),
                        "metric file")
            if spec["reducer"] not in reduce.REDUCERS:
                die(f"unknown reducer {spec['reducer']!r} in metric "
                    f"{metric['name']!r}: reduce.py has "
                    f"{', '.join(reduce.REDUCERS)}")
            work = spec["arguments"].get("work")
            if work is not None and work not in reduce.WORK:
                die(f"unknown work function {work!r} in metric "
                    f"{metric['name']!r}: reduce.py has "
                    f"{', '.join(reduce.WORK)}")
            per_layer.append((metric, spec))

    chips = cell["chips"]
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={chips}")
    # libtpu logs to /tmp/tpu_logs unless told; keep it under TMPDIR
    os.environ.setdefault(
        "TPU_LOG_DIR", os.path.join(tempfile.gettempdir(), "tpu_logs"))
    import jax
    from jax import monitoring

    ctx = Ctx(config, workload, args, chips)
    ctx.part("imports")
    devices = jax.devices()
    ctx.part("backend")
    dev0 = devices[0]
    if not args.rehearse:
        if jax.default_backend() != "tpu":
            die(f"no TPU: jax's backend is {jax.default_backend()!r} "
                f"(--rehearse runs the path on the CPU and measures nothing)")
        try:
            reduce.peaks(dev0.device_kind)
        except ValueError as e:  # an unknown chip is an error, not a default
            die(str(e))
    if len(devices) < chips:
        die(f"workload {cell['name']!r} needs {chips} chips, jax finds "
            f"{len(devices)}")

    try:
        from ring_attention_tpu.utils.benchtime import enable_compile_cache
    except ImportError as e:
        die(f"the program is not in this checkout: {e}")

    cache_dir = enable_compile_cache(os.path.join(HERE, ".cache", "jax"))
    compiles = Compiles()
    compiles.install(monitoring)
    driver = importlib.import_module(f"{PACKAGE}.kinds.{kind}")

    state = driver.setup(ctx)
    setup_requests, setup_misses = compiles.requests, compiles.misses
    trace_dir = os.path.join(HERE, ".trace", cell["name"])
    # The TPU runtime's own start (the first jax.devices()) took 5.6 to
    # 26 s from one run to the next on one machine: it is neither the
    # benchmark's nor the program's work and no PR can move it, so it is
    # printed on the set-up line and left out of setup_s.
    setup_s = time.perf_counter() - _T0 - ctx.parts["backend"]
    if ctx.trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0  # host spans, not every Python call
        jax.profiler.start_trace(trace_dir, profiler_options=options)
    try:
        result = driver.window(ctx, state)
    finally:
        if ctx.trace:
            jax.profiler.stop_trace()
    in_window = compiles.requests - setup_requests

    stats = [d.memory_stats() or {} for d in devices[:chips]]
    # buffers in use and the scratch the runtime reserves for running
    # programs are counted apart; a chip holds both
    def peak(s):
        return s.get("peak_bytes_in_use", 0) + s.get("peak_bytes_reserved", 0)

    memory_peak = max(map(peak, stats))
    check = state["check"]
    correct = bool(check["ok"] and result["finite"] and in_window == 0)
    print("setup " + json.dumps({
        "setup_s": setup_s, "backend_start_s": ctx.parts["backend"],
        "parts_s": ctx.parts, "cache_dir": cache_dir,
        "executables_requested": setup_requests,
        "cache_misses": setup_misses}))
    print("memory " + json.dumps(max(stats, key=peak)))
    print("check " + json.dumps(check))
    print("window " + json.dumps({
        **result["log"], "executables_requested_in_window": in_window,
        "cut": {k: config[k] for k in config.get("reduced", [])}}))

    device = {"platform": dev0.platform, "kind": dev0.device_kind,
              "count": len(devices), "memory_peak_bytes": memory_peak}
    harness = {"setup_s": setup_s, "peak_hbm_gib": memory_peak / 2**30}
    out = {"correct": correct, "attempted": result["attempted"],
           "failed": result["failed"], "metrics": {}, "device": device}
    values = {}
    if ctx.trace:
        found = glob.glob(os.path.join(
            trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
        if not found:
            die(f"the profiler left no trace under {trace_dir}")
        trace = reduce.load_trace(found[0])
        run = {"units": result["units"], "series": result["series"],
               "rates": result["end_to_end"], "shape": driver.shape(ctx),
               "device_kind": dev0.device_kind}
        for metric, spec in per_layer:
            value = reduce.REDUCERS[spec["reducer"]](
                trace, run, **spec["arguments"])
            if value is not None:
                values[metric["name"]] = (value, metric["unit"])
        summary = reduce.device_summary(trace)
        if summary is not None:
            device["busy_s"], device["window_s"] = summary
            out["breakdown"] = reduce.breakdown(trace)
    else:
        reported = {**result["end_to_end"], **harness}
        for metric in manifest["end_to_end"]:
            if applies(metric, cell["name"]):
                name = metric["name"]
                if name not in reported:
                    die(f"metric {name!r} is listed for workload "
                        f"{cell['name']!r} but kind {kind!r} does not "
                        f"report it")
                values[name] = (reported[name], metric["unit"])
    if args.rehearse:
        # a CPU run's times, rates and shares are not measurements
        print("rehearsal (cpu, not measurements) " + json.dumps(
            {k: v[0] for k, v in values.items()}))
        values = {k: v for k, v in values.items() if k == "setup_s"}
        out.pop("breakdown", None)
    out["metrics"] = {k: {"value": v, "unit": u}
                      for k, (v, u) in values.items()}
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
