"""From a profiler trace to per-layer numbers: the yardstick.

Everything a per-layer metric needs lives here, where a later PR cannot
change it: the table of peaks, the arithmetic that turns shapes into
required operations and bytes, the trace reader, and a handful of generic
reducers.  A metric is a data file under ``metrics/`` that names one
reducer and its arguments; ``run.py`` looks the reducer up in
``REDUCERS`` and calls it as ``fn(trace, run, **args)``.  A reducer that
finds nothing to read returns ``None`` and the metric is left out.

A device operation also carries the path the program gave it
(``jax.named_scope`` and flax module names, ``jit(step)/.../ff_layers_0/...``)
and the pass JAX wrote into that path, so a metric can be a layer of the
program: ``scope_time_ms`` with the layer's needles in the metric's own
file.  There is no table of layers here.

``python3 benchmarks/reduce.py <file.xplane.pb>`` prints the planes, lines
and busiest operation names of a trace, then per chip the scope paths that
took most time and the host events that were open longest: look at one by
hand before writing a metric against it.
"""

from __future__ import annotations

import bisect
import gzip
import math
import re
import sys
from dataclasses import dataclass, field

# ----------------------------------------------------------------------
# peaks
# ----------------------------------------------------------------------

# Keyed by the exact ``device_kind`` jax reports.  A device that is not
# here is an error, never a default.
PEAKS = {
    "TPU v5 lite": {
        "flops": 197e12,  # bf16, per chip
        "hbm_bytes": 819e9,  # per second, per chip
        "ici_bits": 1600e9,  # per second, chip to chip
        "source": 'Google Cloud documentation, "TPU v5e"',
    },
}


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no peaks for device_kind {device_kind!r}: add a row with its "
            f"source to PEAKS in benchmarks/reduce.py"
        ) from None


# ----------------------------------------------------------------------
# arithmetic: what the mathematics needs, from shapes
# ----------------------------------------------------------------------

# Matrix multiplications of attention that the mathematics needs: forward
# S = QK^T and O = PV; backward dV = P^T dO, dP = dO V^T, dQ = dS K and
# dK = dS^T Q.  The score recompute every flash backward makes (and the
# second dP of this repo's two-kernel backward) is recomputation and is
# not counted, so a share computed from these can only understate.
FWD_MATMULS = 2
BWD_MATMULS = 4


def attention_flops(seq: int, heads: int, dim_head: int, matmuls: int,
                    batch: int = 1, causal: bool = True) -> float:
    """``matmuls`` x 2 x seq^2 x dim_head per head; a causal mask needs
    half of the square."""
    full = matmuls * 2.0 * seq * seq * dim_head * heads * batch
    return full / 2 if causal else full


def kv_cache_bytes(positions: int, kv_heads: int, dim_head: int,
                   bytes_per_value: int = 2) -> float:
    """Bytes of one layer's k and v over ``positions`` cache rows."""
    return 2.0 * positions * kv_heads * dim_head * bytes_per_value


def matmul_params(shape: dict) -> float:
    """Parameters that sit in a matrix multiplication: every layer's qkv,
    output and feed-forward projections and the output head.  The
    embedding is a lookup and is not counted."""
    hid, dh = shape["hidden"], shape["dim_head"]
    per_layer = (hid * (shape["heads"] + 2 * shape["kv_heads"]) * dh
                 + shape["heads"] * dh * hid + 2 * hid * shape["ffn"])
    return float(shape["depth"] * per_layer + hid * shape["vocab"])


def _flash_flops_per_step(shape: dict, matmuls: int) -> float:
    per_layer = attention_flops(shape["seq"], shape["heads"],
                                shape["dim_head"], matmuls, shape["batch"])
    return shape["depth"] * per_layer / shape["chips"]


def flash_fwd_flops_per_step(shape: dict) -> float:
    """Per chip; the striped ring gives every chip an equal share."""
    return _flash_flops_per_step(shape, FWD_MATMULS)


def flash_bwd_flops_per_step(shape: dict) -> float:
    return _flash_flops_per_step(shape, BWD_MATMULS)


def train_flops_per_token(shape: dict) -> float:
    """Forward and backward the model requires per trained token: 6 per
    matmul parameter plus causal attention at 2 + 4 matmuls.  Nothing
    recomputed (remat, the flash backward's scores) is counted."""
    attn = shape["depth"] * attention_flops(
        shape["seq"], shape["heads"], shape["dim_head"],
        FWD_MATMULS + BWD_MATMULS) / shape["seq"]
    return 6.0 * matmul_params(shape) + attn


def decode_cache_bytes_per_token(shape: dict) -> float:
    """Cache bytes a decoded token must read: every layer's k and v over
    the positions before it (the first decoded position, so later tokens
    are understated by under 1%)."""
    return shape["depth"] * kv_cache_bytes(
        shape["decode_start"], shape["kv_heads"], shape["dim_head"])


WORK = {f.__name__: f for f in (
    flash_fwd_flops_per_step, flash_bwd_flops_per_step,
    train_flops_per_token, decode_cache_bytes_per_token,
)}


# ----------------------------------------------------------------------
# the trace
# ----------------------------------------------------------------------

SPAN_PREFIX = "bench/"
_DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
_OPS_LINE = "XLA Ops"
_MODULES_LINE = "XLA Modules"
_SUFFIX = re.compile(r"(\.\d+)+$")


def op_name(raw: str) -> str:
    """``%fusion.12 = ...`` -> ``fusion``: the name without the HLO sigil,
    the instruction text and the numeric suffix, so that one kernel keeps
    one name across programs."""
    name = raw.lstrip("%").split(" ", 1)[0]
    return _SUFFIX.sub("", name)


def pass_of(scope: str) -> str:
    """The pass JAX wrote into a path: ``rematted_computation`` under
    ``nn.remat``'s backward, ``transpose(jvp(...))`` for the backward."""
    if "rematted_computation" in scope:
        return "recompute"
    return "backward" if "transpose(" in scope else "forward"


@dataclass
class Op:
    name: str
    start: float  # seconds
    end: float
    self_s: float  # duration less the operations nested inside it
    leaf: bool = True
    scope: str = ""  # the instruction's op_name path; "" where it has none
    pass_: str = "forward"  # forward | recompute | backward, by the path


@dataclass
class Trace:
    devices: list[list[Op]] = field(default_factory=list)  # one per chip
    spans: list[tuple[str, float, float]] = field(default_factory=list)
    # every host event with a duration, the runtime's and the spans alike
    host: list[tuple[str, float, float]] = field(default_factory=list)


def nest(events: list[tuple]) -> list[Op]:
    """(name, start, duration[, scope]) on one device line -> ``Op``s with
    self time.  A control-flow operation (a ``while``) spans the operations
    of its body; its own time is what they leave.  Only an operation that
    lies wholly inside another is nested in it (a nanosecond-long
    ``copy-start`` does not adopt the kernel that starts beside it)."""
    ops: list[Op] = []
    stack: list[Op] = []
    short: dict[str, str] = {}  # a step's thousand names repeat every step
    for name, start, dur, *scope in sorted(events,
                                           key=lambda e: (e[1], -e[2])):
        if name not in short:
            short[name] = op_name(name)
        op = Op(short[name], start, start + dur, dur)
        if scope and scope[0]:
            op.scope, op.pass_ = scope[0], pass_of(scope[0])
        while stack and (stack[-1].end <= start
                         or op.end > stack[-1].end + 1e-9):
            stack.pop()
        if stack:
            stack[-1].self_s -= dur
            stack[-1].leaf = False
        stack.append(op)
        ops.append(op)
    return ops


# ``jax.profiler.ProfileData`` reads planes, lines and events, but not the
# ``/host:metadata`` plane's ``HloProto``s, which alone hold each
# instruction's ``op_name`` path.  The wire-format reader below reads just
# those.  It is a copy of the program's (``utils/profiling.py``), kept here
# so that the yardstick does not move when the program's reader is edited.
# Field numbers are the public schema of tsl/profiler/protobuf/xplane.proto
# and xla/service/hlo.proto; unknown fields are skipped by wire type.


def _varint(buf: bytes, i: int) -> tuple[int, int]:
    if buf[i] < 0x80:  # one byte: most keys, lengths and small ids
        return buf[i], i + 1
    r = s = 0
    while True:
        b = buf[i]
        i += 1
        r |= (b & 0x7F) << s
        if not b & 0x80:
            return r, i
        s += 7


def _wire_fields(buf: bytes):
    """``(field_number, wire_type, value)`` of one message: wire type 0 ->
    int, 2 -> bytes, 1 / 5 -> the raw 8 / 4 bytes.  Groups do not occur in
    these protos; an unknown type ends the message rather than guess at
    its framing."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        fn, wt = key >> 3, key & 7
        if wt == 0:
            v, i = _varint(buf, i)
        elif wt == 2:
            ln, i = _varint(buf, i)
            v = buf[i:i + ln]
            i += ln
        elif wt in (1, 5):
            ln = 8 if wt == 1 else 4
            v = buf[i:i + ln]
            i += ln
        else:
            return
        yield fn, wt, v


def _sub(buf: bytes, field_number: int):
    return (v for fn, _, v in _wire_fields(buf) if fn == field_number)


def _hlo_scopes(hlo_proto: bytes) -> dict[str, str]:
    """``{instruction name: op_name path}`` of a serialized ``HloProto``.

    HloProto.hlo_module=1 -> HloModuleProto.computations=3 ->
    HloComputationProto.instructions=2 -> HloInstructionProto.name=1,
    .metadata=7 -> OpMetadata.op_name=2, .id=35, .operand_ids=36.

    What the compiler inserts itself (a ``copy-start`` that prefetches a
    weight, a layout ``copy``) has no path, or only its argument's name.
    It takes the path of the first instruction that uses it, else of its
    first operand: a weight's prefetch belongs to the layer that
    multiplies by it."""
    scopes: dict[int, str] = {}
    names: dict[int, str] = {}
    operands: dict[int, list[int]] = {}
    for module in _sub(hlo_proto, 1):
        for comp in _sub(module, 3):
            for instr in _sub(comp, 2):
                name = scope = ""
                uid = len(names)
                ops: list[int] = []
                for fn, wt, val in _wire_fields(instr):
                    if fn == 1:
                        name = val.decode(errors="replace")
                    elif fn == 7:
                        scope = next(_sub(val, 2), b"").decode(
                            errors="replace")
                    elif fn == 35 and wt == 0:
                        uid = val
                    elif fn == 36 and wt == 0:
                        ops.append(val)
                    elif fn == 36:  # packed
                        i = 0
                        while i < len(val):
                            v, i = _varint(val, i)
                            ops.append(v)
                names[uid], scopes[uid], operands[uid] = name, scope, ops
    users: dict[int, list[int]] = {}
    for uid, ops in operands.items():
        for op in ops:
            users.setdefault(op, []).append(uid)
    pathless = [u for u, s in scopes.items() if "/" not in s]
    for _ in range(4):  # copy-start -> copy-done -> fusion is two rounds
        for uid in pathless:
            near = users.get(uid, []) + operands[uid]
            scopes[uid] = next((scopes[n] for n in near
                                if "/" in scopes.get(n, "")), scopes[uid])
        pathless = [u for u in pathless if "/" not in scopes[u]]
    return {names[u]: s for u, s in scopes.items() if names[u] and s}


_PROGRAM_ID = re.compile(r"\((\d+)\)$")


def _index_metadata_plane(plane: bytes, by_id: dict, by_module: dict):
    """The ``/host:metadata`` plane: each event-metadata entry (field 4,
    its XEventMetadata in field 2) is one profiled program whose
    ``hlo_proto`` stat holds the serialized HloProto.  Indexed by the
    entry's id (on a TPU the program's fingerprint, which also ends the
    name of its ``XLA Modules`` events) and by the module's name less
    that id."""
    for entry in _sub(plane, 4):
        for meta in _sub(entry, 2):
            meta_id = None
            module = ""
            blobs: list[bytes] = []
            for fn, wt, val in _wire_fields(meta):
                if fn == 1 and wt == 0:
                    meta_id = val
                elif fn == 2:
                    module = _PROGRAM_ID.sub("", val.decode(errors="replace"))
                elif fn == 3:  # raw metadata bytes
                    blobs.append(val)
                elif fn == 5:  # an XStat whose bytes_value holds the proto
                    blobs.extend(_sub(val, 6))
            for scopes in filter(None, map(_hlo_scopes, blobs)):
                if meta_id is not None:
                    by_id.setdefault(meta_id, {}).update(scopes)
                if module:
                    by_module.setdefault(module, {}).update(scopes)


def _xspace(path: str) -> bytes:
    """The serialized profile in ``path``; a ``.gz`` (the tests' recorded
    trace) is unpacked in memory."""
    with (gzip.open if path.endswith(".gz") else open)(path, "rb") as f:
        return f.read()


def _profile(data: bytes):
    from jax.profiler import ProfileData

    return ProfileData.from_serialized_xspace(data)


def _scoped(lines: dict, by_id: dict, by_module: dict) -> list[tuple]:
    """One chip's ``XLA Ops`` events as (name, start, duration, scope).  An
    event carries only its times and the instruction's text; its program
    is the enclosing event of the ``XLA Modules`` line, whose name ends in
    the program's id, and the path is that program's for the
    instruction's name."""
    def table(program: str) -> dict:
        found = _PROGRAM_ID.search(program)
        return (by_id.get(found and int(found.group(1)))
                or by_module.get(_PROGRAM_ID.sub("", program))
                or (next(iter(by_module.values()))
                    if len(by_module) == 1 else {}))

    ran = sorted((int(e.start_ns), int(e.duration_ns), e.name)
                 for e in lines.get(_MODULES_LINE, ()))
    starts = [r[0] for r in ran]
    tables = {"": table("")} | {r[2]: table(r[2]) for r in ran}
    out = []
    found: dict[tuple[str, str], str] = {}  # (program, event name) -> path
    for e in lines.get(_OPS_LINE, ()):
        name, start = e.name, e.start_ns
        i = bisect.bisect_right(starts, start) - 1
        inside = i >= 0 and start < ran[i][0] + ran[i][1]
        key = (ran[i][2] if inside else "", name)
        if key not in found:
            # "%fusion.12 = bf16[...] fusion(...)" -> "fusion.12"
            instruction = name.lstrip("%").split(" ", 1)[0]
            found[key] = tables[key[0]].get(instruction, "")
        out.append((name, start * 1e-9, e.duration_ns * 1e-9, found[key]))
    return out


def load_trace(path: str) -> Trace:
    """Read an ``.xplane.pb`` with jax's own reader: device operations from
    each chip's ``XLA Ops`` line, each with its path from the metadata
    plane; the harness's ``bench/`` spans and every other host event with
    a duration from the host's threads.  All are on the profiler's one
    clock.  A capture without a metadata plane reads with every scope
    empty."""
    data = _xspace(path)
    by_id: dict[int, dict[str, str]] = {}
    by_module: dict[str, dict[str, str]] = {}
    for plane in _sub(data, 1):
        if b"metadata" in next(_sub(plane, 2), b""):
            _index_metadata_plane(plane, by_id, by_module)
    trace = Trace()
    for plane in _profile(data).planes:
        if _DEVICE_PLANE.match(plane.name):
            lines = {line.name: line.events for line in plane.lines}
            if _OPS_LINE in lines:
                trace.devices.append(nest(_scoped(lines, by_id, by_module)))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    start = e.start_ns * 1e-9
                    span = (e.name, start, start + e.duration_ns * 1e-9)
                    if e.name.startswith(SPAN_PREFIX):
                        trace.spans.append(span)
                    if e.duration_ns > 0:
                        trace.host.append(span)
    trace.spans.sort(key=lambda s: s[1])
    return trace


# ----------------------------------------------------------------------
# intervals
# ----------------------------------------------------------------------

Intervals = list[tuple[float, float]]


def merged(intervals: Intervals) -> Intervals:
    out: Intervals = []
    for lo, hi in sorted(intervals):
        if out and lo <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], hi))
        else:
            out.append((lo, hi))
    return out


def total(intervals: Intervals) -> float:
    return sum(hi - lo for lo, hi in intervals)


def overlap(a: Intervals, b: Intervals) -> Intervals:
    """Intersection of two merged interval lists."""
    out: Intervals = []
    i = j = 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if lo < hi:
            out.append((lo, hi))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def complement(intervals: Intervals, lo: float, hi: float) -> Intervals:
    """What ``[lo, hi]`` holds outside the merged ``intervals``."""
    out: Intervals = []
    at = lo
    for a, b in overlap(intervals, [(lo, hi)]):
        if a > at:
            out.append((at, a))
        at = b
    if at < hi:
        out.append((at, hi))
    return out


def window(trace: Trace, spans: list[str] | None = None):
    """Extent of the named host spans, every ``bench/`` span when none is
    named: the traced window, or the part of it a metric is about."""
    sel = [s for s in trace.spans if spans is None or s[0] in spans]
    if not sel:
        return None
    return min(s[1] for s in sel), max(s[2] for s in sel)


def busy(ops: list[Op], lo: float, hi: float) -> Intervals:
    """Where an operation runs on the device, inside ``[lo, hi]``."""
    return overlap(merged([(o.start, o.end) for o in ops]), [(lo, hi)])


def _units(run: dict, per: str | None) -> float:
    return float(run["units"][per]) if per else 1.0


def _matching(ops, lo, hi, name_regex, exclude_regex=None):
    want = re.compile(name_regex)
    skip = re.compile(exclude_regex) if exclude_regex else None
    return [o for o in ops
            if lo <= o.start < hi and want.search(o.name)
            and not (skip and skip.search(o.name))]


def _op_seconds(trace, spans, name_regex, exclude_regex=None):
    """Self time of the matching operations on the chip that spent most,
    and the window's length; ``None`` where there is nothing to read."""
    win = window(trace, spans)
    if win is None or not trace.devices:
        return None
    per_device = [
        [o.self_s for o in _matching(ops, *win, name_regex, exclude_regex)]
        for ops in trace.devices]
    if not any(per_device):
        return None
    return max(sum(d) for d in per_device), win[1] - win[0]


# ----------------------------------------------------------------------
# reducers: fn(trace, run, **arguments from the metric's file)
# ----------------------------------------------------------------------


def op_time_ms(trace, run, name_regex, exclude_regex=None, per=None,
               spans=None):
    """Device time of the operations whose name matches, per unit."""
    got = _op_seconds(trace, spans, name_regex, exclude_regex)
    return None if got is None else 1e3 * got[0] / _units(run, per)


def op_share(trace, run, name_regex, exclude_regex=None, spans=None):
    """The same as a share of the window, in percent."""
    got = _op_seconds(trace, spans, name_regex, exclude_regex)
    return None if got is None else 100.0 * got[0] / got[1]


def roofline_share(trace, run, name_regex, work, bound, per, spans=None):
    """Least time the chip could take for the work (``bound`` says which
    peak binds: ``flops`` or ``hbm_bytes``) over the time the matching
    operations took, in percent."""
    got = _op_seconds(trace, spans, name_regex)
    if got is None:
        return None
    least = WORK[work](run["shape"]) / peaks(run["device_kind"])[bound]
    return 100.0 * least / (got[0] / _units(run, per))


def _idle_seconds(trace, spans):
    """Idle time inside the window on the chip that idled most."""
    win = window(trace, spans)
    if win is None or not trace.devices:
        return None
    length = win[1] - win[0]
    return max(length - total(busy(ops, *win))
               for ops in trace.devices), length


def idle_share(trace, run, spans=None):
    """1 - (union of operation intervals) / window, worst chip, percent."""
    got = _idle_seconds(trace, spans)
    return None if got is None else 100.0 * got[0] / got[1]


def host_gap_ms(trace, run, spans=None, per=None):
    """Time per unit in which the device waited for the host."""
    got = _idle_seconds(trace, spans)
    return None if got is None else 1e3 * got[0] / _units(run, per)


def busy_time_ms(trace, run, spans=None, per=None):
    """Device-busy time inside the named spans, busiest chip."""
    win = window(trace, spans)
    if win is None or not trace.devices:
        return None
    most = max(total(busy(ops, *win)) for ops in trace.devices)
    return 1e3 * most / _units(run, per)


def exposed_time_ms(trace, run, name_regex, per=None, spans=None):
    """The part of the matching operations' time during which no other
    operation runs on that chip (containers such as ``while`` aside)."""
    win = window(trace, spans)
    if win is None or not trace.devices:
        return None
    want = re.compile(name_regex)
    worst = None
    for ops in trace.devices:
        leaves = [o for o in ops if o.leaf and win[0] <= o.start < win[1]]
        mine = merged([(o.start, o.end) for o in leaves
                       if want.search(o.name)])
        rest = merged([(o.start, o.end) for o in leaves
                       if not want.search(o.name)])
        if mine:
            alone = total(mine) - total(overlap(mine, rest))
            worst = alone if worst is None else max(worst, alone)
    return None if worst is None else 1e3 * worst / _units(run, per)


def scope_time_ms(trace, run, scopes=None, exclude_scopes=None,
                  name_regex=None, exclude_regex=None, passes=None,
                  per=None, spans=None):
    """Device time, per unit, of the operations whose path holds one of
    the substrings ``scopes`` (any path where none is given) and none of
    ``exclude_scopes``, whose name passes the two expressions as in
    ``op_time_ms`` (a kernel is named by its own name wherever it is
    called from, and is never its scope's), and whose pass is one of
    ``passes``.  Self time, on the chip that spent most."""
    win = window(trace, spans)
    if win is None or not trace.devices:
        return None
    want = re.compile(name_regex) if name_regex else None
    skip = re.compile(exclude_regex) if exclude_regex else None
    memo: dict[tuple[str, str, str], bool] = {}

    def selected(o: Op) -> bool:
        key = (o.scope, o.name, o.pass_)
        if key not in memo:
            memo[key] = bool(
                (scopes is None or any(s in o.scope for s in scopes))
                and not any(s in o.scope for s in exclude_scopes or ())
                and (want is None or want.search(o.name))
                and not (skip and skip.search(o.name))
                and (passes is None or o.pass_ in passes))
        return memo[key]

    per_device = [[o.self_s for o in ops
                   if win[0] <= o.start < win[1] and selected(o)]
                  for ops in trace.devices]
    if not any(per_device):
        return None
    return 1e3 * max(sum(d) for d in per_device) / _units(run, per)


def _open(trace, lo, hi, needles) -> Intervals:
    """Where, inside ``[lo, hi]``, a host event whose name holds one of
    ``needles`` is open."""
    memo: dict[str, bool] = {}
    out = []
    for name, a, b in trace.host:
        if a < hi and b > lo:
            if name not in memo:
                memo[name] = any(n in name for n in needles)
            if memo[name]:
                out.append((a, b))
    return overlap(merged(out), [(lo, hi)])


def host_activity_ms(trace, run, activity=None, exclude=None, spans=None,
                     per=None):
    """Host time, per unit, inside the window, during which a host event
    whose name holds one of the substrings ``activity`` is open (any
    instant where none is given) and none whose name holds one of
    ``exclude``: the union over the host's threads, on the host's clock
    alone.  It is not cut to the device's idle time: a capture puts the
    device's clock and the host's on one axis only to about a millisecond
    (PERF.md section 6, PR 36), which is more than a launch takes."""
    win = window(trace, spans)
    if win is None or not trace.host:
        return None
    open_ = [win] if activity is None else _open(trace, *win, activity)
    if exclude:
        open_ = overlap(open_, complement(_open(trace, *win, exclude), *win))
    if not open_:
        return None
    return 1e3 * total(open_) / _units(run, per)


def percentile(values: list[float], q: float) -> float:
    """Nearest-rank percentile: the smallest value with at least q% of the
    samples at or below it."""
    ordered = sorted(values)
    return ordered[max(math.ceil(q / 100.0 * len(ordered)), 1) - 1]


def host_percentile(trace, run, series, q):
    """A percentile of a series the traffic driver timed on the host."""
    values = run["series"].get(series)
    return percentile(values, q) if values else None


def mfu(trace, run, work, rate):
    """Required operations per token x tokens per second over the chips'
    peak, in percent.  Not a kernel's roofline share."""
    value = run["rates"].get(rate)
    if value is None or run["device_kind"] not in PEAKS:
        return None
    peak = peaks(run["device_kind"])["flops"] * run["shape"]["chips"]
    return 100.0 * WORK[work](run["shape"]) * value / peak


REDUCERS = {f.__name__: f for f in (
    op_time_ms, op_share, roofline_share, idle_share, host_gap_ms,
    busy_time_ms, exposed_time_ms, host_percentile, mfu,
    scope_time_ms, host_activity_ms,
)}


# ----------------------------------------------------------------------
# the run's summary for the ledger
# ----------------------------------------------------------------------


def device_summary(trace: Trace):
    """(busy_s averaged over the chips, window_s) of the traced window."""
    win = window(trace)
    if win is None or not trace.devices:
        return None
    per_chip = [total(busy(ops, *win)) for ops in trace.devices]
    return sum(per_chip) / len(per_chip), win[1] - win[0]


def breakdown(trace: Trace, top: int = 10) -> dict | None:
    """The operations that took most device time (self time, averaged over
    the chips), and the idle time of the chip that idled most by the span
    the host was in."""
    win = window(trace)
    if win is None or not trace.devices:
        return None
    by_name: dict[str, float] = {}
    for ops in trace.devices:
        for o in ops:
            if win[0] <= o.start < win[1]:
                by_name[o.name] = by_name.get(o.name, 0.0) + o.self_s
    device_ops = sorted(((n, s / len(trace.devices))
                         for n, s in by_name.items()),
                        key=lambda x: -x[1])[:top]
    idle = max((complement(busy(ops, *win), *win) for ops in trace.devices),
               key=total)
    by_span: dict[str, float] = {}
    for name in {s[0] for s in trace.spans}:
        inside = merged([(a, b) for n, a, b in trace.spans if n == name])
        by_span[name] = total(overlap(idle, inside))
    by_span["(no span)"] = max(total(idle) - sum(by_span.values()), 0.0)
    idle_gaps = sorted(((n, s) for n, s in by_span.items() if s > 0),
                       key=lambda x: -x[1])[:top]
    return {"device_ops": [list(x) for x in device_ops],
            "idle_gaps": [list(x) for x in idle_gaps]}


def describe(path: str, top: int = 25) -> None:
    """Print what a trace holds: planes, lines, spans, busiest names; then
    what a metric's needles are chosen from: per chip the scope paths that
    took most self time and the share that has none, and the host events
    that were open longest inside the ``bench/`` spans."""
    for plane in _profile(_xspace(path)).planes:
        print(f"plane {plane.name!r}")
        for line in plane.lines:
            events = list(line.events)
            names: dict[str, float] = {}
            for e in events:
                names[e.name] = names.get(e.name, 0.0) + e.duration_ns * 1e-9
            head = sorted(names.items(), key=lambda x: -x[1])[:top]
            print(f"  line {line.name!r}: {len(events)} events")
            if _DEVICE_PLANE.match(plane.name) or any(
                    n.startswith(SPAN_PREFIX) for n in names):
                for name, secs in head:
                    print(f"    {secs:12.6f} s  {name[:100]}")
    trace = load_trace(path)
    win = window(trace)
    for chip, ops in enumerate(trace.devices):
        by_scope: dict[tuple[str, str], float] = {}
        for o in ops:
            key = ("/".join(o.scope.split("/")[-3:]), o.pass_)
            by_scope[key] = by_scope.get(key, 0.0) + o.self_s
        spent = sum(by_scope.values()) or 1.0
        bare = sum(s for (scope, _), s in by_scope.items() if not scope)
        print(f"chip {chip}: scope paths by self time (last three "
              f"components, pass); self time without a scope: "
              f"{100 * bare / spent:.1f}%")
        for (scope, pass_), secs in sorted(by_scope.items(),
                                           key=lambda x: -x[1])[:top]:
            print(f"    {secs:12.6f} s  {pass_:9s} {scope or '(none)'}")
    if win is None:
        return
    open_: dict[str, Intervals] = {}
    for name, a, b in trace.host:
        open_.setdefault(name, []).append((a, b))
    held = {name: total(overlap(merged(spans), [win]))
            for name, spans in open_.items()}
    print(f"host events by the time they are open inside the bench/ spans "
          f"({win[1] - win[0]:.6f} s; the host's clock, which a capture "
          f"aligns with the device's only to about a millisecond)")
    for name, secs in sorted(held.items(), key=lambda x: -x[1])[:15]:
        print(f"    {secs:12.6f} s  {name[:100]}")


if __name__ == "__main__":
    describe(sys.argv[1])
