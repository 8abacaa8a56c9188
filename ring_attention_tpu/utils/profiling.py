"""Profiling: trace capture, step throughput, and reading a capture back.

On TPU the platform profiler (XProf via ``jax.profiler``) is the ground
truth for where device time goes.  This module has what a training loop
calls (a trace context, named annotations, a step-throughput meter) and
the **reader** side: every device operation of an ``.xplane.pb`` capture
joined to its ``jax.named_scope`` / flax module path, hence to a layer of
the program and a pass of the step (:func:`layer_breakdown`), a
per-hop/per-stage timeline keyed on the stack's stable scope names
(``ring/hop{i}``, ``ring/rotate{i}``, ``ulysses/a2a_in``, …), and a
*measured* compute/transfer overlap fraction to sit next to the analytic
one from ``telemetry.ring_comms_accounting`` (docs/observability.md §5.1).

Stdlib-only at module level (jax is imported inside functions).  Events
are read with ``jax.profiler.ProfileData``; the scope paths, which it
does not expose, with a ~100-line protobuf wire-format reader.
"""

from __future__ import annotations

import bisect
import contextlib
import glob
import gzip
import os
import re
import statistics
import warnings
from dataclasses import dataclass, field
from typing import Any, Iterator, NamedTuple

from .tracing import perf_counter as _perf_counter


@contextlib.contextmanager
def trace(logdir: str):
    """Capture an XLA/TPU profile viewable in XProf/TensorBoard.

    Goes through ``utils/compat.profiler_trace`` so the jax-0.4.x
    entry-point differences stay in the shim (docs/observability.md).

    >>> with trace("/tmp/profile"):
    ...     step(...)  # traced region
    """
    from . import compat

    with compat.profiler_trace(logdir):
        yield


def annotate(name: str):
    """Host-side timeline annotation (``jax.profiler.TraceAnnotation``).

    Marks a span on the HOST trace line — dispatch loops, data loading,
    checkpoint saves.  For naming *device* time inside jitted code use
    ``jax.named_scope`` (applied throughout ``parallel/`` and ``ops/``);
    the two compose: a host annotation around a ``step()`` call brackets
    the device ops the named scopes attribute.

    >>> with annotate("train/step"):
    ...     loss = step(...)
    """
    import jax

    return jax.profiler.TraceAnnotation(name)


@dataclass
class StepTimer:
    """Wall-clock throughput meter for a training/decoding loop.

    Blocks on the supplied result each step so async dispatch doesn't hide
    device time; reports steps/s and tokens/s over a sliding window, plus
    p50/p95 per-step latency (the tail is what a wedged collective or a
    slow host callback shows up in first — the mean hides it).

    Timestamps come from ``time.perf_counter`` (monotonic by contract); a
    non-increasing reading anyway — a suspended VM, a broken clock shim —
    resets the window instead of poisoning every rate until it scrolls
    out (``clock_anomalies`` counts the resets).
    """

    tokens_per_step: int = 0
    window: int = 20
    clock_anomalies: int = 0
    _times: list = field(default_factory=list)
    _warned_no_tokens: bool = field(default=False, repr=False)

    def step(self, result=None) -> None:
        if result is not None:
            import jax

            jax.block_until_ready(result)
            if self.tokens_per_step == 0 and not self._warned_no_tokens:
                # tokens_per_sec would read 0.0 forever — say so ONCE
                # instead of letting a dashboard trend a silent zero
                self._warned_no_tokens = True
                warnings.warn(
                    "StepTimer.step() called with a result but "
                    "tokens_per_step is unset — tokens_per_sec will report "
                    "0.0; construct StepTimer(tokens_per_step=...) to get "
                    "throughput",
                    stacklevel=2,
                )
        now = _perf_counter()
        if self._times and now <= self._times[-1]:
            self.clock_anomalies += 1
            self._times.clear()
        self._times.append(now)
        if len(self._times) > self.window + 1:
            self._times.pop(0)

    def _deltas(self) -> list[float]:
        return [
            b - a for a, b in zip(self._times, self._times[1:])
        ]

    @property
    def steps_per_sec(self) -> float:
        if len(self._times) < 2:
            return 0.0
        span = self._times[-1] - self._times[0]
        return (len(self._times) - 1) / span if span > 0 else 0.0

    @property
    def tokens_per_sec(self) -> float:
        return self.steps_per_sec * self.tokens_per_step

    @property
    def step_ms_p50(self) -> float:
        """Median per-step latency (ms) over the window; 0.0 until two
        steps have been recorded."""
        deltas = self._deltas()
        if not deltas:
            return 0.0
        return statistics.median(deltas) * 1e3

    @property
    def step_ms_p95(self) -> float:
        """95th-percentile per-step latency (ms) over the window (linear
        interpolation; equals the max for windows under ~20 steps)."""
        deltas = self._deltas()
        if not deltas:
            return 0.0
        return percentile([d * 1e3 for d in deltas], 0.95)


def percentile(values: list[float], q: float) -> float:
    """Linear-interpolation percentile (numpy 'linear' method), 0.0 on
    empty input — shared by the timer, the timeline, and trace_report."""
    if not values:
        return 0.0
    values = sorted(values)
    if len(values) == 1:
        return values[0]
    pos = q * (len(values) - 1)
    lo = int(pos)
    hi = min(lo + 1, len(values) - 1)
    frac = pos - lo
    return values[lo] * (1 - frac) + values[hi] * frac


# ----------------------------------------------------------------------
# Reading a capture: events from ProfileData, scopes from the wire format
# ----------------------------------------------------------------------
#
# ``jax.profiler.ProfileData`` reads planes, lines and events, but not the
# ``/host:metadata`` plane's ``HloProto`` blobs, which alone hold each
# instruction's ``op_name`` path (``jit(step)/.../ff_layers_0/.../dot``).
# The wire-format reader below reads just those.  Field numbers are the
# public schema of tsl/profiler/protobuf/xplane.proto and
# xla/service/hlo.proto; unknown fields are skipped by wire type.


def _varint(buf: bytes, i: int) -> tuple[int, int]:
    r = 0
    s = 0
    while True:
        b = buf[i]
        i += 1
        r |= (b & 0x7F) << s  # ra: allow(RA012 protobuf varint 7-bit payload mask, not quantization)
        if not b & 0x80:
            return r, i
        s += 7


def _wire_fields(buf: bytes) -> Iterator[tuple[int, int, Any]]:
    """Yield ``(field_number, wire_type, value)`` triples of one message.

    wire type 0 -> int, 2 -> bytes, 1/5 -> raw 8/4 bytes.  Groups (3/4)
    do not occur in these protos; an unknown type aborts the message
    rather than guessing at framing.
    """
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
        elif wt == 5:
            v = buf[i:i + 4]
            i += 4
        elif wt == 1:
            v = buf[i:i + 8]
            i += 8
        else:  # unknown framing: stop decoding this message
            return
        yield fn, wt, v


def _sub(buf: bytes, field: int) -> Iterator[Any]:
    return (v for fn, _, v in _wire_fields(buf) if fn == field)


def _hlo_scopes(hlo_proto: bytes) -> dict[str, str]:
    """``{instruction_name: op_name}`` from a serialized ``HloProto``.

    HloProto.hlo_module=1 -> HloModuleProto.computations=3 ->
    HloComputationProto.instructions=2 -> HloInstructionProto.name=1 /
    .metadata=7 -> OpMetadata.op_name=2 (the ``jit(f)/…/ring/hop0/…``
    path the named_scope annotations put there), .id=35, .operand_ids=36.

    What the compiler inserts itself (a ``copy-start`` that prefetches a
    weight, a layout ``copy``) has no path, or only its argument's name
    (``cache['k'][0]``).  It takes the path of the first instruction that
    uses it, else of its first operand: a weight's prefetch belongs to
    the layer that multiplies by it.
    """
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
                        scope = next(_sub(val, 2), b"").decode(errors="replace")
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


def _index_metadata_plane(
    plane: bytes,
    by_id: dict[int, dict[str, str]],
    by_module: dict[str, dict[str, str]],
) -> None:
    """The ``/host:metadata`` plane: each event-metadata entry (field 4,
    its XEventMetadata in field 2) is one profiled program whose
    ``hlo_proto`` stat holds the serialized HloProto.  Indexed by the
    entry's id (on a TPU the program's fingerprint, which also ends the
    name of its ``XLA Modules`` events) and by the module's name less that
    id (a CPU executable loaded from the compile cache runs under another
    id than the one its HloProto was stored with)."""
    for entry in _sub(plane, 4):
        for meta in _sub(entry, 2):
            meta_id = None
            module_name = ""
            blobs: list[bytes] = []
            for fn, wt, val in _wire_fields(meta):
                if fn == 1 and wt == 0:
                    meta_id = val
                elif fn == 2:
                    module_name = _PROGRAM_ID.sub(
                        "", val.decode(errors="replace"))
                elif fn == 3:  # raw metadata bytes
                    blobs.append(val)
                elif fn == 5:  # XStat whose bytes_value carries the proto
                    blobs.extend(_sub(val, 6))
            for scopes in filter(None, map(_hlo_scopes, blobs)):
                if meta_id is not None:
                    by_id.setdefault(meta_id, {}).update(scopes)
                if module_name:
                    by_module.setdefault(module_name, {}).update(scopes)


class OpEvent(NamedTuple):
    """One profiled op occurrence with its resolved scope path."""

    plane: str
    line: str
    name: str       # HLO instruction name ("dot.14", "collective-permute.4")
    scope: str      # op_name metadata path ("" when the join found none)
    stage: str      # stage label from STAGES ("other" when unmatched)
    kind: str       # "compute" | "transfer" | "other"
    start_ns: int
    dur_ns: int
    chip: int = 0       # device ordinal
    self_ns: int = 0    # dur_ns less the ops that ran inside it (a while's body)
    layer: str = "other"    # the program's layer (STAGES' fourth column)
    pass_: str = "forward"  # forward | recompute | backward | update

    @property
    def end_ns(self) -> int:
        return self.start_ns + self.dur_ns


class HostEvent(NamedTuple):
    """A span of the host's: a ``TraceAnnotation`` or one of the runtime's."""

    name: str
    start_ns: int
    dur_ns: int


class Capture(NamedTuple):
    ops: list[OpEvent]
    host: list[HostEvent]
    programs: list[tuple[int, str, int, int]]  # (chip, name, start_ns, dur_ns)
    note: str = ""  # why nothing could be read; empty on success


# Scope/kernel names -> (needle, stage, kind, layer, pass where the name
# fixes it).  First match wins.  First the stable names threaded through
# parallel/ and ops/ (docs/observability.md §4; "transfer" = inter-device
# payload movement the ring schedule wants hidden under "compute"), then
# the model's own modules, which flax names for free, and utils/train.py's
# ``train/*`` scopes.
_C, _T, _K, _L = "compute", "transfer", "flash kernels", "loss and head"
STAGES: list[tuple[str, str, str, str, str | None]] = [
    ("ring/rotate", "ring kv rotation", _T, "ring", None),
    ("ring/catchup", "ring dkv catch-up", _T, "ring", "backward"),
    ("ring/bwd", "ring backward", _C, "ring", "backward"),
    ("ring/hop", "ring hop compute", _C, "ring", None),
    # fused ring (ops/pallas_ring.py): the CPU-degradable local tier's
    # KV gather is transfer; the single launch itself is compute — its
    # in-kernel remote DMAs never surface as separate timeline ops, which
    # is exactly the launch-free-hops property (docs/ring_overlap.md)
    ("ring/fused_gather", "fused ring kv gather", _T, "ring", None),
    ("ring/fused", "fused ring kernel", _C, "ring", None),
    ("fused_ring", "fused ring kernel", _C, _K, None),
    ("kv_head_reshard", "gqa kv reshard", _T, "ring", None),
    ("ulysses/a2a", "ulysses all-to-all", _T, "ring", None),
    ("ulysses/flash", "ulysses local flash", _C, "ring", None),
    ("hybrid/a2a", "hybrid all-to-all", _T, "ring", None),
    ("hybrid/inner", "hybrid inner ring", _C, "ring", None),
    ("zigzag/gather", "zigzag gather", _T, "ring", None),
    ("zigzag/", "zigzag", _C, "ring", None),
    ("tree_decode/gather", "tree-decode merge", _T, "ring", None),
    ("tree_decode/", "tree-decode local", _C, "ring", None),
    # pallas kernel names, and the XLA blockwise path's named_scopes
    ("flash_bwd", "flash backward kernel", _C, _K, "backward"),
    ("flash/bwd", "flash backward", _C, "xla flash", "backward"),
    ("flash_decode", "flash decode kernel", _C, _K, None),
    ("flash/fwd", "flash forward", _C, "xla flash", None),
    ("flash", "flash forward kernel", _C, _K, None),
    ("train/optimizer", "optimizer update", _C, "optimizer", "update"),
    ("train/clip", "gradient clip and guard", _C, "optimizer", "update"),
    ("train/accumulate", "gradient accumulation", _C, "optimizer", "update"),
    # ahead of the method it sits in: the chunked loss's gradient products,
    # made in its forward scan, are a stage of their own
    ("loss/grad", "loss gradient", _C, _L, None),
    ("_chunked_ce", "loss", _C, _L, None),
    ("loss/nll", "loss", _C, _L, None),
    ("_valid_labels", "loss", _C, _L, None),
    ("to_logits", "logits head", _C, _L, None),
    ("final_norm", "final norm", _C, _L, None),
    # the routed feed-forward (models/moe.py) and the gated attention's
    # own parts, ahead of the module rows they sit inside
    ("moe/groups", "expert group choice", _C, "router", None),
    ("moe/router", "expert router", _C, "router", None),
    ("moe/dispatch", "expert dispatch", _C, "experts", None),
    ("moe/experts", "expert products", _C, "experts", None),
    ("moe/shared", "shared expert", _C, "experts", None),
    # the add of a pass's rows into the layer's output, ahead of the
    # needle it holds
    ("moe/combine_sum", "expert combine sum", _C, "experts", None),
    ("moe/combine", "expert combine", _C, "experts", None),
    # latent attention's own parts (models/attention.py LatentAttention);
    # its decode kernel is a flash kernel by its name (flash_decode_latent)
    ("attn/latent_q", "latent query projections", _C, "latent attention", None),
    ("attn/latent_kv", "latent key/value and cache write", _C,
     "latent attention", None),
    ("attn/expand", "latent expanded to heads", _C, "latent attention", None),
    ("attn/absorb", "latent absorbed products", _C, "latent attention", None),
    # a Mamba-2 mixer's own parts (models/ssm.py); its decode kernel is a
    # state space row by its name, wherever it is called from
    ("ssm_decode_step", "state-space decode kernel", _C, "state space", None),
    ("ssm/in_proj", "state-space input projection", _C, "state space", None),
    ("ssm/conv", "state-space convolution", _C, "state space", None),
    ("ssm/scan", "state-space chunked scan", _C, "state space", None),
    ("ssm/step", "state-space recurrent step", _C, "state space", None),
    ("ssm/gate_norm", "state-space gate and norm", _C, "state space", None),
    ("ssm/out_proj", "state-space output projection", _C, "state space", None),
    # an attention module's own parts (models/attention.py), behind the
    # latent rows: a latent layer's positional keys are rotated inside
    # attn/latent_kv and stay there.  What no scope names falls to the
    # attn_layers_ row below
    ("attn/qk_norm", "q/k norm", _C, "attention projections", None),
    ("attn/gate", "attention output gate", _C, "attention projections", None),
    ("attn/qkv_proj", "qkv product", _C, "attention projections", None),
    ("attn/heads", "head layout", _C, "attention projections", None),
    ("attn/rotary", "rotary", _C, "attention projections", None),
    ("attn/cache_write", "cache write", _C, "attention projections", None),
    ("attn/out_proj", "output product", _C, "attention projections", None),
    # around the attention call's kernel; the sequence-parallel scopes,
    # flash/* and the kernels' own names stand ahead of it
    ("attn/kernel_io", "kernel operand layout", _C, "attention projections",
     None),
    ("post_attn_norms_", "post-attention norm", _C, "attention projections", None),
    ("post_ff_norms_", "post-feed-forward norm", _C, "feed-forward", None),
    ("ff_layers_", "feed-forward", _C, "feed-forward", None),
    ("attn_layers_", "attention projections", _C, "attention projections", None),
    # the stack walker's own adds (models/transformer.py _blocks), then
    # what else runs at its level in no module's scope: the residual
    # stream's gradient sums (JAX's add_any) and remat's copies
    ("block/residual", "residual add", _C, "residual", None),
    ("_blocks/", "stack walker", _C, "residual", None),
    ("embed", "embedding", _C, "embed", None),
]

# instruction-name prefixes that are payload movement wherever they sit
# (an unattributed collective is itself a finding — RA004 lints the source
# side of this); jax 0.9 names the CPU's after the primitive
_COLLECTIVE_PREFIXES = (
    "collective-permute", "all-to-all", "all-gather", "all-reduce",
    "reduce-scatter", "collective-broadcast", "ppermute", "all_to_all",
    "all_gather", "psum",
)
_PERMUTE_PREFIXES = ("collective-permute", "ppermute")
_CONTAINER_PREFIXES = ("while", "conditional", "call")

_SUFFIX = re.compile(r"(\.(\d+|clone|remat))+$")  # "fusion.12.clone" -> "fusion"
_HOP_RE = re.compile(r"ring/(?:bwd_)?hop(\d+)")
_ROTATE_RE = re.compile(r"ring/rotate(\d+)")


def _stage_row(hay: str, kernels: bool = True):
    hay = hay.lower()
    return next((row for row in STAGES if row[0] in hay
                 and (kernels or row[3] != _K)), None)


def stage_of(name: str, scope: str = "") -> tuple[str, str]:
    """``(label, kind)`` for an op: scope needles first (first match in
    STAGES wins), then the bare-collective fallback, else ``other``.  A
    collective is a transfer whatever scope it sits in."""
    row = _stage_row(scope or name)
    collective = name.startswith(_COLLECTIVE_PREFIXES)
    if row and collective and row[2] != "transfer":
        return f"collective in {row[1]}", "transfer"
    if row:
        return row[1], row[2]
    if collective:
        return "unattributed collective", "transfer"
    return "other", "other"


def layer_of(name: str, scope: str = "") -> tuple[str, str]:
    """``(layer, pass)`` for an op.  A kernel is named by its own name
    wherever it is called from (a ``flash_partials_tile`` inside
    ``ring/hop2`` is a flash kernel) and by nothing else: the glue XLA
    runs beside it is its scope's.  Everything else goes by its scope.
    The pass is the row's where the name fixes it, else what JAX wrote
    into the path: ``rematted_computation`` under ``nn.remat``'s backward,
    ``transpose(jvp(...))`` for the backward itself."""
    row = _stage_row(name) or _stage_row(scope, kernels=False)
    layer = row[3] if row else (
        "ring" if name.startswith(_COLLECTIVE_PREFIXES) else "other")
    if row and row[4]:
        return layer, row[4]
    if "rematted_computation" in scope:
        return layer, "recompute"
    return layer, "backward" if "transpose(" in scope else "forward"


def _self_times(spans: list[tuple[int, int]]) -> list[int]:
    """Self time of each ``(start, end)`` on one chip: the time during
    which it is the innermost span open.  A ``while`` keeps what its body
    leaves; the self times sum to the union of the spans exactly."""
    out = [0] * len(spans)
    order = sorted(range(len(spans)),
                   key=lambda i: (spans[i][0], -spans[i][1]))
    stack, at = [], 0  # indices of the spans that are open, innermost last
    for i in [*order, None]:  # None: drain what is still open
        until = spans[i][0] if i is not None else max(
            (end for _, end in spans), default=0)
        while stack and at < until:  # the innermost open span has [at, ...)
            end = spans[stack[-1]][1]
            if end > at:
                out[stack[-1]] += min(end, until) - at
                at = min(end, until)
            if end <= at:
                stack.pop()
        at = max(at, until)
        if i is not None:
            stack.append(i)
    return out


_DEVICE_PLANE = re.compile(r"^/device:[A-Z]+:(\d+)$")


def read_capture(path: str) -> Capture:
    """The newest ``*.xplane.pb[.gz]`` under ``path`` (or the file itself)
    as device ops with their scopes, host events, and which program ran
    when.  Never raises: the note says why there is nothing."""
    found = [path] if not os.path.isdir(path) else sorted(
        (p for ext in ("*.xplane.pb", "*.xplane.pb.gz") for p in glob.glob(
            os.path.join(path, "**", ext), recursive=True)),
        key=os.path.getmtime)
    if not found:
        return Capture([], [], [], f"no .xplane.pb under {path}")
    by_id: dict[int, dict[str, str]] = {}
    by_module: dict[str, dict[str, str]] = {}
    try:
        from jax.profiler import ProfileData  # what reads the events

        with open(found[-1], "rb") as f:
            data = f.read()
        if found[-1].endswith(".gz"):
            data = gzip.decompress(data)
        for plane in _sub(data, 1):
            if b"metadata" in next(_sub(plane, 2), b""):
                _index_metadata_plane(plane, by_id, by_module)
        planes = list(ProfileData.from_serialized_xspace(data).planes)
        capture = _join(planes, by_id, by_module)
    except ImportError as e:
        return Capture([], [], [], f"jax cannot be imported here: {e}")
    except Exception as e:  # noqa: BLE001 - unreadable, or truncated by a
        # killed profiler: a note, because the timeline is a diagnostic
        return Capture([], [], [], f"unreadable or malformed capture "
                                   f"{found[-1]}: {type(e).__name__}: {e}")
    if not capture.ops:
        return capture._replace(note=f"no op events parsed from {found[-1]}")
    return capture


def read_xplane_events(path: str) -> tuple[list[OpEvent], str]:
    """``(ops, note)`` of :func:`read_capture`; never raises."""
    capture = read_capture(path)
    return capture.ops, capture.note


def _join(planes, by_id, by_module) -> Capture:
    """Ops of every chip joined to their scopes.  On a TPU a chip is a
    ``/device:TPU:<n>`` plane: its ``XLA Ops`` events carry only times and
    the instruction's text, and the program is the enclosing event of the
    ``XLA Modules`` line.  A CPU capture has no device plane: its ops sit
    among the host threads' events and carry ``hlo_op``, ``program_id``
    and ``device_ordinal`` stats."""
    raw: list[tuple] = []  # plane, line, chip, instruction, scopes, start, dur
    host: list[HostEvent] = []
    programs: list[tuple[int, str, int, int]] = []
    on_device = any(_DEVICE_PLANE.match(p.name) for p in planes)

    def scopes_of(program_id, module):
        return by_id.get(program_id) or by_module.get(module) or (
            next(iter(by_module.values())) if len(by_module) == 1 else {})

    for plane in planes:
        device = _DEVICE_PLANE.match(plane.name)
        lines = {line.name: line.events for line in plane.lines}
        if device:
            chip = int(device.group(1))
            ran = sorted((int(e.start_ns), int(e.duration_ns), e.name)
                         for e in lines.get("XLA Modules", ()))
            programs += [(chip, _PROGRAM_ID.sub("", n), s, d)
                         for s, d, n in ran]
            starts = [s for s, _, _ in ran]
            tables = {"": scopes_of(None, "")}
            for _, _, n in ran:
                found = _PROGRAM_ID.search(n)
                tables[n] = scopes_of(found and int(found.group(1)),
                                      _PROGRAM_ID.sub("", n))
            for e in lines.get("XLA Ops", ()):
                start = int(e.start_ns)
                i = bisect.bisect_right(starts, start) - 1
                inside = i >= 0 and start < ran[i][0] + ran[i][1]
                # "%fusion.12 = bf16[...] fusion(...)" -> "fusion.12"
                raw.append((plane.name, "XLA Ops", chip,
                            e.name.lstrip("%").split(" ", 1)[0],
                            tables[ran[i][2] if inside else ""],
                            start, int(e.duration_ns)))
        elif plane.name.startswith("/host:"):
            for lname, events in lines.items():
                for e in events:
                    stats = {} if on_device else dict(e.stats)
                    if "hlo_op" in stats and "program_id" in stats:
                        raw.append((
                            plane.name, lname,
                            int(stats.get("device_ordinal", 0)),
                            str(stats["hlo_op"]),
                            scopes_of(int(stats["program_id"]),
                                      str(stats.get("hlo_module", ""))),
                            int(e.start_ns), int(e.duration_ns)))
                    elif e.duration_ns > 0:
                        host.append(HostEvent(
                            e.name, int(e.start_ns), int(e.duration_ns)))
    selfs = [0] * len(raw)
    for chip in {r[2] for r in raw}:
        members = [i for i, r in enumerate(raw) if r[2] == chip]
        for i, t in zip(members, _self_times(
                [(raw[i][5], raw[i][5] + raw[i][6]) for i in members])):
            selfs[i] = t
    memo: dict[tuple, tuple] = {}
    ops = []
    for (pname, lname, chip, name, table, start, dur), self_ns in zip(
            raw, selfs):
        key = (id(table), name)
        if key not in memo:
            scope = table.get(name, "")
            memo[key] = (scope, *stage_of(name, scope),
                         *layer_of(name, scope))
        scope, stage, kind, layer, pass_ = memo[key]
        ops.append(OpEvent(pname, lname, name, scope, stage, kind, start,
                           max(dur, 1), chip, self_ns, layer, pass_))
    return Capture(ops, host, programs)


# ----------------------------------------------------------------------
# Timeline reconstruction + measured overlap
# ----------------------------------------------------------------------


def stage_timeline(
    events: list[OpEvent], *, ring_size: int | None = None
) -> dict[str, Any]:
    """Per-stage/per-hop timeline over one capture.

    Returns::

        {"stages": [{"stage", "kind", "events", "busy_ms",
                     "p50_ms", "p95_ms"}, ...],        # busy-desc order
         "hops":   [{"hop", "compute_ms", "transfer_ms",
                     "samples"}, ...],                  # hop index order
         "total_busy_ms": float}

    Times are self times.  ``p50/p95`` are over stage *instances*, one
    sample per (line, stage, hop-index) group, not per HLO op: a hop that
    fragments into 40 fusions is one latency sample.  The unrolled Pallas
    path carries static ``ring/hop{i}`` / ``ring/rotate{i}`` indices; the
    XLA scan path re-runs ONE set of instructions per hop, so its indices
    are reconstructed temporally: on each line, hop ``i`` is whatever runs
    after the line's ``i``-th completed KV rotation (approximate when a
    thread pool interleaves devices on one line, hence ``samples``).

    For a multi-step capture pass ``ring_size``: hop indices fold modulo
    the ring and each step contributes its own latency sample (a hop
    index that DECREASES on a line marks the step boundary).
    """
    instances: dict[tuple, float] = {}
    stage_events: dict[str, int] = {}
    stage_kind: dict[str, str] = {}
    hop_busy: dict[int, dict[str, float]] = {}
    hop_samples: dict[int, int] = {}
    rotations_seen: dict[tuple[str, str], int] = {}
    prev_hop: dict[tuple[str, str], int] = {}
    cycles: dict[tuple[str, str], int] = {}
    for ev in sorted(events, key=lambda e: e.start_ns):
        if ev.stage == "other":
            continue
        line_key = (ev.plane, ev.line)
        hop = None
        m = _HOP_RE.search(ev.scope) or _ROTATE_RE.search(ev.scope)
        if m:
            hop = int(m.group(1))
        elif ev.stage == "ring kv rotation":
            hop = rotations_seen.get(line_key, 0)
            if ev.name.startswith(_PERMUTE_PREFIXES):
                # the permute op itself advances the line's hop counter;
                # its satellite copies/converts stay on the same index
                rotations_seen[line_key] = hop + 1
        elif ev.stage in ("ring hop compute", "ring backward"):
            hop = rotations_seen.get(line_key, 0)
        cycle = 0
        if hop is not None:
            if ring_size:
                hop %= ring_size
            # a hop index going BACKWARDS on a line = a new step/cycle:
            # its occurrences become fresh latency samples instead of
            # accumulating into the first step's instance
            if hop < prev_hop.get(line_key, hop):
                cycles[line_key] = cycles.get(line_key, 0) + 1
            prev_hop[line_key] = hop
            cycle = cycles.get(line_key, 0)
        key = (ev.plane, ev.line, ev.stage, hop, cycle)
        first = key not in instances
        instances[key] = instances.get(key, 0.0) + ev.self_ns / 1e6
        stage_events[ev.stage] = stage_events.get(ev.stage, 0) + 1
        stage_kind[ev.stage] = ev.kind
        if hop is not None:
            slot = hop_busy.setdefault(hop, {"compute": 0.0, "transfer": 0.0})
            if ev.kind in slot:
                slot[ev.kind] += ev.self_ns / 1e6
            if first:
                hop_samples[hop] = hop_samples.get(hop, 0) + 1
    per_stage: dict[str, list[float]] = {}
    for (_, _, stage, _, _), busy in instances.items():
        per_stage.setdefault(stage, []).append(busy)
    stages = [
        {
            "stage": stage,
            "kind": stage_kind[stage],
            "events": stage_events[stage],
            "busy_ms": round(sum(samples), 4),
            "p50_ms": round(percentile(samples, 0.5), 4),
            "p95_ms": round(percentile(samples, 0.95), 4),
        }
        for stage, samples in per_stage.items()
    ]
    stages.sort(key=lambda r: -r["busy_ms"])
    hops = [
        {
            "hop": hop,
            "compute_ms": round(hop_busy[hop]["compute"], 4),
            "transfer_ms": round(hop_busy[hop]["transfer"], 4),
            "samples": hop_samples.get(hop, 0),
        }
        for hop in sorted(hop_busy)
    ]
    return {
        "stages": stages,
        "hops": hops,
        "total_busy_ms": round(sum(r["busy_ms"] for r in stages), 4),
    }


def _merge_intervals(spans: list[tuple[int, int]]) -> list[tuple[int, int]]:
    if not spans:
        return []
    spans = sorted(spans)
    out = [spans[0]]
    for lo, hi in spans[1:]:
        if lo <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], hi))
        else:
            out.append((lo, hi))
    return out


def measured_overlap(events: list[OpEvent]) -> dict[str, Any]:
    """Measured compute/transfer overlap over one capture.

    Merges all transfer spans (KV rotations, all-to-alls, catch-up
    permutes) and all compute spans into interval unions and reports what
    fraction of transfer wall time ran concurrently with compute —
    ``overlap_fraction = overlapped_ms / transfer_ms`` (0.0 when the
    capture has no transfer spans, which is its own finding).  The
    empirical counterpart of ``ring_comms_accounting``'s
    ``hop_overlap_fraction``: the analytic one says whether the shapes
    *can* hide the hop, this one says whether the schedule *did*.
    """
    # a while spans its whole body, transfers and all: leaves only
    leaves = [e for e in events
              if not e.name.startswith(_CONTAINER_PREFIXES)]
    transfer = _merge_intervals(
        [(e.start_ns, e.end_ns) for e in leaves if e.kind == "transfer"]
    )
    compute = _merge_intervals(
        [(e.start_ns, e.end_ns) for e in leaves if e.kind == "compute"]
    )
    transfer_ns = sum(hi - lo for lo, hi in transfer)
    compute_ns = sum(hi - lo for lo, hi in compute)
    overlapped = 0
    ci = 0
    for lo, hi in transfer:
        while ci < len(compute) and compute[ci][1] <= lo:
            ci += 1
        cj = ci
        while cj < len(compute) and compute[cj][0] < hi:
            overlapped += min(hi, compute[cj][1]) - max(lo, compute[cj][0])
            cj += 1
    return {
        "compute_ms": round(compute_ns / 1e6, 4),
        "transfer_ms": round(transfer_ns / 1e6, 4),
        "overlapped_ms": round(overlapped / 1e6, 4),
        "overlap_fraction": (
            round(overlapped / transfer_ns, 4) if transfer_ns else 0.0
        ),
    }


def overlap_report(
    source: str | list[OpEvent],
    *,
    analytic: float | dict | None = None,
    tolerance: float = 0.25,
    ring_size: int | None = None,
) -> dict[str, Any]:
    """Timeline + measured overlap for a capture, compared against the
    analytic model when one is supplied.

    ``source`` is a capture directory/file or pre-parsed events;
    ``analytic`` is ``ring_comms_accounting(...)`` output (its
    ``hop_overlap_fraction`` is used) or a bare fraction.  When the two
    disagree by more than ``tolerance``, the report carries
    ``agrees=False`` plus a one-line ``finding``: a model that no longer
    describes the hardware is itself a regression.
    """
    if isinstance(source, str):
        events, note = read_xplane_events(source)
    else:
        events, note = source, ""
    report: dict[str, Any] = {"parsed_events": len(events)}
    if note:
        report["note"] = note
        return report
    report["timeline"] = stage_timeline(events, ring_size=ring_size)
    report.update(measured_overlap(events))
    if analytic is not None:
        if isinstance(analytic, dict):
            analytic = analytic.get("hop_overlap_fraction", 0.0)
        report["analytic_overlap_fraction"] = round(float(analytic), 4)
        delta = abs(report["overlap_fraction"] - float(analytic))
        report["overlap_delta"] = round(delta, 4)
        report["tolerance"] = tolerance
        report["agrees"] = delta <= tolerance
        if not report["agrees"]:
            report["finding"] = (
                f"measured overlap {report['overlap_fraction']:.3f} vs "
                f"analytic {float(analytic):.3f} (|delta| "
                f"{delta:.3f} > tolerance {tolerance:.3f}) — the comms "
                f"model no longer describes this capture"
            )
    return report


# ----------------------------------------------------------------------
# Every device millisecond to a layer and a pass
# ----------------------------------------------------------------------

# What the host was doing: the first activity that any event open at an
# instant matches.  A launch still being enqueued keeps the device waiting
# whatever else the host does: dispatch is first.  ``np.asarray`` is in
# neither: it is open for the whole device step it waits for.
HOST_ACTIVITIES: list[tuple[str, tuple[str, ...]]] = [
    ("dispatch", ("PjitFunction", "LoadedExecutable", "ExecuteHelper",
                  "ExecuteLaunch", "IssueSequencedEvent", "EnqueueProgram",
                  "EnqueueContinuation", "AllocateAndFillTupleIndexTable")),
    ("fetch", ("D2H", "TransferFromDevice", "ToLiteral", "Delinearize")),
]
# the idle row is split by host activity only where the capture's two
# clocks are known to sit this close together
ALIGNED_NS = 100_000
_LAUNCH, _BLOCKING_FETCH = "PjitFunction(", "np.asarray"


def _extent(capture: Capture, window) -> tuple[int, int] | None:
    if isinstance(window, tuple):
        return window
    names = {window} if isinstance(window, str) else set(window or ())
    spans = [(o.start_ns, o.end_ns) for o in capture.ops] if window is None else [
        (s, s + d) for n, s, d in [*capture.host, *(p[1:] for p in capture.programs)]
        if n in names]
    return (min(s for s, _ in spans), max(e for _, e in spans)) if spans else None


def _activity_of(names) -> str:
    return next((a for a, needles in HOST_ACTIVITIES
                 if any(n in name for name in names for n in needles)),
                "other")


def _host_activity(host, lo, hi) -> dict[str, int]:
    """Nanoseconds of ``[lo, hi]`` by what the host was doing, on the
    host's clock alone (no device timestamp enters): the union over its
    threads of the events of each of :data:`HOST_ACTIVITIES`, the first
    that matches at an instant (what the benchmark's
    ``entry.dispatch_ms_per_token`` / ``entry.fetch_ms_per_token`` read)."""
    edges = []
    for h in host:
        act = _activity_of([h.name])
        a, b = max(h.start_ns, lo), min(h.start_ns + h.dur_ns, hi)
        if act != "other" and a < b:
            edges += [(a, 1, act), (b, -1, act)]
    edges.sort()
    out = {a: 0 for a, _ in HOST_ACTIVITIES}
    open_ = dict.fromkeys(out, 0)
    at = lo
    for t, step, act in edges:
        now = next((a for a in out if open_[a]), None)
        if now is not None:
            out[now] += t - at
        at = t
        open_[act] += step
    return out


def _offset_bounds(capture: Capture, lo, hi, chip):
    """``(lower, upper)`` nanoseconds, either ``None`` where nothing
    bounds it: what may be added to the device's timestamps to put them
    on the host's clock without breaking causality.  A program cannot
    start on the device before the host call that launched it began (the
    ``i``-th ``PjitFunction(jit(f))`` is the ``i``-th ``jit_f`` on the
    chip): the largest such lower bound over the window's programs.  A
    blocking fetch (``np.asarray``) that began after a program's launch
    and before the next cannot end before that program has: the smallest
    such upper bound.  A CPU capture's ops are host events: ``(0, 0)``."""
    if not capture.programs:
        return 0, 0
    calls: dict[str, list[tuple[int, int]]] = {}
    for h in capture.host:
        if h.name.startswith(_LAUNCH):
            name = re.sub(r"\W+", "_", h.name[len(_LAUNCH):]).strip("_")
            calls.setdefault(name, []).append(
                (h.start_ns, h.start_ns + h.dur_ns))
    launches = {n: _merge_intervals(spans) for n, spans in calls.items()}
    every = sorted(s for spans in launches.values() for s, _ in spans)
    fetches = sorted((h.start_ns, h.start_ns + h.dur_ns)
                     for h in capture.host
                     if h.name.startswith(_BLOCKING_FETCH))
    ran: dict[str, list[tuple[int, int]]] = {}
    for c, name, start, dur in sorted(capture.programs, key=lambda p: p[2]):
        if c == chip:
            ran.setdefault(name, []).append((start, start + dur))
    lower = upper = None
    for name, runs in ran.items():
        if len(launches.get(name, ())) != len(runs):
            continue  # a capture that began or ended inside a call
        for (start, end), (called, _) in zip(runs, launches[name]):
            if not lo <= start < hi:
                continue
            lower = called - start if lower is None else max(
                lower, called - start)
            nxt = bisect.bisect_right(every, called)
            until = every[nxt] if nxt < len(every) else float("inf")
            done = [e for s, e in fetches if called <= s < until]
            if done:
                upper = min(done) - end if upper is None else min(
                    upper, min(done) - end)
    return lower, upper


def _host_split(idle, host, lo, hi) -> dict[tuple[str, str], int]:
    """Idle nanoseconds by ``(innermost host event, activity)``: one sweep
    over the idle intervals' and the host events' edges."""
    edges = []  # (time, opens-after-closes, host index or None for idle)
    for a, b in idle:
        edges += [(a, 1, None), (b, 0, None)]
    for i, h in enumerate(host):
        if h.start_ns < hi and h.start_ns + h.dur_ns > lo:
            edges += [(h.start_ns, 1, i), (h.start_ns + h.dur_ns, 0, i)]
    edges.sort(key=lambda e: e[:2])
    out: dict[tuple[str, str], int] = {}
    open_: set[int] = set()
    idling, at = False, lo
    for t, opens, i in edges:
        if idling and t > at:
            names = [host[j].name for j in sorted(
                open_, key=lambda j: (host[j].start_ns, -host[j].dur_ns))]
            key = (names[-1] if names else "(no host event)",
                   _activity_of(names))
            out[key] = out.get(key, 0) + t - at
        at = max(at, t)
        if i is None:
            idling = bool(opens)
        else:
            (open_.add if opens else open_.discard)(i)
    return out


def layer_breakdown(capture: str | Capture, window=None, *, per=None,
                    chip: int | None = None) -> dict[str, Any]:
    """Every nanosecond of a traced window on one chip, put down to a
    layer of the program and a pass of the step, or to idle.

    ``window``: ``None`` for the extent of the device's ops; a name (or
    several) for the extent of the host events or programs so named
    (``"bench/step"``, ``"jit_decode_fn"``); or ``(lo_ns, hi_ns)``.
    ``per``: a number of units, or a name whose events inside the window
    are counted (steps, tokens); times are per unit.  ``chip``: default
    the one that was busy longest.

    ``rows``: ``{layer, pass, ms, share, ops, transfer_ms}`` by time, then
    ``other`` rows for ops whose scope matched nothing (counted, never
    dropped; ``other_ops`` names the largest) and an ``idle`` row; their
    ``ms`` sum to ``window_ms`` and ``transfer_ms`` is the collectives'
    part of a row.  ``stages``: the same time by ``{layer, stage, pass}``
    (:data:`STAGES`' labels) with each stage's five largest instruction
    names in ``top``.  An operation is one fusion and a fusion has one
    path, its root's: where XLA fuses two scopes' work into one
    instruction the pair's time falls to one stage, so neighbouring
    stages' sum is firm and their split is the compiler's.

    ``host_activity``: the host's dispatch and fetch time a unit, on the
    host's clock alone.  ``offset_bounds_ms``: the causal bounds on what
    separates the capture's two clocks (:func:`_offset_bounds`).  Only
    where they are closer than :data:`ALIGNED_NS` is the idle row cut by
    host activity: ``idle_host`` splits it by the innermost host event
    open across it, whoever's it is, and ``idle_activity`` sums that into
    dispatch, fetch and other (:data:`HOST_ACTIVITIES`); otherwise both
    are ``None`` (a TPU capture's clocks sit 0.3 to 0.8 ms apart, more
    than a launch takes).
    """
    if isinstance(capture, str):
        capture = read_capture(capture)
    if capture.note:
        return {"note": capture.note}
    extent = _extent(capture, window)
    if extent is None:
        return {"note": f"nothing in the capture is named {window!r}"}
    lo, hi = extent
    clipped: dict[int, list[tuple[OpEvent, int, int]]] = {}
    for o in capture.ops:
        a, b = max(o.start_ns, lo), min(o.end_ns, hi)
        if a < b:
            clipped.setdefault(o.chip, []).append((o, a, b))
    if not clipped:
        return {"note": f"no device op inside the window {window!r}"}
    times = {c: _self_times([(a, b) for _, a, b in members])
             for c, members in clipped.items()}
    if chip is None:
        chip = max(times, key=lambda c: sum(times[c]))
    if isinstance(per, str):
        starts = [h.start_ns for h in capture.host if h.name == per] + [
            s for c, n, s, _ in capture.programs if n == per and c == chip]
        per = sum(lo <= s < hi for s in starts)
    units = per or 1
    scale = 1e-6 / units
    cells: dict[tuple[str, str], list[int]] = {}
    stages: dict[tuple[str, str, str], dict[str, int]] = {}
    unscoped: dict[str, float] = {}
    for (o, _, _), t in zip(clipped[chip], times[chip]):
        cell = cells.setdefault((o.layer, o.pass_), [0, 0, 0])
        cell[0] += t
        cell[1] += 1
        cell[2] += t if o.kind == "transfer" else 0
        key = _SUFFIX.sub("", o.name)
        names = stages.setdefault((o.layer, o.stage, o.pass_), {})
        names[key] = names.get(key, 0) + t
        if o.layer == "other":
            unscoped[key] = unscoped.get(key, 0.0) + t * scale
    cells["idle", ""] = [(hi - lo) - sum(times[chip]), 0, 0]
    rows = [{"layer": layer, "pass": pass_, "ms": ns * scale,
             "share": ns / (hi - lo), "ops": n, "transfer_ms": moved * scale}
            for (layer, pass_), (ns, n, moved) in cells.items()]
    rows.sort(key=lambda r: ({"other": 1, "idle": 2}.get(r["layer"], 0),
                             -r["ms"]))
    spans = _merge_intervals([(a, b) for _, a, b in clipped[chip]])
    edges = [lo, *(t for span in spans for t in span), hi]
    idle = [(a, b) for a, b in zip(edges[::2], edges[1::2]) if a < b]
    bounds = _offset_bounds(capture, lo, hi, chip)
    activity = idle_host = None
    if None not in bounds and 0 <= bounds[1] - bounds[0] < ALIGNED_NS:
        split = _host_split(idle, capture.host, lo, hi)
        activity = {}
        for (_, act), ns in split.items():
            activity[act] = activity.get(act, 0.0) + ns * scale
        idle_host = sorted(
            ({"event": e, "activity": a, "ms": ns * scale}
             for (e, a), ns in split.items()), key=lambda r: -r["ms"])
    return {
        "window_ms": (hi - lo) * scale, "units": units, "chip": chip,
        "busy_ms_by_chip": {c: sum(t) * scale for c, t in sorted(times.items())},
        "rows": rows,
        "stages": sorted(
            ({"layer": layer, "stage": stage, "pass": pass_,
              "ms": sum(names.values()) * scale,
              "top": [(n, ns * scale) for n, ns in sorted(
                  names.items(), key=lambda x: -x[1])[:5]]}
             for (layer, stage, pass_), names in stages.items()),
            key=lambda r: (r["layer"], -r["ms"])),
        "other_ops": sorted(unscoped.items(), key=lambda x: -x[1])[:10],
        "host_activity": {a: ns * scale for a, ns in _host_activity(
            capture.host, lo, hi).items()},
        "offset_bounds_ms": [None if b is None else b * 1e-6 for b in bounds],
        "idle_host": idle_host,
        "idle_activity": activity,
    }
