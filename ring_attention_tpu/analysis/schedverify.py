"""DMA/semaphore protocol verifier for the fused-ring kernel.

PR 18's ``fused_ring_remote`` is the only code in the package with real
device-to-device concurrency, and its review found two genuine races
that no shipped analyzer could have caught: a grant-less push variant
whose incoming DMA overwrote KV mid-read under causal compute skew, and
ring-rank LOGICAL DMA device ids that address the wrong replica group on
multi-axis meshes.  ``contracts.py`` counts the kernel's DMA/semaphore
primitives but proves nothing about their *ordering* — this module adds
the ordering proof, in three layers:

  1. **Extraction** (:func:`extract_fused_schedule`) — a
     :class:`dataflow.JaxprWalker` subclass threads kernel-invar
     IDENTITY (not dtype) through the pallas kernel's cond branches and
     while carries, so every ``dma_start`` / ``dma_wait`` /
     ``semaphore_signal`` / ``semaphore_wait`` equation in the traced
     kernel resolves to named buffers and semaphores, a remote/local
     classification (the param tree's trailing device-id leaves), and
     its ``DeviceIdType`` (anything but MESH on the remote ops is a
     finding — the logical-id review bug, caught at the jaxpr).
  2. **The declared protocol** (``ops/pallas_ring.py::PROTOCOL``) — a
     literal table of copy/handshake rows (slots, semaphores, guards,
     the receiver->sender grant) that the extracted equations are
     cross-checked against site-by-site (:func:`crosscheck_protocol`).
     The fused contract's primitive counts are DERIVED from the table
     (:func:`derived_fused_counts`), so the pins can never drift from
     the verified model; lint RA015 fences the call sites to the rows.
  3. **Model check** (:func:`verify_protocol`) — the table is expanded
     into the composed N-device event schedule for ring sizes 2..8 (and
     a 2-group mesh, proving MESH addressing stays inside the replica
     group) and checked symbolically: every ``dma_start`` has a
     matching wait on both ends; no kvbuf slot is written while a
     concurrent reader holds it (the race detector — a guaranteed
     happens-before graph built to a fixpoint from semaphore signal->
     wait edges, sound under ARBITRARY per-device compute skew); all
     semaphores drain to zero at schedule end; and the schedule cannot
     deadlock (greedy maximal simulation — semaphore-only programs are
     confluent: signals only produce and waits only consume, so if the
     eager schedule completes, every fair schedule completes).

Violations are one-line diagnostics naming hop/slot/semaphore, the house
style of ``coverage.py``/``contracts.py``.  The grant-less and
logical-id review bugs are kept alive as protocol variants
(:func:`grantless_protocol`, :func:`logical_id_protocol`) that the
negative regression tests feed back through the verifier.

The happens-before construction: a signal->wait edge is added only when
the wait CANNOT complete in any execution without that signal — for a
wait needing cumulative count C on a semaphore instance, a signal is
necessary iff the other signals that could still land before the wait
(those not already ordered after it) sum below C.  Adding an edge
shrinks "could still land" for other waits, so the rule iterates to a
fixpoint; the result under-approximates real ordering (sound: a race it
cannot exclude is reported).  Local tile-scoped pairs (``load_sem``,
``kv_sems`` — start and wait in the same tile) are proven by the
extraction cross-check and modeled as atomic; the circulated
``send/recv/grant/barrier`` semaphores carry the cross-device protocol
and are modeled exactly.

Like ``dataflow.py``: stdlib-only at module level; jax (and the kernel
module) import inside functions.  Extraction runs at trace level on any
backend — CPU with 8 virtual devices is the test tier.
"""

from __future__ import annotations

from collections import Counter, defaultdict
from dataclasses import dataclass, field

from .dataflow import EqnSite, JaxprWalker

# The primitive surface the verifier accounts for (the fused contract's
# FUSED_RING_PRIMS minus ppermute, whose pin is "zero, anywhere").
SCHED_PRIMS = (
    "dma_start", "dma_wait", "semaphore_signal", "semaphore_wait",
    "get_barrier_semaphore",
)

# Ring sizes the model check proves (the ISSUE's 2..8), and the second
# mesh axis size used to prove MESH addressing resolves inside the
# sender's replica group.
VERIFY_RINGS = (2, 3, 4, 5, 6, 7, 8)
MESH_GROUPS = 2


def _protocol():
    from ..ops.pallas_ring import PROTOCOL

    return PROTOCOL


# ---------------------------------------------------------------------------
# Layer 1: jaxpr extraction
# ---------------------------------------------------------------------------

# Kernel invar order is fixed by fused_ring_remote's grid spec: 4 scalar-
# prefetch tables, the operands, 6 outputs (out/lse + the HBM working
# buffers), 9 scratch refs.  The quantized feed inserts the q-scale
# operand; payload parts take the k/v source roles.
_REF_NAMES_PLAIN = (
    "his", "los", "works", "nbrs", "q", "k_src", "v_src",
    "out", "lse", "kvbuf", "accb", "mb", "lb",
    "kvv", "acc", "m", "l",
    "load_sem", "kv_sems", "send_sem", "recv_sem", "grant_sem",
)
_REF_NAMES_Q8 = (
    "his", "los", "works", "nbrs", "q", "qs", "k_src", "v_src",
    "out", "lse", "kvbuf", "accb", "mb", "lb",
    "kvv", "acc", "m", "l",
    "load_sem", "kv_sems", "send_sem", "recv_sem", "grant_sem",
)


@dataclass(frozen=True)
class ExtractedOp:
    """One DMA/semaphore equation from the traced kernel, resolved."""

    kind: str               # primitive name
    path: str               # EqnSite path string inside the kernel
    bufs: tuple             # non-semaphore ref names, invar order
    sems: tuple             # semaphore ref names, invar order
    remote: bool            # carries device-id leaves in its param tree
    device_id_type: str     # "mesh" / "logical" / "" (local)
    lits: tuple             # integer literals in the invars (slot indices)

    def __str__(self) -> str:
        where = "remote" if self.remote else "local"
        return (f"{self.kind}[{where}] at {self.path} "
                f"bufs={list(self.bufs)} sems={list(self.sems)}")


class _ScheduleExtractor(JaxprWalker):
    """Threads kernel-invar identity (position tokens) through the
    kernel body so each DMA/semaphore equation's refs resolve to names.
    Refs pass through cond-branch seeding and while carries positionally
    (the base walker's descent), so their lattice values stay singleton
    tokens; the barrier semaphore is the one ref born inside the kernel
    and gets its own token at the ``get_barrier_semaphore`` site."""

    def __init__(self, names: tuple):
        super().__init__()
        self.names = names
        self.ops: dict = {}  # (path, idx) -> ExtractedOp

    def pallas_kernel_env(self, body, eqn) -> dict:
        env = {}
        for i, v in enumerate(body.invars):
            env[v] = frozenset({i})
        for v in body.constvars:
            env[v] = frozenset()
        return env

    def transfer(self, eqn, in_vals, site):
        if eqn.primitive.name == "get_barrier_semaphore":
            return [frozenset({"barrier"}) for _ in eqn.outvars]
        return super().transfer(eqn, in_vals, site)

    def _name(self, val) -> str:
        if isinstance(val, frozenset) and len(val) == 1:
            tok = next(iter(val))
            if tok == "barrier":
                return "barrier"
            if isinstance(tok, int) and tok < len(self.names):
                return self.names[tok]
        return "?"

    def visit(self, eqn, in_vals, out_vals, site: EqnSite) -> None:
        if eqn.primitive.name not in SCHED_PRIMS:
            return
        if not any(p.startswith("pallas_call#") for p in site.path):
            return
        key = (site.path, site.index)
        if key in self.ops:
            return  # fixpoint sweeps revisit loop bodies
        from jax.extend import core as jex_core

        bufs, sems, lits = [], [], []
        for atom, val in zip(eqn.invars, in_vals):
            aval_s = str(getattr(atom, "aval", ""))
            if aval_s.startswith("Ref"):  # Ref<any>{...}, Ref<semaphore_mem>{...}
                (sems if "sem" in aval_s else bufs).append(self._name(val))
            elif isinstance(atom, jex_core.Literal):
                try:
                    lits.append(int(atom.val))
                except (TypeError, ValueError):
                    pass
        tree = eqn.params.get("tree", eqn.params.get("args_tree", ""))
        remote = "(*, *)" in str(tree)
        dit = str(eqn.params.get("device_id_type", "")).lower()
        dit = ("mesh" if "mesh" in dit
               else "logical" if "logical" in dit else "")
        self.ops[key] = ExtractedOp(
            kind=eqn.primitive.name, path=str(site),
            bufs=tuple(bufs), sems=tuple(sems), remote=remote,
            device_id_type=dit, lits=tuple(lits),
        )


def extract_fused_schedule(*, quantized: bool = False) -> list[ExtractedOp]:
    """Trace ``fused_ring_remote`` under ``shard_map`` on the full-device
    CPU ring (the contract trace) and resolve every in-kernel
    DMA/semaphore equation to named buffers and semaphores.  Needs the
    simulated multi-device backend
    (``--xla_force_host_platform_device_count``); make_jaxpr only."""
    from . import contracts

    jaxpr, _ = contracts.trace_fused_ring(quantized=quantized)
    ex = _ScheduleExtractor(_REF_NAMES_Q8 if quantized else _REF_NAMES_PLAIN)
    ex.run(jaxpr)
    return [ex.ops[k] for k in sorted(ex.ops)]


# ---------------------------------------------------------------------------
# Layer 2: cross-check against the declared PROTOCOL
# ---------------------------------------------------------------------------


def _row_signatures(row) -> list[tuple]:
    """The (kind, bufs, sems, remote) equation signatures a row accounts
    for — what the extractor's resolved ops are matched against."""
    op = row["op"]
    if op == "copy":
        sig = ((row["src"], row["dst"]), (row["sem"],), False)
        return [("dma_start",) + sig, ("dma_wait",) + sig]
    if op == "remote_copy":
        bufs = (row["src"], row["dst"])
        return [  # the lowered equation does not pin sem operand order
            ("dma_start", bufs, (row["send_sem"], row["recv_sem"]), True),
            ("dma_start", bufs, (row["recv_sem"], row["send_sem"]), True),
        ]
    if op == "remote_drain":
        bufs = ("kvbuf", "kvbuf")
        return [  # each drained descriptor waits send AND recv; the
                  # waited semaphore leads the equation's sem operands
            ("dma_wait", bufs, (row["send_sem"], row["recv_sem"]), True),
            ("dma_wait", bufs, (row["recv_sem"], row["send_sem"]), True),
        ]
    if op == "barrier":
        return [
            ("get_barrier_semaphore", (), (), False),
            ("semaphore_signal", (), (row["sem"],), True),
            ("semaphore_wait", (), (row["sem"],), False),
        ]
    if op == "sem_signal":
        return [("semaphore_signal", (), (row["sem"],), True)]
    if op == "sem_wait":
        return [("semaphore_wait", (), (row["sem"],), False)]
    raise ValueError(f"unknown protocol op {op!r}")


def crosscheck_protocol(ops: list, protocol=None,
                        label: str = "fused_ring") -> list[str]:
    """Hold the extracted equations to the declared table: every op must
    match a row's signature, every row's per-kind site count must match
    what the trace contains, and every remote op must address by MESH
    coordinates.  One-line violations, empty = the trace IS the table."""
    protocol = _protocol() if protocol is None else protocol
    sig2row = {}
    for row in protocol:
        for sig in _row_signatures(row):
            sig2row[sig] = row["row"]
    observed: Counter = Counter()
    out: list[str] = []
    for op in ops:
        if op.remote and op.device_id_type != "mesh":
            out.append(
                f"{label}: {op.kind} at {op.path} uses "
                f"DeviceIdType.{op.device_id_type.upper() or '?'} — remote "
                f"DMA/semaphore ops must address by per-axis MESH "
                f"coordinates (a ring-rank LOGICAL id targets the wrong "
                f"replica group on multi-axis meshes) [rule: dma-device-id]"
            )
        row = sig2row.get((op.kind, op.bufs, op.sems, op.remote))
        if row is None:
            out.append(
                f"{label}: {op} matches no PROTOCOL row — undeclared "
                f"DMA/semaphore site [rule: protocol-coverage]"
            )
            continue
        observed[(row, op.kind)] += 1
    for row in protocol:
        for kind, want in row["sites"].items():
            got = observed.pop((row["row"], kind), 0)
            if got != want:
                out.append(
                    f"{label}: protocol row {row['row']}: {kind} x{got} in "
                    f"the traced kernel, table declares {want} "
                    f"[rule: protocol-sites]"
                )
    for (row, kind), got in sorted(observed.items()):
        out.append(
            f"{label}: protocol row {row}: {kind} x{got} traced beyond the "
            f"declared sites [rule: protocol-sites]"
        )
    return out


def derived_fused_counts(protocol=None) -> dict[str, int]:
    """The fused contract's expected primitive counts, derived from the
    PROTOCOL table's ``sites`` fields (plus the zero-ppermute pin) — the
    hand-pinned numbers this replaces can no longer drift from the
    verified schedule."""
    protocol = _protocol() if protocol is None else protocol
    counts = {k: 0 for k in SCHED_PRIMS}
    for row in protocol:
        for kind, n in row["sites"].items():
            counts[kind] += n
    counts["ppermute"] = 0
    return counts


# ---------------------------------------------------------------------------
# Layer 3: the N-device model check
# ---------------------------------------------------------------------------


@dataclass
class _Ev:
    """One schedule event.  ``at`` is the device whose semaphore
    instance / buffer the event touches (== ``dev`` except for remote
    signals and DMA landings); ``frm`` names a write's sender."""

    i: int
    dev: tuple
    hop: int
    kind: str           # "sig" | "wait" | "start" | "write" | "read" | "local"
    sem: str = ""
    at: tuple = ()
    inc: int = 0
    need: int = 0
    slot: int = -1
    row: str = ""
    frm: tuple = ()


@dataclass
class _Schedule:
    ring: int
    groups: int
    hops: int
    evs: list = field(default_factory=list)
    po: dict = field(default_factory=dict)      # dev -> [event ids]
    edges: list = field(default_factory=list)   # async HB edges (a, b)
    spawn: dict = field(default_factory=dict)   # start id -> [async ids]
    reads: list = field(default_factory=list)   # (dev, slot, begin, end, hop, row)
    static: list = field(default_factory=list)  # expansion-time violations

    @property
    def tag(self) -> str:
        return (f"ring={self.ring}" if self.groups == 1
                else f"ring={self.ring}x{self.groups}")

    def dev_str(self, dev: tuple) -> str:
        return str(dev[1]) if self.groups == 1 else f"{dev[0]}.{dev[1]}"


def _guard(expr: str, hop: int, hops: int) -> bool:
    return bool(eval(expr, {"__builtins__": {}}, {"hop": hop, "hops": hops}))


def _slot(expr: str, hop: int, hops: int) -> int:
    return int(eval(expr, {"__builtins__": {}}, {"hop": hop, "hops": hops}))


def _expand(protocol, ring: int, groups: int = 1) -> _Schedule:
    """Per-device, per-hop event lists from the protocol table, in table
    (== kernel program) order; remote copies spawn their async
    completions (send signal, landing write, recv signal) off program
    order.  Logical-id rows resolve their target the way the bug did —
    the ring-rank index linearized over the WHOLE mesh — and flag the
    replica-group escape statically."""
    hops = ring
    sched = _Schedule(ring=ring, groups=groups, hops=hops)
    flagged = set()

    def add(dev, hop, kind, **kw):
        ev = _Ev(len(sched.evs), dev, hop, kind, **kw)
        sched.evs.append(ev)
        return ev

    def target(dev, to, row, hop):
        g, r = dev
        delta = -1 if to == "left" else 1
        mesh_t = (g, (r + delta) % ring)
        if row.get("addressing", "mesh") != "logical":
            return mesh_t
        flat = (r + delta) % ring  # ring-rank id over the FULL mesh
        logical_t = (flat // ring, flat % ring)
        if logical_t != mesh_t and (row["row"], dev) not in flagged:
            flagged.add((row["row"], dev))
            sched.static.append(
                f"{sched.tag}: hop {hop} {row['row']}: push from device "
                f"{sched.dev_str(dev)} addresses logical ring-rank id "
                f"{flat} = device {sched.dev_str(logical_t)} — outside its "
                f"replica group (per-axis MESH coordinates required) "
                f"[rule: dma-device-id]"
            )
        return logical_t

    for g in range(groups):
        for r in range(ring):
            dev = (g, r)
            order = sched.po.setdefault(dev, [])
            for hop in range(hops):
                for row in protocol:
                    if not _guard(row["guard"], hop, hops):
                        continue
                    op, rid = row["op"], row["row"]
                    if op == "copy":
                        if row.get("src") == "kvbuf" and row["src_slot"]:
                            ev = add(dev, hop, "read", at=dev, row=rid,
                                     slot=_slot(row["src_slot"], hop, hops))
                            order.append(ev.i)
                            sched.reads.append(
                                (dev, ev.slot, ev.i, ev.i, hop, rid))
                        elif row.get("dst") == "kvbuf" and row["dst_slot"]:
                            ev = add(dev, hop, "write", at=dev, frm=dev,
                                     row=rid,
                                     slot=_slot(row["dst_slot"], hop, hops))
                            order.append(ev.i)
                        else:
                            order.append(add(dev, hop, "local", row=rid).i)
                    elif op == "remote_copy":
                        tgt = target(dev, row["to"], row, hop)
                        s = add(dev, hop, "start", row=rid)
                        order.append(s.i)
                        snd = add(dev, hop, "sig", sem=row["send_sem"],
                                  at=dev, inc=1, row=rid)
                        wrt = add(dev, hop, "write", at=tgt, frm=dev,
                                  row=rid,
                                  slot=_slot(row["dst_slot"], hop, hops))
                        rcv = add(dev, hop, "sig", sem=row["recv_sem"],
                                  at=tgt, inc=1, row=rid)
                        sched.spawn[s.i] = [snd.i, wrt.i, rcv.i]
                        sched.edges += [(s.i, snd.i), (s.i, wrt.i),
                                        (wrt.i, rcv.i)]
                        # the outbound copy READS the source slot until
                        # the send semaphore fires
                        sched.reads.append(
                            (dev, _slot(row["src_slot"], hop, hops),
                             s.i, snd.i, hop, rid))
                    elif op == "remote_drain":
                        for sem in (row["send_sem"], row["recv_sem"]):
                            ev = add(dev, hop, "wait", sem=sem, at=dev,
                                     need=1, row=rid)
                            order.append(ev.i)
                    elif op == "barrier":
                        for to in row["signal_to"]:
                            tgt = target(dev, to, row, hop)
                            order.append(add(dev, hop, "sig", sem=row["sem"],
                                             at=tgt, inc=row["inc"],
                                             row=rid).i)
                        order.append(add(dev, hop, "wait", sem=row["sem"],
                                         at=dev, need=row["value"],
                                         row=rid).i)
                    elif op == "sem_signal":
                        tgt = target(dev, row["to"], row, hop)
                        order.append(add(dev, hop, "sig", sem=row["sem"],
                                         at=tgt, inc=row["inc"], row=rid).i)
                    elif op == "sem_wait":
                        order.append(add(dev, hop, "wait", sem=row["sem"],
                                         at=dev, need=row["value"],
                                         row=rid).i)
    return sched


def _check_matched(sched: _Schedule) -> list[str]:
    """Every dma_start has a matching wait on both ends: per semaphore
    instance, total signaled == total waited (send side on the sender,
    recv side on the landing device, grant/barrier handshakes even)."""
    inc: Counter = Counter()
    need: Counter = Counter()
    for e in sched.evs:
        if e.kind == "sig":
            inc[(e.at, e.sem)] += e.inc
        elif e.kind == "wait":
            need[(e.at, e.sem)] += e.need
    out = []
    for dev, sem in sorted(set(inc) | set(need)):
        a, b = inc[(dev, sem)], need[(dev, sem)]
        if a != b:
            out.append(
                f"{sched.tag}: {sem} on device {sched.dev_str(dev)}: {a} "
                f"signal(s) against {b} wait(s) — every dma_start/signal "
                f"needs a matching wait on both ends "
                f"[rule: dma-matched-wait]"
            )
    return out


def _simulate(sched: _Schedule) -> list[str]:
    """Greedy maximal execution: deadlock freedom (if the eager schedule
    completes, every fair schedule does — signals only produce, waits
    only consume, no shared-token conflicts) plus end-state semaphore
    drain."""
    sem: Counter = Counter()
    ptr = {d: 0 for d in sched.po}

    def fire(ev):
        if ev.kind == "sig":
            sem[(ev.at, ev.sem)] += ev.inc
        for a in sched.spawn.get(ev.i, ()):
            fire(sched.evs[a])  # eager async completion

    progress = True
    while progress:
        progress = False
        for dev, order in sched.po.items():
            while ptr[dev] < len(order):
                ev = sched.evs[order[ptr[dev]]]
                if ev.kind == "wait":
                    if sem[(ev.at, ev.sem)] < ev.need:
                        break
                    sem[(ev.at, ev.sem)] -= ev.need
                fire(ev)
                ptr[dev] += 1
                progress = True
    out = []
    stuck = {d: o[ptr[d]] for d, o in sched.po.items() if ptr[d] < len(o)}
    for dev in sorted(stuck):
        ev = sched.evs[stuck[dev]]
        out.append(
            f"{sched.tag}: deadlock — device {sched.dev_str(dev)} stuck at "
            f"hop {ev.hop} {ev.row} waiting {ev.sem} (have "
            f"{sem[(ev.at, ev.sem)]}, need {ev.need}) [rule: ring-deadlock]"
        )
    if not stuck:
        for (dev, s), c in sorted(sem.items()):
            if c:
                out.append(
                    f"{sched.tag}: semaphore {s} on device "
                    f"{sched.dev_str(dev)} drains to {c}, not 0 — "
                    f"unconsumed signal at schedule end "
                    f"[rule: semaphore-drain]"
                )
    return out


def _closure(n: int, succ: list) -> list | None:
    """Transitive-closure bitmasks over a DAG (None on a cycle)."""
    indeg = [0] * n
    for v in range(n):
        for u in succ[v]:
            indeg[u] += 1
    order, head = [v for v in range(n) if not indeg[v]], 0
    while head < len(order):
        v = order[head]
        head += 1
        for u in succ[v]:
            indeg[u] -= 1
            if not indeg[u]:
                order.append(u)
    if len(order) < n:
        return None
    reach = [0] * n
    for v in reversed(order):
        m = 1 << v
        for u in succ[v]:
            m |= reach[u]
        reach[v] = m
    return reach


def _happens_before(sched: _Schedule):
    """The guaranteed happens-before relation: program order + DMA
    spawn/landing edges, plus signal->wait edges iterated to a fixpoint
    (an edge exists iff the wait cannot complete in any execution
    without that signal).  Returns (reach bitmasks, cycle flag)."""
    n = len(sched.evs)
    succ = [[] for _ in range(n)]
    for order in sched.po.values():
        for a, b in zip(order, order[1:]):
            succ[a].append(b)
    for a, b in sched.edges:
        succ[a].append(b)

    waits_by = defaultdict(list)
    sigs_by = defaultdict(list)
    for order in sched.po.values():
        for i in order:
            ev = sched.evs[i]
            if ev.kind == "wait":
                waits_by[(ev.at, ev.sem)].append(ev)
    for ev in sched.evs:
        if ev.kind == "sig":
            sigs_by[(ev.at, ev.sem)].append(ev)

    have = set()
    while True:
        reach = _closure(n, succ)
        if reach is None:
            return None, True
        added = False
        for key, waits in waits_by.items():
            sigs = sigs_by.get(key, ())
            cum = 0
            for w in waits:
                cum += w.need
                for s in sigs:
                    if (s.i, w.i) in have:
                        continue
                    avail = sum(
                        s2.inc for s2 in sigs
                        if s2.i != s.i and not (reach[w.i] >> s2.i) & 1
                    )
                    if avail < cum:
                        succ[s.i].append(w.i)
                        have.add((s.i, w.i))
                        added = True
        if not added:
            return reach, False


def _check_races(sched: _Schedule) -> list[str]:
    """No kvbuf slot is written while a concurrent reader holds it: for
    every (write, read-interval) and (write, write) pair on the same
    device and slot, the guaranteed happens-before graph must order one
    side fully before the other."""
    reach, cyclic = _happens_before(sched)
    if cyclic:
        return [f"{sched.tag}: happens-before graph is cyclic — the "
                f"wait-for relation cannot be acyclic [rule: ring-deadlock]"]
    out = []
    writes = [e for e in sched.evs if e.kind == "write"]
    before = lambda a, b: bool((reach[a] >> b) & 1)
    for w in writes:
        for dev, slot, begin, end, hop, rid in sched.reads:
            if dev != w.at or slot != w.slot or w.i in (begin, end):
                continue
            if not (before(w.i, begin) or before(end, w.i)):
                out.append(
                    f"{sched.tag}: kvbuf slot {slot} on device "
                    f"{sched.dev_str(dev)} written at hop {w.hop} (push "
                    f"from device {sched.dev_str(w.frm)}) while the "
                    f"hop-{hop} {rid} read holds it — no happens-before "
                    f"edge orders them [rule: slot-overwrite-race]"
                )
        for w2 in writes:
            if (w2.i <= w.i or w2.at != w.at or w2.slot != w.slot):
                continue
            if not (before(w.i, w2.i) or before(w2.i, w.i)):
                out.append(
                    f"{sched.tag}: kvbuf slot {w.slot} on device "
                    f"{sched.dev_str(w.at)} written concurrently at hops "
                    f"{w.hop} and {w2.hop} (from devices "
                    f"{sched.dev_str(w.frm)}, {sched.dev_str(w2.frm)}) "
                    f"[rule: slot-overwrite-race]"
                )
    return out


def verify_ring(protocol=None, *, ring: int, groups: int = 1) -> list[str]:
    """Model-check one composed schedule; one-line violations."""
    protocol = _protocol() if protocol is None else protocol
    sched = _expand(protocol, ring, groups)
    out = list(sched.static)
    out += _check_matched(sched)
    out += _simulate(sched)
    out += _check_races(sched)
    return list(dict.fromkeys(out))


def verify_protocol(protocol=None, *, rings=VERIFY_RINGS,
                    mesh_groups: int = MESH_GROUPS) -> list[str]:
    """The full proof: every ring size on the bare ring AND on a
    ``mesh_groups``-wide multi-axis mesh (replica-group isolation of the
    MESH addressing).  Empty = grant balance, no overwrite-before-read,
    semaphore drain, and deadlock freedom all hold."""
    protocol = _protocol() if protocol is None else protocol
    out: list[str] = []
    for ring in rings:
        out += verify_ring(protocol, ring=ring, groups=1)
        out += verify_ring(protocol, ring=ring, groups=mesh_groups)
    return list(dict.fromkeys(out))


# ---------------------------------------------------------------------------
# The PR-18 review bugs, kept alive as protocol variants
# ---------------------------------------------------------------------------


def grantless_protocol():
    """Review bug #1: the push WITHOUT the receiver->sender grant.  A
    one-hop compute skew (guaranteed under causal works schedules) lets
    hop i+1's incoming DMA overwrite the slot hop i is still reading —
    the verifier reports the overwrite race at every ring size >= 3."""
    return tuple(r for r in _protocol()
                 if r["row"] not in ("push-grant", "grant"))


def logical_id_protocol():
    """Review bug #2: the push addressed by ring-rank LOGICAL device id.
    Correct on a bare ring (group 0 IS the mesh), wrong the moment the
    mesh grows a second axis: every replica outside group 0 pushes its
    KV into group 0's buffers — the verifier reports the replica-group
    escape, the orphaned recv waits, and the resulting deadlock."""
    return tuple(
        {**r, "addressing": "logical"} if r["row"] == "push-kv" else r
        for r in _protocol()
    )


# ---------------------------------------------------------------------------
# Suite / fingerprint entry points
# ---------------------------------------------------------------------------


def run_schedverify_suite(*, feeds=(False, True)) -> list[tuple[str, list]]:
    """The full verifier, house-suite shaped (``(name, violations)``
    rows): the N-device model check over rings 2..8 (bare + 2-group
    mesh), then the jaxpr extraction cross-check for the plain and q8
    feeds.  Extraction needs the simulated multi-device backend; the
    model check is pure python."""
    checks: list[tuple[str, list]] = [(
        f"schedverify: protocol model (rings "
        f"{VERIFY_RINGS[0]}-{VERIFY_RINGS[-1]}, mesh x{MESH_GROUPS})",
        verify_protocol(),
    )]
    for quantized in feeds:
        feed = "q8" if quantized else "plain"
        label = f"fused_ring_{feed}" if quantized else "fused_ring"
        ops = extract_fused_schedule(quantized=quantized)
        checks.append((
            f"schedverify: jaxpr extraction ({feed}, {len(ops)} ops)",
            crosscheck_protocol(ops, label=label),
        ))
    return checks


def protocol_fingerprint() -> dict:
    """The protocol's signature: derived primitive counts, table
    size, per-ring model event counts, total violations (0 on a healthy
    tree), and per-feed extracted-op counts.  Deterministic — any edit
    to the kernel's hop schedule or the PROTOCOL table moves it."""
    protocol = _protocol()
    fp: dict = {
        "counts": derived_fused_counts(protocol),
        "rows": len(protocol),
        "rings": {},
        "violations": 0,
    }
    for ring in VERIFY_RINGS:
        sched = _expand(protocol, ring, 1)
        fp["rings"][f"ring{ring}"] = len(sched.evs)
    for name, violations in run_schedverify_suite():
        fp["violations"] += len(violations)
    for quantized in (False, True):
        ops = extract_fused_schedule(quantized=quantized)
        fp["q8_ops" if quantized else "plain_ops"] = len(ops)
    return fp
