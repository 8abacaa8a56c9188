"""Declarative collective/HLO contracts for every sequence-parallel entry.

Ring attention's value proposition IS a communication contract: exactly
``ring - 1`` collective-permutes per forward (Liu et al.; Striped Attention
changes only the permutation, not the count), ``2*ring - 1`` in backward
(the kv counter-rotation — ``ring - 1`` after XLA drops the unused final
rotate — plus the full ``ring``-hop dkv circulation back to its owner), and
the hybrid factoring must cut the hop count by the Ulysses degree while
adding exactly two all-to-alls per tensor leg.  Before this module those
invariants lived as scattered one-off HLO pins; here they are ONE
declarative table (:data:`CONTRACTS`), verified two ways for every strategy
x mesh shape:

  - **optimized HLO** (the hot path: unrolled Pallas hop loop, or the XLA
    path for gather/all-to-all strategies): exact instruction counts per
    collective kind, source/target-pair and replica-group *axis* checks
    (a ring permute must keep every non-ring mesh coordinate fixed), and
    the global rule that any collective kind the contract does not declare
    must not appear at all — an accidental ``all-gather`` of O(seq)
    activations fails loudly;
  - **jaxpr structure** (the scanned XLA path): collective counts with
    scan bodies multiplied by their trip count, plus the rule that no
    collective may sit inside a ``lax.cond`` branch (a data-dependent
    collective schedule deadlocks SPMD programs).

Count expressions are strings evaluated over the mesh dims
(``ring`` / ``ulysses`` / ``world`` / ``passes`` / ``data``) so the table
reads as documentation (docs/static_analysis.md renders it directly).

The contracts pin the *base* path: unsegmented, unmasked, unidirectional,
full passes — the configuration every other variant adds collectives onto.
All checks run on CPU (``--xla_force_host_platform_device_count``); the
compiled collective sequence is backend-independent at this level.
"""

from __future__ import annotations

import re
from collections import Counter
from dataclasses import dataclass, field
from typing import Any

import numpy as np

# ---------------------------------------------------------------------------
# The declarative table
# ---------------------------------------------------------------------------

CONTRACTS: dict[str, dict[str, Any]] = {
    "ring": {
        "description": "KV rotation: one ppermute per hop, nothing else",
        "impl": "pallas",
        "mesh": "plain",
        "axes": {"collective-permute": "seq"},
        "hlo": {
            "fwd": {"collective-permute": "ring - 1"},
            "fwdbwd": {"collective-permute": "3 * ring - 2"},
        },
        "scan": {
            "fwd": {"ppermute": "passes"},
            "fwdbwd": {"ppermute": "3 * passes"},
        },
    },
    "striped": {
        "description": "balanced-causal ring: permutation changes, count "
                       "does not (Striped Attention, arXiv 2311.09431)",
        "impl": "pallas",
        "mesh": "plain",
        "striped": True,
        "axes": {"collective-permute": "seq"},
        "hlo": {
            "fwd": {"collective-permute": "ring - 1"},
            "fwdbwd": {"collective-permute": "3 * ring - 2"},
        },
        "scan": {
            "fwd": {"ppermute": "passes"},
            "fwdbwd": {"ppermute": "3 * passes"},
        },
    },
    "counter": {
        "description": "TokenRing counter-rotation (arXiv 2412.20501): the "
                       "Q+(acc,m,l) pack rotates one ring direction while "
                       "KV rotates the other (permute pairs in BOTH "
                       "directions); backward circulates only the q-side "
                       "pack with KV/dKV resident — fwd pays one extra "
                       "collective (the out/lse catch-up) and the backward "
                       "repays it: 2*ring per step vs the baseline 3*ring-2",
        "impl": "pallas",
        "mesh": "plain",
        "ring_kwargs": {"counter_rotate": True},
        "both_directions": True,
        "axes": {"collective-permute": "seq"},
        "hlo": {
            "fwd": {"collective-permute": "ring"},
            "fwdbwd": {"collective-permute": "2 * ring"},
        },
        "scan": {
            # the single-lax.scan body covers two hops (one Q-rotation,
            # one KV-rotation) + the out/lse catch-up; backward is one
            # uniform ppermute per hop, landing home at full circulation
            "fwd": {"ppermute": "2 * (passes // 2) + 1"},
            "fwdbwd": {"ppermute": "2 * (passes // 2) + 1 + passes"},
        },
    },
    "ring_compressed": {
        "description": "int8-compressed KV hops: per-token absmax values + "
                       "bitcast f32 scales in ONE payload — hop count "
                       "identical to the ring contract, bytes/hop "
                       "(d+4)/(4d) of the f32 ring's",
        "impl": "pallas",
        "mesh": "plain",
        "ring_kwargs": {"hop_compression": "int8"},
        "axes": {"collective-permute": "seq"},
        "hlo": {
            "fwd": {"collective-permute": "ring - 1"},
            "fwdbwd": {"collective-permute": "3 * ring - 2"},
        },
        "scan": {
            "fwd": {"ppermute": "passes"},
            "fwdbwd": {"ppermute": "3 * passes"},
        },
        "hop_bytes": {
            # every forward rotation moves the (2, b, hk, chunk, d+4) int8
            # handle; backward recirculates exact kv + f32 dkv (its own
            # larger payloads), so the pin is forward-only
            "fwd": {
                "min": "2 * b * kv_heads * chunk * (dim_head + 4)",
                "max": "2 * b * kv_heads * chunk * (dim_head + 4)",
            },
        },
    },
    "counter_compressed": {
        "description": "counter-rotation with int8 KV hops: counts match "
                       "the counter contract exactly; the smallest "
                       "circulating payload is the compressed KV handle",
        "impl": "pallas",
        "mesh": "plain",
        "ring_kwargs": {"counter_rotate": True, "hop_compression": "int8"},
        "both_directions": True,
        "axes": {"collective-permute": "seq"},
        "hlo": {
            "fwd": {"collective-permute": "ring"},
            "fwdbwd": {"collective-permute": "2 * ring"},
        },
        "scan": {
            "fwd": {"ppermute": "2 * (passes // 2) + 1"},
            "fwdbwd": {"ppermute": "2 * (passes // 2) + 1 + passes"},
        },
        "hop_bytes": {
            "fwd": {
                "min": "2 * b * kv_heads * chunk * (dim_head + 4)",
                "max": "4 * b * heads * chunk * (2 * dim_head + 2)",
            },
        },
    },
    "counter_q8": {
        "description": "counter-rotation with int8 hops feeding the int8 "
                       "COMPUTE kernels directly (PR 13, dequant-free "
                       "composition, docs/precision.md): the collective "
                       "schedule is IDENTICAL to counter_compressed — the "
                       "quantized matmuls change what the kernels read, "
                       "never what the ring moves — and the payload still "
                       "circulates as one int8 array per hop",
        "impl": "pallas",
        "mesh": "plain",
        "ring_kwargs": {"counter_rotate": True, "hop_compression": "int8",
                        "compute_dtype": "int8"},
        "both_directions": True,
        "axes": {"collective-permute": "seq"},
        "hlo": {
            "fwd": {"collective-permute": "ring"},
            "fwdbwd": {"collective-permute": "2 * ring"},
        },
        "hop_bytes": {
            "fwd": {
                "min": "2 * b * kv_heads * chunk * (dim_head + 4)",
                "max": "4 * b * heads * chunk * (2 * dim_head + 2)",
            },
        },
    },
    "zigzag": {
        "description": "Llama-3 CP: gather K and V once; grads flow back "
                       "through the gather transpose (reduce-scatter)",
        "impl": "xla",
        "mesh": "plain",
        "axes": {"all-gather": "seq", "reduce-scatter": "seq"},
        "hlo": {
            "fwd": {"all-gather": "2"},
            "fwdbwd": {"all-gather": "2", "reduce-scatter": "2"},
        },
    },
    "ulysses": {
        "description": "head-parallel: two all-to-alls per tensor leg "
                       "(q/k/v in, out back; bwd transposes combine to 3)",
        "impl": "xla",
        "mesh": "plain",
        "axes": {"all-to-all": "seq"},
        "hlo": {
            "fwd": {"all-to-all": "4"},
            "fwdbwd": {"all-to-all": "7"},
        },
    },
    "ulysses_gqa": {
        "description": "small-hk GQA: real kv heads ship ONCE (all-gather) "
                       "and expand locally — never world/gcd repeated "
                       "all-to-all copies",
        "impl": "xla",
        "mesh": "plain",
        "kv_heads": 2,
        "directions": ("fwd",),
        "axes": {"all-to-all": "seq", "all-gather": "seq"},
        "hlo": {
            "fwd": {"all-to-all": "2", "all-gather": "2"},
        },
    },
    "hybrid": {
        "description": "Ulysses x Ring factoring: all-to-alls on the inner "
                       "axis only, ppermutes on the outer axis only, "
                       "ulysses-x fewer hops than a pure ring at equal world",
        "impl": "pallas",
        "mesh": "factored",
        "axes": {
            "collective-permute": "ring",
            "all-to-all": "ulysses",
            "all-gather": "ulysses",
        },
        "hlo": {
            "fwd": {"all-to-all": "4", "collective-permute": "ring - 1"},
            "fwdbwd": {"all-to-all": "7", "collective-permute": "3 * ring - 2"},
        },
        "scan": {
            "fwd": {"ppermute": "passes", "all_to_all": "4"},
            "fwdbwd": {"ppermute": "3 * passes", "all_to_all": "8"},
        },
    },
    "blockwise_ffn": {
        "description": "chunked feedforward (Ring Attention's blockwise "
                       "FFN, arXiv 2310.01889): chunks split WITHIN each "
                       "sequence shard, so the rematted scan adds ZERO "
                       "collectives — forward has none at all, backward "
                       "has exactly the dense FFN's two weight-grad "
                       "all-reduces",
        "impl": "xla",
        "mesh": "plain",
        "axes": {},
        "hlo": {
            "fwd": {},
            "fwdbwd": {"all-reduce": "2"},
        },
    },
    "tree_decode": {
        "description": "tree-attention decode merge: pmax + two psums, "
                       "nothing touches the O(seq) cache shards",
        "impl": "xla",
        "mesh": "plain",
        "directions": ("fwd",),
        "axes": {"all-reduce": "seq"},
        "hlo": {
            "fwd": {"all-reduce": "3"},
        },
        "scan": {
            "fwd": {"pmax": "1", "psum": "2"},
        },
    },
}

# Collective kinds tracked in optimized HLO.  Any kind present in a
# program but absent from its contract's expectation dict is a violation
# (the "no undeclared collective in the hot path" rule).
HLO_COLLECTIVE_KINDS = (
    "all-gather",
    "all-to-all",
    "collective-permute",
    "all-reduce",
    "reduce-scatter",
    "collective-broadcast",
)

# jaxpr-level collective primitive names (the traced contract).
JAXPR_COLLECTIVE_PRIMS = {
    "ppermute",
    "all_to_all",
    "all_gather",
    "all_gather_invariant",
    "psum",
    "psum_invariant",
    "pmax",
    "pmin",
    "reduce_scatter",
    "psum_scatter",
}

# One collective instruction line of optimized HLO, keyed on the OPCODE
# (``%ppermute.3 = f32[2,4]{1,0} collective-permute(%param.1), ...``): XLA
# names instructions after the jax op that produced them, so the name
# left of ``=`` says nothing about the kind.  ``-start`` is the async
# form's issuing half; ``-done`` carries no opcode paren match here.
_HLO_COLLECTIVE_RE = re.compile(
    r"^[^=\n]* = [^\n]*?[\s)](" + "|".join(HLO_COLLECTIVE_KINDS)
    + r")(?:-start)?\([^\n]*",
    re.MULTILINE,
)


def _hlo_instructions(txt: str, kind: str) -> list[str]:
    """Full text of every ``kind`` instruction line, in program order."""
    return [m.group(0) for m in _HLO_COLLECTIVE_RE.finditer(txt)
            if m.group(1) == kind]


_PPERMUTE_PAIRS_RE = re.compile(
    r"collective-permute[^\n]*source_target_pairs=\{([0-9,{} ]*)\}"
)
_GROUPS_RE = re.compile(r"replica_groups=\{(\{[0-9, ]+\}(?:,\{[0-9, ]+\})*)\}")
# iota (v2) form some XLA builds print instead of brace lists:
#   replica_groups=[2,4]<=[8]  or  [4,2]<=[2,4]T(1,0)
_GROUPS_IOTA_RE = re.compile(
    r"replica_groups=\[(\d+),(\d+)\]<=\[([0-9,]+)\](?:T\(([0-9,]+)\))?"
)


def _parse_replica_groups(line: str) -> list[list[int]] | None | str:
    """Replica groups of one HLO instruction line: a list of groups, None
    when the instruction carries no ``replica_groups=`` attribute at all
    (scalar/degenerate form), or an error string for a format this parser
    does not recognize — callers must surface that loudly, never skip it
    (a silently unparsed group would turn the axis rule into a no-op)."""
    gm = _GROUPS_RE.search(line)
    if gm:
        return [
            [int(x) for x in g.split(",")]
            for g in re.findall(r"\{([0-9, ]+)\}", gm.group(1))
        ]
    im = _GROUPS_IOTA_RE.search(line)
    if im:
        ng, gs = int(im.group(1)), int(im.group(2))
        dims = [int(x) for x in im.group(3).split(",")]
        ids = np.arange(int(np.prod(dims))).reshape(dims)
        if im.group(4):
            ids = ids.transpose([int(x) for x in im.group(4).split(",")])
        return ids.reshape(ng, gs).tolist()
    if "replica_groups=" in line:
        return line.split("replica_groups=", 1)[1][:40]
    return None


# ---------------------------------------------------------------------------
# HLO-side helpers (shared with the test-suite pins)
# ---------------------------------------------------------------------------


def _arrays_moved(m: re.Match) -> int:
    """Arrays one collective instruction (a :data:`_HLO_COLLECTIVE_RE`
    match) moves: the top-level operands in the parentheses after its
    opcode.  XLA's combiners fold neighbouring all-reduces (and the like)
    into ONE tuple-shaped instruction — ``(f32[..], f32[..])
    all-reduce(%a, %b)`` — and which neighbours it folds moves with the
    XLA version; the program asked for one collective per array, so that
    is what the contracts count.  An ``all-to-all`` is the exception: its
    tuple form is ONE array handed over as a piece per peer."""
    if m.group(1) == "all-to-all":
        return 1
    depth, n = 0, 1
    for ch in m.group(0)[m.end(1) - m.start(0):]:
        if ch in "([{":
            depth += 1
        elif ch in ")]}":
            depth -= 1
            if depth == 0:
                break
        elif ch == "," and depth == 1:
            n += 1
    return n


def hlo_collective_sequence(txt: str) -> list[str]:
    """Collective kinds in program order, one entry per array moved (see
    :func:`_arrays_moved`) — the telemetry pin's signature: an
    instrumented program must issue the same sequence as its base."""
    return [
        m.group(1)
        for m in _HLO_COLLECTIVE_RE.finditer(txt)
        for _ in range(_arrays_moved(m))
    ]


def hlo_collective_counts(txt: str) -> dict[str, int]:
    """Collectives per kind in optimized HLO text, one per array moved."""
    return dict(Counter(hlo_collective_sequence(txt)))


def hlo_ppermute_pairs(txt: str) -> list[list[tuple[int, int]]]:
    """Per-instruction ``source_target_pairs`` of every collective-permute."""
    return [
        [(int(a), int(b)) for a, b in re.findall(r"\{(\d+),(\d+)\}", m.group(1))]
        for m in _PPERMUTE_PAIRS_RE.finditer(txt)
    ]


def _device_coords(device_id: int, mesh_shape: tuple[int, ...]) -> tuple[int, ...]:
    return tuple(int(c) for c in np.unravel_index(device_id, mesh_shape))


def check_pairs_axis(
    pairs: list[list[tuple[int, int]]],
    mesh_shape: tuple[int, ...],
    axis_index: int,
    axis_name: str,
) -> list[str]:
    """Every source->target pair must change ONLY the given mesh axis."""
    out = []
    for inst, ps in enumerate(pairs):
        if not ps:
            out.append(
                f"collective-permute #{inst}: empty source_target_pairs "
                f"[rule: {axis_name}-axis-only]"
            )
        for s, t in ps:
            cs, ct = _device_coords(s, mesh_shape), _device_coords(t, mesh_shape)
            fixed_ok = all(
                cs[i] == ct[i] for i in range(len(mesh_shape)) if i != axis_index
            )
            if not fixed_ok or s == t:
                out.append(
                    f"collective-permute #{inst}: pair {s}->{t} leaves the "
                    f"{axis_name} axis (coords {cs}->{ct}) "
                    f"[rule: {axis_name}-axis-only]"
                )
    return out


def check_groups_axis(
    txt: str,
    kind: str,
    mesh_shape: tuple[int, ...],
    axis_index: int,
    axis_name: str,
) -> list[str]:
    """Replica groups of ``kind`` instructions must each span exactly the
    given mesh axis (all other coordinates fixed within a group)."""
    out = []
    for inst, line in enumerate(_hlo_instructions(txt, kind)):
        groups = _parse_replica_groups(line)
        if groups is None:
            continue  # scalar/degenerate form without explicit groups
        if isinstance(groups, str):
            out.append(
                f"{kind} #{inst}: unrecognized replica_groups format "
                f"{groups!r} — cannot verify the {axis_name} axis rule "
                f"[rule: {axis_name}-axis-only]"
            )
            continue
        for g in groups:
            coords = [_device_coords(d, mesh_shape) for d in g]
            for i in range(len(mesh_shape)):
                if i == axis_index:
                    continue
                if len({c[i] for c in coords}) != 1:
                    out.append(
                        f"{kind} #{inst}: group {g} spans mesh axis {i}, "
                        f"not only {axis_name} [rule: {axis_name}-axis-only]"
                    )
            if len(g) != mesh_shape[axis_index]:
                out.append(
                    f"{kind} #{inst}: group {g} does not cover the full "
                    f"{axis_name} axis (size {mesh_shape[axis_index]}) "
                    f"[rule: {axis_name}-axis-only]"
                )
    return out


# ---------------------------------------------------------------------------
# jaxpr-side helpers
# ---------------------------------------------------------------------------


@dataclass
class JaxprCollectives:
    """Scan-aware collective counts from a traced program."""

    counts: dict[str, int] = field(default_factory=dict)
    in_cond: list[str] = field(default_factory=list)  # prims under lax.cond
    in_while: list[str] = field(default_factory=list)  # prims under lax.while
    # bytes of each ppermute's payload (one entry per traced instruction,
    # NOT multiplied by scan trip counts): the backend-independent
    # bytes-per-hop signature the compression contracts pin
    ppermute_bytes: list[int] = field(default_factory=list)

    @property
    def dynamic(self) -> bool:
        """A while-loop body issues collectives: the trip count is unknown
        statically, so no count expression can verify the program."""
        return bool(self.in_while)


def _sub_jaxprs(value):
    from jax.extend import core as jex_core

    stack = [value]
    while stack:
        v = stack.pop()
        if isinstance(v, jex_core.ClosedJaxpr):
            yield v.jaxpr
        elif isinstance(v, jex_core.Jaxpr):
            yield v
        elif isinstance(v, (tuple, list)):
            stack.extend(v)


def jaxpr_collectives(closed_jaxpr) -> JaxprCollectives:
    """Walk a (closed) jaxpr counting collective primitives, multiplying
    counts inside ``lax.scan`` bodies by the trip count and recording any
    collective that sits inside a ``lax.cond`` branch (a divergent
    collective schedule — the SPMD deadlock hazard this codebase keeps
    its rotations outside conds to avoid) or a ``lax.while_loop`` body
    (trip count unknown statically — no count expression can verify the
    program, so the checkers fail it rather than undercount)."""
    res = JaxprCollectives(counts=Counter())

    def walk(jaxpr, mult: int, in_cond: bool, in_while: bool) -> None:
        for eqn in jaxpr.eqns:
            name = eqn.primitive.name
            if name in JAXPR_COLLECTIVE_PRIMS:
                # under check_vma jax binds ``psum_invariant`` /
                # ``all_gather_invariant`` for a plain ``lax.psum`` /
                # ``lax.all_gather``: the same collective by another name
                name = name.removesuffix("_invariant")
                res.counts[name] += mult
                if in_cond:
                    res.in_cond.append(name)
                if in_while:
                    res.in_while.append(name)
                if name == "ppermute":
                    aval = eqn.invars[0].aval
                    res.ppermute_bytes.append(
                        int(np.prod(aval.shape)) * aval.dtype.itemsize
                    )
            if name == "scan":
                walk(eqn.params["jaxpr"].jaxpr,
                     mult * int(eqn.params["length"]), in_cond, in_while)
            elif name == "cond":
                for br in eqn.params["branches"]:
                    walk(br.jaxpr, mult, True, in_while)
            elif name == "while":
                for key in ("body_jaxpr", "cond_jaxpr"):
                    walk(eqn.params[key].jaxpr, mult, in_cond, True)
            else:
                for v in eqn.params.values():
                    for sub in _sub_jaxprs(v):
                        walk(sub, mult, in_cond, in_while)

    walk(closed_jaxpr.jaxpr, 1, False, False)
    res.counts = dict(res.counts)
    return res


# ---------------------------------------------------------------------------
# Contract evaluation
# ---------------------------------------------------------------------------


def expected_counts(strategy: str, direction: str, dims: dict[str, int],
                    table: str = "hlo") -> dict[str, int]:
    """Evaluate the contract table's count expressions for one strategy."""
    contract = CONTRACTS[strategy]
    exprs = contract.get(table, {}).get(direction)
    if exprs is None:
        raise KeyError(f"{strategy} declares no {table!r} contract for "
                       f"{direction!r}")
    ns = dict(dims)
    return {
        kind: int(eval(expr, {"__builtins__": {}}, ns))  # noqa: S307 - table-only
        for kind, expr in exprs.items()
    }


@dataclass
class ContractReport:
    strategy: str
    direction: str
    impl: str
    mesh_shape: tuple[int, ...]
    dims: dict[str, int]
    counts: dict[str, int] = field(default_factory=dict)
    expected: dict[str, int] = field(default_factory=dict)
    jaxpr_counts: dict[str, int] = field(default_factory=dict)
    violations: list[str] = field(default_factory=list)

    @property
    def ok(self) -> bool:
        return not self.violations

    def to_json(self) -> dict:
        return {
            "strategy": self.strategy,
            "direction": self.direction,
            "impl": self.impl,
            "mesh_shape": list(self.mesh_shape),
            "dims": self.dims,
            "counts": self.counts,
            "expected": self.expected,
            "jaxpr_counts": self.jaxpr_counts,
            "ok": self.ok,
            "violations": self.violations,
        }


def _mesh_dims(mesh) -> dict[str, int]:
    from ..parallel.mesh import (
        DCN_DATA_AXIS,
        RING_AXIS,
        SEQ_AXIS,
        ULYSSES_AXIS,
        seq_world,
    )

    shape = dict(mesh.shape)
    ring = shape.get(RING_AXIS) or shape.get(SEQ_AXIS) or 1
    return {
        "data": shape.get("data", 1),
        "dcn": shape.get(DCN_DATA_AXIS, 1),
        "ring": ring,
        "ulysses": shape.get(ULYSSES_AXIS, 1),
        "world": seq_world(mesh),
        "passes": ring,
    }


def default_mesh(strategy: str):
    """The canonical CPU mesh for a strategy: all devices on the sequence
    axis (factored with ulysses=2 for hybrid)."""
    import jax

    from ..parallel.mesh import create_mesh

    n = len(jax.devices())
    if CONTRACTS[strategy].get("mesh") == "factored":
        return create_mesh(ulysses_size=2, ring_size=n // 2)
    return create_mesh(ring_size=n)


def build_entry(strategy: str, mesh, *, b: int = 1, heads: int = 8,
                seq: int = 64, dim_head: int = 8, impl: str | None = None):
    """(fn, args, dims): the strategy's functional core wrapped in
    ``compat.shard_map`` over ``mesh``, ready to lower.  ``fn`` takes
    ``(q, k, v)`` global arrays; tiny shapes — these programs exist to be
    compiled and inspected, not run."""
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ..parallel.hybrid import hybrid_attention
    from ..parallel.mesh import (
        RING_AXIS,
        SEQ_AXIS,
        ULYSSES_AXIS,
        data_partition,
        is_factored,
        seq_partition,
    )
    from ..parallel.ring import ring_flash_attention
    from ..parallel.tree_decode import tree_attn_decode
    from ..parallel.ulysses import ulysses_attention
    from ..parallel.zigzag import zigzag_attention
    from ..utils import compat

    contract = CONTRACTS[strategy]
    impl = impl or contract["impl"]
    kv_heads = contract.get("kv_heads", heads)
    striped = contract.get("striped", False)
    dims = _mesh_dims(mesh)
    # shape dims join the namespace so hop-byte expressions read like the
    # payload formulas they pin ("chunk" = the ring-leg KV block length)
    dims.update(
        b=b, heads=heads, kv_heads=kv_heads, seq=seq, dim_head=dim_head,
        chunk=seq // dims["world"] * dims["ulysses"],
    )
    if contract.get("mesh") == "factored" and not is_factored(mesh):
        raise ValueError(f"{strategy} needs a factored (data, ring, ulysses) "
                         "mesh — create_mesh(ulysses_size=...)")
    if contract.get("mesh") == "plain" and is_factored(mesh):
        raise ValueError(f"{strategy} runs on a plain (data, seq) mesh")

    rng = np.random.default_rng(0)
    # the batch must tile the full data-parallel degree (both tiers of a
    # hierarchical mesh)
    b = b * dims["data"] * dims["dcn"]

    def mk(h, n=seq):
        return jnp.asarray(rng.standard_normal((b, h, n, dim_head)),
                           jnp.float32)

    dspec = data_partition(mesh)
    spec = P(dspec, None, seq_partition(mesh), None)
    rep = P(dspec, None, None, None)
    bucket = max(seq // dims["world"] // 2, 4)

    if strategy in ("ring", "striped", "counter", "ring_compressed",
                    "counter_compressed", "counter_q8"):
        ring_kwargs = contract.get("ring_kwargs", {})

        def core(q, k, v):
            return ring_flash_attention(
                q, k, v, None, SEQ_AXIS, causal=True, striped=striped,
                bucket_size=bucket, impl=impl, **ring_kwargs,
            )
        in_specs = (spec, spec, spec)
        out_specs = spec
        args = (mk(heads), mk(kv_heads), mk(kv_heads))
    elif strategy == "zigzag":
        def core(q, k, v):
            return zigzag_attention(
                q, k, v, SEQ_AXIS, causal=True, bucket_size=bucket, impl=impl,
            )
        in_specs = (spec, spec, spec)
        out_specs = spec
        args = (mk(heads), mk(kv_heads), mk(kv_heads))
    elif strategy in ("ulysses", "ulysses_gqa"):
        def core(q, k, v):
            return ulysses_attention(
                q, k, v, SEQ_AXIS, causal=True, bucket_size=bucket, impl=impl,
            )
        in_specs = (spec, spec, spec)
        out_specs = spec
        args = (mk(heads), mk(kv_heads), mk(kv_heads))
    elif strategy == "hybrid":
        def core(q, k, v):
            return hybrid_attention(
                q, k, v, None, ULYSSES_AXIS, RING_AXIS, causal=True,
                bucket_size=bucket, impl=impl,
            )
        in_specs = (spec, spec, spec)
        out_specs = spec
        args = (mk(heads), mk(kv_heads), mk(kv_heads))
    elif strategy == "tree_decode":
        def core(q, k, v):
            return tree_attn_decode(
                q, k, v, axis_name=SEQ_AXIS, bucket_size=bucket, impl=impl,
            )
        in_specs = (rep, spec, spec)
        out_specs = rep
        args = (mk(heads, 1), mk(kv_heads), mk(kv_heads))
    elif strategy == "blockwise_ffn":
        # the one auto-sharded (GSPMD) row: the chunked FeedForward runs
        # under the partitioner like the model path does, NOT inside
        # shard_map — the contract pins what the partitioner inserts.
        # fn(x, w_in, w_out) keeps build_entry's uniform 3-arg shape so
        # _direction_fn's (0, 1, 2) grads produce the weight all-reduces.
        import jax

        from ..models.layers import FeedForward

        world = dims["world"]
        ff = FeedForward(
            dim=dim_head, mult=4, chunk_size=max(seq // world // 2, 1),
            seq_shards=world, mesh=mesh,
        )
        x = jnp.asarray(rng.standard_normal((b, seq, dim_head)), jnp.float32)
        params = ff.init(jax.random.PRNGKey(0), x)
        gamma = params["params"]["RMSNorm_0"]["gamma"]

        def ffn(x, w_in, w_out):
            p = {"params": {
                "RMSNorm_0": {"gamma": gamma},
                "Dense_0": {"kernel": w_in},
                "Dense_1": {"kernel": w_out},
            }}
            return ff.apply(p, x)

        x = jax.device_put(x, NamedSharding(
            mesh, P(dspec, seq_partition(mesh), None)
        ))
        return ffn, (
            x,
            params["params"]["Dense_0"]["kernel"],
            params["params"]["Dense_1"]["kernel"],
        ), dims
    else:
        raise KeyError(f"unknown strategy {strategy!r}; "
                       f"known: {sorted(CONTRACTS)}")

    fn = compat.shard_map(
        core, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=(impl != "pallas"),
    )
    return fn, args, dims


def _direction_fn(fn, direction: str):
    import jax

    if direction == "fwd":
        return fn
    if direction == "fwdbwd":
        def grads(q, k, v):
            return jax.grad(
                lambda q, k, v: fn(q, k, v).sum(), argnums=(0, 1, 2)
            )(q, k, v)
        return grads
    raise ValueError(f"unknown direction {direction!r}")


def _compiled_hlo(fn, args) -> str:
    """Optimized-HLO text of ``fn(*args)``, compiled with XLA's
    common-subexpression pass off.  Every collective of one program now
    carries the same ``channel_id``, so that pass folds a rotation the
    backward asks for again into the forward's identical one (the ring's
    kv counter-rotation: ``ring`` permutes left of ``2 * ring - 1``) — a
    saving one XLA version makes, not a change in what the program asks
    for, which is what the contracts hold.  Dead-code elimination and
    every other pass still run, so the formulas stay the ones of a
    compiled program (``ring - 1`` after XLA drops the unused final
    rotate)."""
    from ..utils import compat

    return compat.jit(fn).lower(*args).compile(
        compiler_options={"xla_disable_hlo_passes": "cse"}
    ).as_text()


def verify_hlo(strategy: str, direction: str, txt: str,
               dims: dict[str, int], mesh_shape: tuple[int, ...],
               axis_names: list[str]) -> list[str]:
    """Check one compiled program's optimized-HLO text against a
    strategy's contract: exact counts for every declared collective kind,
    zero for every undeclared kind, axis discipline for permute pairs and
    replica groups.  Returns one-line violations (empty = contract holds).

    This is the shared core behind :func:`check_strategy`, the test-suite
    pins, and negative-case toys — anything that can produce HLO text can
    be held to a contract.
    """
    contract = CONTRACTS[strategy]
    counts = hlo_collective_counts(txt)
    expected = expected_counts(strategy, direction, dims)
    violations: list[str] = []

    for kind in HLO_COLLECTIVE_KINDS:
        got = counts.get(kind, 0)
        want = expected.get(kind, 0)
        if got != want:
            expr = contract["hlo"][direction].get(kind, "0 (undeclared)")
            violations.append(
                f"{strategy}/{direction}: {kind} x{got}, contract says "
                f"{want} ({expr!r} at {dims_str(dims)}) "
                f"[rule: collective-contract]"
            )

    for kind, axis in contract.get("axes", {}).items():
        if axis not in axis_names:
            continue
        axis_index = axis_names.index(axis)
        if kind == "collective-permute":
            violations.extend(check_pairs_axis(
                hlo_ppermute_pairs(txt), mesh_shape, axis_index, axis,
            ))
        else:
            violations.extend(check_groups_axis(
                txt, kind, mesh_shape, axis_index, axis,
            ))

    if contract.get("both_directions"):
        axis = contract["axes"]["collective-permute"]
        if axis in axis_names:
            axis_index = axis_names.index(axis)
            size = mesh_shape[axis_index]
            shifts = set()
            for ps in hlo_ppermute_pairs(txt):
                for s, t in ps:
                    cs = _device_coords(s, mesh_shape)
                    ct = _device_coords(t, mesh_shape)
                    shifts.add((ct[axis_index] - cs[axis_index]) % size)
            if size > 1 and not {1, size - 1} <= shifts:
                violations.append(
                    f"{strategy}/{direction}: permute shifts {sorted(shifts)} "
                    f"do not cover both ring directions (+1 and -1) — the "
                    f"counter-rotation must load both full-duplex link "
                    f"directions [rule: both-directions]"
                )
    return violations


def check_hop_bytes(strategy: str, direction: str, dims: dict[str, int],
                    ppermute_bytes: list[int]) -> list[str]:
    """Pin the smallest/largest circulating ppermute payload against the
    contract's declared bytes-per-hop expressions (jaxpr-level avals —
    backend-independent, immune to the CPU runtime's dtype promotions)."""
    contract = CONTRACTS[strategy]
    exprs = contract.get("hop_bytes", {}).get(direction)
    if not exprs:
        return []
    if not ppermute_bytes:
        return [f"{strategy}/{direction}: no ppermute payloads found but "
                f"hop_bytes declared [rule: hop-bytes]"]
    out = []
    got = {"min": min(ppermute_bytes), "max": max(ppermute_bytes)}
    for bound, expr in exprs.items():
        want = int(eval(expr, {"__builtins__": {}}, dict(dims)))  # noqa: S307 - table-only
        if got[bound] != want:
            out.append(
                f"{strategy}/{direction}: {bound} ppermute payload "
                f"{got[bound]} bytes, contract says {want} ({expr!r} at "
                f"{dims_str(dims)}) [rule: hop-bytes]"
            )
    return out


def check_strategy(strategy: str, mesh=None, *, directions=None,
                   **shape_kw) -> list[ContractReport]:
    """Verify one strategy's collective contract on a mesh.

    For each direction the entry point is compiled and its optimized HLO
    checked against the declarative table: exact counts per declared
    collective kind, zero for every undeclared kind, axis discipline for
    permute pairs and replica groups — plus the jaxpr-structure rules
    (scan-aware counts where declared; never a collective inside a
    ``lax.cond`` branch).  Returns one :class:`ContractReport` per
    direction; a report with a non-empty ``violations`` list failed.
    """
    import jax

    contract = CONTRACTS[strategy]
    if mesh is None:
        mesh = default_mesh(strategy)
    if directions is None:
        directions = contract.get("directions", ("fwd", "fwdbwd"))
    mesh_shape = tuple(mesh.shape.values())
    axis_names = list(mesh.shape.keys())

    fn, args, dims = build_entry(strategy, mesh, **shape_kw)
    reports = []
    for direction in directions:
        dfn = _direction_fn(fn, direction)
        report = ContractReport(
            strategy=strategy, direction=direction, impl=contract["impl"],
            mesh_shape=mesh_shape, dims=dims,
        )
        txt = _compiled_hlo(dfn, args)
        report.counts = hlo_collective_counts(txt)
        report.expected = expected_counts(strategy, direction, dims)
        report.violations.extend(verify_hlo(
            strategy, direction, txt, dims, mesh_shape, axis_names,
        ))

        # traced structure: scan-aware counts + the no-collective-in-cond rule
        jc = jaxpr_collectives(jax.make_jaxpr(dfn)(*args))
        report.jaxpr_counts = jc.counts
        report.violations.extend(check_hop_bytes(
            strategy, direction, dims, jc.ppermute_bytes,
        ))
        if jc.in_cond:
            report.violations.append(
                f"{strategy}/{direction}: collective(s) {sorted(set(jc.in_cond))} "
                f"inside a lax.cond branch — data-dependent collective "
                f"schedules deadlock SPMD programs [rule: no-cond-collective]"
            )
        if jc.dynamic:
            report.violations.append(
                f"{strategy}/{direction}: collective(s) "
                f"{sorted(set(jc.in_while))} inside a lax.while_loop body — "
                f"trip count unknown statically, collective counts "
                f"unverifiable [rule: no-while-collective]"
            )
        reports.append(report)
    return reports


def check_scan_contract(strategy: str, mesh=None, *, directions=None,
                        **shape_kw) -> list[ContractReport]:
    """The traced (``impl="xla"``, scanned-hop) side of a strategy's
    contract: jaxpr collective counts with scan multipliers."""
    import jax

    contract = dict(CONTRACTS[strategy])
    if "scan" not in contract:
        raise KeyError(f"{strategy} declares no scan contract")
    if mesh is None:
        mesh = default_mesh(strategy)
    if directions is None:
        directions = tuple(contract["scan"])

    # rebuild the entry on the scanned XLA path
    fn, args, dims = build_entry(strategy, mesh, impl="xla", **shape_kw)

    reports = []
    for direction in directions:
        dfn = _direction_fn(fn, direction)
        report = ContractReport(
            strategy=strategy, direction=direction, impl="xla",
            mesh_shape=tuple(mesh.shape.values()), dims=dims,
        )
        jc = jaxpr_collectives(jax.make_jaxpr(dfn)(*args))
        report.jaxpr_counts = jc.counts
        report.expected = expected_counts(strategy, direction, dims,
                                          table="scan")
        for prim, want in report.expected.items():
            got = jc.counts.get(prim, 0)
            if got != want:
                expr = CONTRACTS[strategy]["scan"][direction][prim]
                report.violations.append(
                    f"{strategy}/{direction} (traced): {prim} x{got}, "
                    f"contract says {want} ({expr!r} at {dims_str(dims)}) "
                    f"[rule: collective-contract]"
                )
        if jc.in_cond:
            report.violations.append(
                f"{strategy}/{direction} (traced): collective(s) "
                f"{sorted(set(jc.in_cond))} inside a lax.cond branch "
                f"[rule: no-cond-collective]"
            )
        if jc.dynamic:
            report.violations.append(
                f"{strategy}/{direction} (traced): collective(s) "
                f"{sorted(set(jc.in_while))} inside a lax.while_loop body — "
                f"trip count unknown statically [rule: no-while-collective]"
            )
        reports.append(report)
    return reports


# The fused-ring row (ops/pallas_ring.py::fused_ring_remote) pins a
# DIFFERENT surface from the scan-path contracts above: hops are in-kernel
# remote DMAs, so the proof counts Mosaic DMA/semaphore primitives from the
# traced kernel body instead of HLO collectives.  The counts are structural
# (static ``pl.when`` branches, a once-traced ``fori_loop`` body), so they
# are ring-size and shard-size independent.  They were hand-derived here
# through PR 18 (dma_start 14, dma_wait 14, signal 3, wait 2, barrier 1);
# since the protocol verifier landed they are DERIVED from the declared
# schedule — the per-row ``sites`` fields of ops/pallas_ring.py::PROTOCOL,
# summed by schedverify.derived_fused_counts() — so the contract pin and
# the model-checked protocol cannot disagree silently.  The zero-ppermute
# pin (the launch-free-hops claim itself) rides along in the derivation.
FUSED_RING_PRIMS = (
    "dma_start", "dma_wait", "semaphore_signal", "semaphore_wait",
    "get_barrier_semaphore", "ppermute",
)


def _derived_fused_expected() -> dict[str, int]:
    from .schedverify import derived_fused_counts

    return derived_fused_counts()


def __getattr__(name: str):
    # FUSED_RING_EXPECTED stays importable (tests pin against it) but is
    # computed from the verified PROTOCOL table, not hand-maintained.
    if name == "FUSED_RING_EXPECTED":
        return _derived_fused_expected()
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


def jaxpr_primitive_counts(closed_jaxpr, names) -> dict[str, int]:
    """Exhaustive primitive counts from a traced program, descending into
    every sub-jaxpr a param carries (scan/cond/while bodies, shard_map,
    pallas_call kernels) — unlike :func:`jaxpr_collectives` there is no
    scan multiplication; this counts traced instructions."""
    counts: Counter = Counter()

    def walk(jaxpr) -> None:
        for eqn in jaxpr.eqns:
            if eqn.primitive.name in names:
                counts[eqn.primitive.name] += 1
            for v in eqn.params.values():
                for sub in _sub_jaxprs(v):
                    walk(sub)

    walk(closed_jaxpr.jaxpr)
    return dict(counts)


def trace_fused_ring(*, quantized: bool = False, b: int = 1, heads: int = 4,
                     kv_heads: int = 2, seq: int = 256, dim_head: int = 16):
    """Trace the single-launch remote kernel under ``shard_map`` on the
    full-device CPU ring — the shared feed for the fused contract row AND
    schedverify's jaxpr extraction.  Returns ``(closed_jaxpr, dims)``;
    make_jaxpr only, nothing compiles or runs."""
    import jax
    import jax.numpy as jnp

    from ..ops import pallas_ring
    from ..ops import quant as _quant
    from ..parallel.mesh import SEQ_AXIS, data_partition, seq_partition
    from ..utils import compat
    from jax.sharding import PartitionSpec as P

    mesh = default_mesh("ring")
    dims = _mesh_dims(mesh)
    ring = dims["ring"]
    n_local = seq // ring
    dims.update(b=b, heads=heads, kv_heads=kv_heads, seq=seq,
                dim_head=dim_head, chunk=n_local)
    rng = np.random.default_rng(0)
    b_full = b * dims["data"] * dims["dcn"]

    def mk(h):
        return jnp.asarray(rng.standard_normal((b_full, h, seq, dim_head)),
                           jnp.float32)

    def core(q, k, v):
        his = jnp.full((ring,), n_local, jnp.int32)
        los = jnp.full((ring,), -n_local, jnp.int32)
        works = jnp.ones((ring,), jnp.int32)
        # per-axis MESH coordinates — this mesh is multi-axis (data/dcn
        # around the ring), exactly the shape where a ring-rank-only
        # LOGICAL id would address the wrong replica group
        nbr_coords = pallas_ring.neighbor_mesh_coords(SEQ_AXIS, ring)
        payload = (_quant.pack_kv(k, v, v_block=n_local)
                   if quantized else None)
        out, _ = pallas_ring.fused_ring_remote(
            q, k, v, his=his, los=los, works=works, nbr_coords=nbr_coords,
            scale=dim_head ** -0.5, payload=payload,
        )
        return out

    spec = P(data_partition(mesh), None, seq_partition(mesh), None)
    fn = compat.shard_map(
        core, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False,
    )
    jaxpr = jax.make_jaxpr(fn)(mk(heads), mk(kv_heads), mk(kv_heads))
    return jaxpr, dims


def check_fused_ring_contract(
    *, quantized: bool = False, b: int = 1, heads: int = 4,
    kv_heads: int = 2, seq: int = 256, dim_head: int = 16,
) -> ContractReport:
    """The fused-ring contract row: trace the single-launch remote kernel
    under ``shard_map`` on the full-device CPU ring and hold its traced
    body to the schedverify-derived expected counts — the in-kernel remote
    copies and semaphore handshakes declared by the verified PROTOCOL
    table, and zero ``ppermute``s (the scan-path ring's per-hop collective
    has no business in the fused forward).  The ``quantized`` variant
    feeds PR 13's packed int8 payload through the same kernel and must
    produce IDENTICAL counts: scales ride the KV buffer, never their own
    copy."""
    jaxpr, dims = trace_fused_ring(
        quantized=quantized, b=b, heads=heads, kv_heads=kv_heads, seq=seq,
        dim_head=dim_head,
    )
    mesh = default_mesh("ring")
    counted = jaxpr_primitive_counts(jaxpr, FUSED_RING_PRIMS)

    report = ContractReport(
        strategy="fused_ring_q8" if quantized else "fused_ring",
        direction="fwd", impl="fused",
        mesh_shape=tuple(mesh.shape.values()), dims=dims,
        # zeros stay explicit: "ppermute": 0 IS the launch-free-hops pin
        counts={p: counted.get(p, 0) for p in FUSED_RING_PRIMS},
        expected=_derived_fused_expected(),
    )
    for prim, want in report.expected.items():
        got = report.counts.get(prim, 0)
        if got != want:
            rule = ("launch-free-hops" if prim == "ppermute"
                    else "fused-ring-dma")
            report.violations.append(
                f"{report.strategy}/fwd (traced kernel): {prim} x{got}, "
                f"contract says {want} at {dims_str(dims)} [rule: {rule}]"
            )
    return report


def check_hybrid_hop_reduction(world: int | None = None, ulysses: int = 2,
                               **shape_kw) -> ContractReport:
    """The tentpole relation, proven from two compiled programs: at equal
    sequence-parallel world, the hybrid factoring's ring hop count is
    exactly ``ulysses``-x smaller (``world/ulysses - 1`` vs ``world - 1``)."""
    import jax

    from ..parallel.mesh import create_mesh

    if world is None:
        world = len(jax.devices())
    hmesh = create_mesh(ulysses_size=ulysses, ring_size=world // ulysses)
    rmesh = create_mesh(ring_size=world)

    hfn, hargs, hdims = build_entry("hybrid", hmesh, **shape_kw)
    rfn, rargs, rdims = build_entry("ring", rmesh, **shape_kw)
    hops_h = hlo_collective_counts(
        _compiled_hlo(hfn, hargs)
    ).get("collective-permute", 0)
    hops_r = hlo_collective_counts(
        _compiled_hlo(rfn, rargs)
    ).get("collective-permute", 0)

    report = ContractReport(
        strategy="hybrid_vs_ring", direction="fwd", impl="pallas",
        mesh_shape=tuple(hmesh.shape.values()),
        dims={**hdims, "pure_ring_world": world},
        counts={"hybrid_hops": hops_h, "pure_ring_hops": hops_r},
        expected={"hybrid_hops": world // ulysses - 1,
                  "pure_ring_hops": world - 1},
    )
    if hops_r != world - 1:
        report.violations.append(
            f"pure ring at world {world}: {hops_r} hops, contract says "
            f"{world - 1} [rule: hop-reduction]"
        )
    if hops_h != world // ulysses - 1:
        report.violations.append(
            f"hybrid at world {world} (ulysses {ulysses}): {hops_h} hops, "
            f"contract says {world // ulysses - 1} [rule: hop-reduction]"
        )
    if (hops_h + 1) * ulysses != hops_r + 1:
        report.violations.append(
            f"hybrid hop chain ({hops_h + 1} rotations incl. the elided "
            f"last) is not ulysses-x ({ulysses}) shorter than the pure "
            f"ring's ({hops_r + 1}) [rule: hop-reduction]"
        )
    return report


def check_counter_collective_budget(**shape_kw) -> ContractReport:
    """The counter-rotation acceptance pin, proven from compiled programs:
    a counter-rotated train step (fwd + bwd) issues NO MORE collectives
    than the unidirectional baseline's — ``2 * ring`` vs ``3 * ring - 2``
    (fwd alone pays one extra for the out/lse catch-up, ``ring`` vs
    ``ring - 1``; the backward's resident-KV schedule repays it with
    ``ring`` vs ``2 * ring - 1``)."""
    mesh = default_mesh("ring")
    ring = _mesh_dims(mesh)["ring"]

    def permutes(strategy, direction):
        fn, args, _ = build_entry(strategy, mesh, **shape_kw)
        txt = _compiled_hlo(_direction_fn(fn, direction), args)
        return hlo_collective_counts(txt).get("collective-permute", 0)

    base_fwd = permutes("ring", "fwd")
    base_step = permutes("ring", "fwdbwd")
    ctr_fwd = permutes("counter", "fwd")
    ctr_step = permutes("counter", "fwdbwd")

    report = ContractReport(
        strategy="counter_vs_ring", direction="fwdbwd", impl="pallas",
        mesh_shape=tuple(mesh.shape.values()), dims={"ring": ring},
        counts={"counter_fwd": ctr_fwd, "counter_step": ctr_step,
                "baseline_fwd": base_fwd, "baseline_step": base_step},
        expected={"counter_fwd": ring, "counter_step": 2 * ring,
                  "baseline_fwd": ring - 1, "baseline_step": 3 * ring - 2},
    )
    for key, want in report.expected.items():
        if report.counts[key] != want:
            report.violations.append(
                f"{key}: {report.counts[key]} collective-permutes, contract "
                f"says {want} at ring={ring} [rule: counter-budget]"
            )
    if ctr_step > base_step:
        report.violations.append(
            f"counter-rotated step issues {ctr_step} collective-permutes, "
            f"MORE than the unidirectional baseline's {base_step} "
            f"[rule: counter-budget]"
        )
    return report


def hlo_dcn_isolation(
    txt: str, mesh_shape: tuple[int, ...], axis_names: list[str]
) -> list[str]:
    """The pod-scale placement proof: ZERO sequence-parallel collectives
    cross the ``dcn_data`` axis in optimized HLO.

    Every collective-permute pair and every all-to-all / all-gather /
    reduce-scatter replica group must keep the dcn coordinate fixed —
    a ring hop or head all-to-all that touches two dcn groups is riding
    the slow inter-slice links TASP (arXiv 2509.26541) places sequence
    parallelism to avoid.  ``all-reduce`` is exempt: the once-per-step
    gradient reduction is the ONE collective that legitimately spans DCN.
    Returns one-line violations.
    """
    from ..parallel.mesh import DCN_DATA_AXIS

    if DCN_DATA_AXIS not in axis_names:
        return [f"mesh axes {axis_names} carry no {DCN_DATA_AXIS} axis — "
                f"nothing to prove [rule: dcn-isolation]"]
    dcn_i = axis_names.index(DCN_DATA_AXIS)
    out: list[str] = []
    for inst, pairs in enumerate(hlo_ppermute_pairs(txt)):
        for s, t in pairs:
            cs = _device_coords(s, mesh_shape)
            ct = _device_coords(t, mesh_shape)
            if cs[dcn_i] != ct[dcn_i]:
                out.append(
                    f"collective-permute #{inst}: pair {s}->{t} crosses "
                    f"the dcn_data axis (coords {cs}->{ct}) — a ring hop "
                    f"over DCN [rule: dcn-isolation]"
                )
    for kind in ("all-to-all", "all-gather", "reduce-scatter"):
        for inst, line in enumerate(_hlo_instructions(txt, kind)):
            groups = _parse_replica_groups(line)
            if groups is None:
                continue
            if isinstance(groups, str):
                out.append(
                    f"{kind} #{inst}: unrecognized replica_groups format "
                    f"{groups!r} — cannot verify dcn isolation "
                    f"[rule: dcn-isolation]"
                )
                continue
            for g in groups:
                coords = {_device_coords(d, mesh_shape)[dcn_i] for d in g}
                if len(coords) > 1:
                    out.append(
                        f"{kind} #{inst}: group {g} spans dcn_data "
                        f"coordinates {sorted(coords)} [rule: dcn-isolation]"
                    )
    return out


def jaxpr_collective_axis_names(closed_jaxpr) -> dict[str, set]:
    """Axis names each collective primitive binds in a traced program —
    the jaxpr half of the dcn-isolation proof (an ``axis_name`` is the
    mesh axis the collective moves data over)."""
    res: dict[str, set] = {}

    def walk(jaxpr) -> None:
        for eqn in jaxpr.eqns:
            name = eqn.primitive.name
            if name in JAXPR_COLLECTIVE_PRIMS:
                axes = eqn.params.get("axis_name", ())
                if not isinstance(axes, (tuple, list)):
                    axes = (axes,)
                res.setdefault(name, set()).update(str(a) for a in axes)
            for v in eqn.params.values():
                for sub in _sub_jaxprs(v):
                    walk(sub)

    walk(closed_jaxpr.jaxpr)
    return res


def check_dcn_isolation(
    *, dcn: int = 2, ulysses: int = 2, directions=None, **shape_kw
) -> list[ContractReport]:
    """The hierarchical-mesh contract rows: the ring and hybrid entries
    compiled over a ``(dcn_data, data, ...)`` mesh hold their ordinary
    collective contracts AND provably issue zero sequence-parallel
    collectives over the dcn axis — from optimized HLO
    (:func:`hlo_dcn_isolation`) and from the jaxpr walk
    (:func:`jaxpr_collective_axis_names`).  Rows: ``ring_dcn`` always,
    ``hybrid_dcn`` when the per-group world still factors as
    ring x ulysses."""
    import jax

    from ..parallel.mesh import DCN_DATA_AXIS, create_mesh

    n = len(jax.devices())
    if n % dcn or n // dcn < 2:
        raise ValueError(
            f"check_dcn_isolation: need >= {2 * dcn} devices factorable "
            f"by dcn={dcn}, have {n}"
        )
    inner = n // dcn
    cases = [("ring", create_mesh(dcn_data_size=dcn, ring_size=inner))]
    if inner % ulysses == 0 and inner // ulysses >= 2:
        cases.append((
            "hybrid",
            create_mesh(dcn_data_size=dcn, ring_size=inner // ulysses,
                        ulysses_size=ulysses),
        ))
    reports: list[ContractReport] = []
    for strategy, mesh in cases:
        mesh_shape = tuple(mesh.shape.values())
        axis_names = list(mesh.shape.keys())
        fn, args, dims = build_entry(strategy, mesh, **shape_kw)
        dirs = directions or CONTRACTS[strategy].get(
            "directions", ("fwd", "fwdbwd")
        )
        for direction in dirs:
            dfn = _direction_fn(fn, direction)
            report = ContractReport(
                strategy=f"{strategy}_dcn", direction=direction,
                impl=CONTRACTS[strategy]["impl"], mesh_shape=mesh_shape,
                dims=dims,
            )
            txt = _compiled_hlo(dfn, args)
            report.counts = hlo_collective_counts(txt)
            report.expected = expected_counts(strategy, direction, dims)
            # the ordinary contract (exact counts, axis discipline, no
            # undeclared kinds) still holds at the dcn factoring...
            report.violations.extend(verify_hlo(
                strategy, direction, txt, dims, mesh_shape, axis_names,
            ))
            # ...plus the hierarchical placement rule itself
            report.violations.extend(
                hlo_dcn_isolation(txt, mesh_shape, axis_names)
            )
            axes_by_prim = jaxpr_collective_axis_names(
                jax.make_jaxpr(dfn)(*args)
            )
            report.jaxpr_counts = {
                prim: sorted(axes) for prim, axes in axes_by_prim.items()
            }
            for prim, axes in axes_by_prim.items():
                if DCN_DATA_AXIS in axes:
                    report.violations.append(
                        f"{strategy}_dcn/{direction} (traced): {prim} "
                        f"binds the {DCN_DATA_AXIS} axis — sequence "
                        f"parallelism crossed DCN [rule: dcn-isolation]"
                    )
            reports.append(report)
    return reports


def dcn_collective_fingerprint(*, dcn: int = 2, ulysses: int = 2) -> dict:
    """The multihost-dryrun comms signature: per-row forward collective
    counts over the hierarchical ``(dcn_data, ...)`` mesh, plus the
    machine-checked verdict that no sequence-parallel collective crossed
    the dcn axis.  CPU-runnable; ``tests/test_analysis.py`` pins it."""
    out: dict[str, Any] = {}
    ok = True
    for report in check_dcn_isolation(
        dcn=dcn, ulysses=ulysses, directions=("fwd",)
    ):
        out[report.strategy] = {
            k.replace("collective-permute", "ppermute")
             .replace("all-to-all", "all_to_all")
             .replace("all-gather", "all_gather")
             .replace("all-reduce", "all_reduce"): v
            for k, v in sorted(report.counts.items())
        }
        ok = ok and report.ok
    out["dcn_ok"] = ok
    return out


def dims_str(dims: dict[str, int]) -> str:
    return ", ".join(f"{k}={v}" for k, v in sorted(dims.items()))


def run_contract_suite(strategies=None, *, scan: bool = True,
                       **shape_kw) -> list[ContractReport]:
    """Every strategy's contract on its canonical CPU mesh, plus the
    hybrid-vs-ring hop-reduction relation.  The CLI runs exactly this."""
    if strategies is None or strategies == "all":
        strategies = list(CONTRACTS)
    reports: list[ContractReport] = []
    for strategy in strategies:
        reports.extend(check_strategy(strategy, **shape_kw))
        if scan and "scan" in CONTRACTS[strategy]:
            reports.extend(check_scan_contract(strategy, **shape_kw))
    if "hybrid" in strategies and "ring" in strategies:
        reports.append(check_hybrid_hop_reduction(**shape_kw))
    if "counter" in strategies and "ring" in strategies:
        reports.append(check_counter_collective_budget(**shape_kw))
    if "ring" in strategies:
        import jax

        if len(jax.devices()) >= 4:
            reports.extend(check_dcn_isolation(**shape_kw))
        reports.append(check_fused_ring_contract())
        reports.append(check_fused_ring_contract(quantized=True))
    return reports


def collective_fingerprint(
    strategies=("ring", "ulysses", "hybrid", "counter", "ring_compressed",
                "counter_q8", "blockwise_ffn"),
) -> dict:
    """Compact comms signature (``__graft_entry__.dryrun_multichip``
    prints it): per-strategy forward collective counts from compiled HLO,
    so a hop-count or accidental-gather regression shows in the dry run's
    last line.  The counter-rotation and int8-compressed ring variants
    ride along."""
    out: dict[str, Any] = {}
    ok = True
    for strategy in strategies:
        reports = check_strategy(strategy, directions=("fwd",))
        rep = reports[0]
        out[strategy] = {
            k.replace("collective-permute", "ppermute")
             .replace("all-to-all", "all_to_all")
             .replace("all-gather", "all_gather")
             .replace("all-reduce", "all_reduce"): v
            for k, v in sorted(rep.counts.items())
        }
        ok = ok and rep.ok
    # the fused-ring rows speak Mosaic primitives, not HLO collectives:
    # in-kernel remote-copy/semaphore counts with the zero-ppermute pin
    for quantized in (False, True):
        rep = check_fused_ring_contract(quantized=quantized)
        out[rep.strategy] = dict(sorted(rep.counts.items()))
        ok = ok and rep.ok
    out["contract_ok"] = ok
    return out
