"""Jaxpr abstract interpretation: the dataflow layer under the audit passes.

PR 5's audits are spot checks — ``audit_accumulator_dtypes`` eval_shapes
two entry points and trusts that the rest of the package holds the same
discipline, and the contracts checker's no-collective-under-cond rule is a
*syntactic* ban rather than the actual SPMD requirement (branches may
collectively communicate, as long as every rank communicates the SAME
way).  This module adds the missing machinery: a small worklist walker
over closed jaxprs (:class:`JaxprWalker`) that descends into
scan/while/cond/pjit/custom-vjp/remat/shard_map/pallas sub-jaxprs, carries
a per-variable lattice value to a fixpoint through loop carries, and keeps
an equation provenance path so every finding is a one-line diagnostic
naming where in the program the violation sits.

Two passes ride on it:

  - :func:`audit_precision_flow` — the precision-flow auditor
    generalizing ``audit_accumulator_dtypes``: quantized-int8 content is
    tracked as taint through the whole program while every
    reduction/dot/carry site is checked against its storage dtype, and a
    violation is raised when (a) a float reduction / exponential / dot
    accumulation executes at sub-f32 storage (the softmax
    ``(acc, m, l)``/lse/delta contract), (b) a scan/while carry
    accumulates arithmetic results at sub-f32 storage, or (c) a
    quantized int8 payload reaches accumulation without its
    dequantization scale multiply (the int8 hop-compression contract,
    TokenRing lineage, arXiv 2412.20501).
  - :func:`check_spmd_divergence` — the SPMD divergence checker: for
    every ``lax.cond`` the *collective sequence* (kind, axes, operand
    shape/dtype, in order, scan-aware) must be identical across all
    branches, and no ``lax.while_loop`` body may communicate at all
    (its trip count is rank-local, so no sequence can be proven) — the
    proof-level upgrade of the lint/contract heuristics: no rank can
    deadlock waiting for a collective another rank never issues.

Like ``recompile.py``, this module is stdlib-only at module level; jax
imports live inside functions.  Everything runs at trace level (``jax.
make_jaxpr``) — no compile, no devices, any backend.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Callable

# ---------------------------------------------------------------------------
# The walker
# ---------------------------------------------------------------------------

# Primitive param keys that hold sub-jaxprs, by descent style.  Anything
# not listed falls back to the conservative generic descent (operands
# joined into every sub-jaxpr input).
_SCAN, _WHILE, _COND, _CALL, _PALLAS = "scan", "while", "cond", "call", "pallas"

_DESCENT_STYLE = {
    "scan": _SCAN,
    "while": _WHILE,
    "cond": _COND,
    "pjit": _CALL,
    "closed_call": _CALL,
    "core_call": _CALL,
    "remat2": _CALL,
    "checkpoint": _CALL,
    "custom_jvp_call": _CALL,
    "custom_vjp_call": _CALL,
    "custom_jvp_call_jaxpr": _CALL,
    "custom_vjp_call_jaxpr": _CALL,
    "shard_map": _CALL,
    "pallas_call": _PALLAS,
}

# Max fixpoint sweeps through a loop body.  The lattices used here are
# tiny finite joins (taint tag sets), so 2-3 sweeps converge; the cap is
# a backstop against a non-monotone custom transfer, never a correctness
# input (the walker joins, so an early stop under-reports rather than
# crashes).
_MAX_FIXPOINT_SWEEPS = 8


@dataclass(frozen=True)
class EqnSite:
    """Provenance of one equation: the enclosing-primitive path plus the
    equation's own primitive and output signature — enough to name the
    offending operation in one line without a traceback."""

    path: tuple[str, ...]
    prim: str
    index: int
    out_aval: str

    def __str__(self) -> str:
        where = "/".join(self.path) or "top"
        return f"{where}::{self.prim}#{self.index} -> {self.out_aval}"


def _aval_str(var) -> str:
    aval = getattr(var, "aval", None)
    dtype = getattr(aval, "dtype", None)
    shape = getattr(aval, "shape", None)
    if dtype is None:
        return "?"
    return f"{dtype}{list(shape) if shape is not None else ''}"


def _inner_aval(aval):
    """The value aval behind a pallas/state Ref aval (identity otherwise)."""
    return getattr(aval, "inner_aval", aval)


def _sub_closed_jaxprs(value):
    """Yield every (Closed)Jaxpr nested in a params value."""
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


def _as_jaxpr(value):
    from jax.extend import core as jex_core

    if isinstance(value, jex_core.ClosedJaxpr):
        return value.jaxpr
    return value


class JaxprWalker:
    """Forward abstract interpretation over a closed jaxpr.

    Subclasses define the lattice: :meth:`init_value` (an input/constant
    variable's starting value, from its aval), :meth:`join`, and
    :meth:`transfer` (equation semantics -> output values).  The base
    class owns the structure: environment threading, descent into
    control-flow sub-jaxprs (scan/while carries run to a join fixpoint;
    cond branches are walked independently and joined), conservative
    descent into unknown sub-jaxpr-carrying primitives, and provenance
    (:class:`EqnSite`) for every visited equation.  :meth:`visit` is the
    hook passes use to emit findings.
    """

    def __init__(self) -> None:
        self.findings: list[str] = []

    # -- lattice interface -------------------------------------------------
    def init_value(self, aval) -> Any:
        return frozenset()

    def join(self, a, b):
        return a | b

    def transfer(self, eqn, in_vals, site: EqnSite) -> list[Any]:
        joined = self.init_value(None)
        for v in in_vals:
            joined = self.join(joined, v)
        return [joined for _ in eqn.outvars]

    def visit(self, eqn, in_vals, out_vals, site: EqnSite) -> None:
        """Pass hook, called once per equation (per fixpoint sweep —
        emit findings idempotently; the base class dedups)."""

    # -- environment helpers ----------------------------------------------
    def _read(self, env, atom):
        from jax.extend import core as jex_core

        if isinstance(atom, jex_core.Literal):
            return self.init_value(getattr(atom, "aval", None))
        if atom in env:
            return env[atom]
        return self.init_value(atom.aval)

    def _write(self, env, var, val) -> None:
        env[var] = self.join(env.get(var, self.init_value(var.aval)), val)

    # -- the walk ----------------------------------------------------------
    def run(self, closed_jaxpr, label: str = "") -> list[str]:
        jaxpr = _as_jaxpr(closed_jaxpr)
        env: dict = {}
        for v in list(jaxpr.invars) + list(jaxpr.constvars):
            env[v] = self.init_value(v.aval)
        self._walk(jaxpr, env, path=())
        # findings are emitted per sweep; keep first occurrence order
        self.findings = list(dict.fromkeys(self.findings))
        if label:
            self.findings = [f"{label}: {f}" for f in self.findings]
        return self.findings

    def _seed(self, jaxpr, in_vals) -> dict:
        env: dict = {}
        for var, val in zip(jaxpr.invars, in_vals):
            env[var] = val
        for var in jaxpr.constvars:
            env[var] = self.init_value(var.aval)
        return env

    def _walk(self, jaxpr, env, path) -> list[Any]:
        for idx, eqn in enumerate(jaxpr.eqns):
            in_vals = [self._read(env, a) for a in eqn.invars]
            site = EqnSite(
                path, eqn.primitive.name, idx,
                _aval_str(eqn.outvars[0]) if eqn.outvars else "()",
            )
            style = _DESCENT_STYLE.get(eqn.primitive.name)
            if style == _SCAN:
                out_vals = self._walk_scan(eqn, in_vals, path, idx)
            elif style == _WHILE:
                out_vals = self._walk_while(eqn, in_vals, path, idx)
            elif style == _COND:
                out_vals = self._walk_cond(eqn, in_vals, path, idx)
            elif style == _CALL:
                out_vals = self._walk_call(eqn, in_vals, path, idx)
            elif style == _PALLAS:
                out_vals = self._walk_pallas(eqn, in_vals, path, idx)
            else:
                out_vals = self._walk_generic(eqn, in_vals, path, idx, site)
            self.visit(eqn, in_vals, out_vals, site)
            for var, val in zip(eqn.outvars, out_vals):
                self._write(env, var, val)
            self.post_eqn(env, eqn, in_vals, out_vals)
        return [self._read(env, a) for a in jaxpr.outvars]

    def post_eqn(self, env, eqn, in_vals, out_vals) -> None:
        """Post-write hook (e.g. ref-mutation semantics for pallas/state
        primitives — the environment is mutable here)."""

    def _walk_scan(self, eqn, in_vals, path, idx):
        body = _as_jaxpr(eqn.params["jaxpr"])
        n_consts = eqn.params["num_consts"]
        n_carry = eqn.params["num_carry"]
        length = int(eqn.params["length"])
        consts = in_vals[:n_consts]
        carry = list(in_vals[n_consts:n_consts + n_carry])
        xs = in_vals[n_consts + n_carry:]
        sub_path = path + (f"scan[{length}]#{idx}",)
        ys = [self.init_value(v.aval) for v in body.outvars[n_carry:]]
        for _ in range(_MAX_FIXPOINT_SWEEPS):
            env = self._seed(body, consts + carry + xs)
            outs = self._walk(body, env, sub_path)
            new_carry = [self.join(c, o) for c, o in zip(carry, outs[:n_carry])]
            ys = [self.join(y, o) for y, o in zip(ys, outs[n_carry:])]
            if new_carry == carry:
                break
            carry = new_carry
        self._check_loop_carries(eqn, body, carry, n_consts, n_carry,
                                 sub_path, kind="scan")
        return carry + ys

    def _walk_while(self, eqn, in_vals, path, idx):
        cond = _as_jaxpr(eqn.params["cond_jaxpr"])
        body = _as_jaxpr(eqn.params["body_jaxpr"])
        nc = eqn.params["cond_nconsts"]
        nb = eqn.params["body_nconsts"]
        cond_consts = in_vals[:nc]
        body_consts = in_vals[nc:nc + nb]
        carry = list(in_vals[nc + nb:])
        sub_path = path + (f"while#{idx}",)
        for _ in range(_MAX_FIXPOINT_SWEEPS):
            env = self._seed(cond, cond_consts + carry)
            self._walk(cond, env, sub_path + ("cond",))
            env = self._seed(body, body_consts + carry)
            outs = self._walk(body, env, sub_path + ("body",))
            new_carry = [self.join(c, o) for c, o in zip(carry, outs)]
            if new_carry == carry:
                break
            carry = new_carry
        self._check_loop_carries(eqn, body, carry, nb, len(carry),
                                 sub_path, kind="while", body_offset=nb)
        return carry

    def _check_loop_carries(self, eqn, body, carry_vals, n_consts, n_carry,
                            sub_path, kind, body_offset=None):
        """Hook for carry-level checks (the precision pass overrides)."""

    def _walk_cond(self, eqn, in_vals, path, idx):
        ops = in_vals[1:]
        out_vals = None
        for bi, branch in enumerate(eqn.params["branches"]):
            body = _as_jaxpr(branch)
            env = self._seed(body, ops)
            outs = self._walk(body, env, path + (f"cond#{idx}/branch{bi}",))
            if out_vals is None:
                out_vals = list(outs)
            else:
                out_vals = [self.join(a, b) for a, b in zip(out_vals, outs)]
        return out_vals or []

    def _walk_call(self, eqn, in_vals, path, idx):
        for key in ("jaxpr", "call_jaxpr", "fun_jaxpr"):
            if key in eqn.params:
                body = _as_jaxpr(eqn.params[key])
                break
        else:
            return self._walk_generic(eqn, in_vals, path, idx, None)
        if len(body.invars) != len(in_vals):
            return self._walk_generic(eqn, in_vals, path, idx, None)
        env = self._seed(body, in_vals)
        outs = self._walk(env=env, jaxpr=body,
                          path=path + (f"{eqn.primitive.name}#{idx}",))
        if len(outs) == len(eqn.outvars):
            return outs
        joined = self.init_value(None)
        for o in outs:
            joined = self.join(joined, o)
        return [joined for _ in eqn.outvars]

    def _walk_pallas(self, eqn, in_vals, path, idx):
        """Kernel descent: the grid machinery between the outer operands
        and the kernel's refs is layout-dependent across jax versions, so
        the kernel jaxpr is re-seeded from its OWN ref avals (dtype-exact
        — precisely what a dtype/taint lattice needs) rather than mapped
        positionally; outer outputs re-seed from their avals likewise.
        The seeding itself is a hook (:meth:`pallas_kernel_env`) so a
        pass that needs ref IDENTITY rather than ref dtype — the
        schedule extractor threads each kernel invar's POSITION through
        cond branches and while carries to name the buffer/semaphore
        behind every DMA equation — can override just the environment."""
        body = None
        for key in ("jaxpr", "kernel_jaxpr"):
            if key in eqn.params:
                body = _as_jaxpr(eqn.params[key])
                break
        if body is not None:
            env = self.pallas_kernel_env(body, eqn)
            self._walk(body, env, path + (f"pallas_call#{idx}",))
        return [self.init_value(v.aval) for v in eqn.outvars]

    def pallas_kernel_env(self, body, eqn) -> dict:
        """Initial environment for a pallas kernel body.  Default: every
        ref invar/constvar starts at ``init_value`` of its inner aval."""
        return {
            v: self.init_value(_inner_aval(v.aval))
            for v in list(body.invars) + list(body.constvars)
        }

    def _walk_generic(self, eqn, in_vals, path, idx, site):
        """Default: apply the transfer function; conservatively descend
        into any nested jaxprs with every input joined (sound for a
        union lattice — over-approximates, never drops, taint)."""
        subs = []
        for v in eqn.params.values():
            subs.extend(_sub_closed_jaxprs(v))
        if subs:
            joined = self.init_value(None)
            for val in in_vals:
                joined = self.join(joined, val)
            for sub in subs:
                env = {
                    var: self.join(joined, self.init_value(var.aval))
                    for var in list(sub.invars) + list(sub.constvars)
                }
                self._walk(sub, env, path + (f"{eqn.primitive.name}#{idx}",))
        return self.transfer(
            eqn, in_vals,
            site or EqnSite(path, eqn.primitive.name, idx,
                            _aval_str(eqn.outvars[0]) if eqn.outvars else "()"),
        )


# ---------------------------------------------------------------------------
# Pass 1: precision flow
# ---------------------------------------------------------------------------

# The one flow-sensitive tag: INT8Q marks quantized content that has not
# yet met its dequantization scale.  Sub-f32 precision needs no taint —
# a bf16 INPUT is fine and expected; the violation is a reduction /
# accumulation EXECUTING at sub-f32 storage, which the sinks and carry
# checks read straight off the avals at the site.
INT8Q = "int8-quantized"

# Reductions/exponentials on the softmax-accumulator path: executing one
# at sub-f32 storage is the contract violation audit_accumulator_dtypes
# spot-checked for (acc, m, l) and this pass proves everywhere.
_REDUCTION_SINKS = {
    "reduce_sum", "reduce_max", "reduce_min", "cumsum", "cumlogsumexp",
    "exp", "exp2", "log", "log1p", "logistic",
}

# Arithmetic that constitutes "accumulation" for carry/backtrace checks.
_ARITH_PRIMS = {
    "add", "sub", "mul", "div", "dot_general", "max", "min", "exp", "exp2",
    "log", "integer_pow", "pow", "rsqrt", "sqrt", "tanh", "reduce_sum",
    "reduce_max", "reduce_min", "cumsum",
}

# Structure-only primitives: taint flows through, no arithmetic happened.
_TRANSPARENT_PRIMS = {
    "transpose", "reshape", "broadcast_in_dim", "squeeze", "expand_dims",
    "slice", "dynamic_slice", "dynamic_update_slice", "concatenate", "rev",
    "gather", "scatter", "pad", "select_n", "copy", "stop_gradient",
    "convert_element_type", "ppermute", "all_to_all", "all_gather",
    "all_gather_invariant", "pbroadcast", "pvary", "device_put", "iota",
    "split", "tie_in",
}

# Predicate-producing primitives: a bool output carries no precision.
_PREDICATE_PRIMS = {"eq", "ne", "lt", "le", "gt", "ge", "and", "or", "not",
                    "xor", "is_finite", "reduce_and", "reduce_or"}


def _dtype_of(aval):
    return getattr(aval, "dtype", None)


def _is_float(dtype) -> bool:
    # bf16's numpy dtype kind is 'V' (ml_dtypes extension type), so kind
    # checks lie; issubdtype knows the extension hierarchy
    if dtype is None:
        return False
    import jax.numpy as jnp

    return bool(jnp.issubdtype(dtype, jnp.floating))


def _is_sub_f32(dtype) -> bool:
    return _is_float(dtype) and dtype.itemsize < 4


def _is_int8(dtype) -> bool:
    if dtype is None:
        return False
    import jax.numpy as jnp

    return bool(jnp.issubdtype(dtype, jnp.integer)) and dtype.itemsize == 1


class PrecisionFlow(JaxprWalker):
    """Quantized-content taint (frozensets over {INT8Q}) plus storage-
    dtype checks at every reduction/dot/carry site."""

    def __init__(self, sinks_checked: list[str] | None = None):
        super().__init__()
        self.sinks_checked = sinks_checked if sinks_checked is not None else []

    def init_value(self, aval):
        dtype = _dtype_of(_inner_aval(aval) if aval is not None else None)
        if _is_int8(dtype):
            return frozenset({INT8Q})
        return frozenset()

    def transfer(self, eqn, in_vals, site):
        name = eqn.primitive.name
        union = frozenset().union(*in_vals) if in_vals else frozenset()
        out_dtype = (_dtype_of(getattr(eqn.outvars[0], "aval", None))
                     if eqn.outvars else None)
        if name in _PREDICATE_PRIMS:
            out = frozenset()
        elif name in ("mul", "div") and INT8Q in union and not all(
            INT8Q in v for v in in_vals
        ):
            # the dequantization pattern: quantized values scaled by a
            # non-quantized factor — content is real again
            out = union - {INT8Q}
        elif name == "bitcast_convert_type":
            # the hop-payload codec (quantize_ring_payload): a float
            # scale bitcast into bytes is opaque payload, and bytes
            # bitcast back to float are the scale again — not content
            # that needs a dequant multiply
            out = (union - {INT8Q}) if _is_float(out_dtype) else union
        else:
            out = union
        if _is_int8(out_dtype) and name not in _PREDICATE_PRIMS:
            # int8 storage is quantized content until a scale multiply
            # proves otherwise (pure index/flag math exits through
            # predicates or integer sinks, which the rules ignore)
            out = out | {INT8Q}
        return [out for _ in eqn.outvars]

    def post_eqn(self, env, eqn, in_vals, out_vals):
        # ref mutation: a store joins the stored value's taint into the
        # ref variable so later loads observe it
        if eqn.primitive.name in ("swap", "addupdate") and eqn.invars:
            from jax.extend import core as jex_core

            ref = eqn.invars[0]
            stored = (frozenset().union(*in_vals[1:])
                      if in_vals[1:] else frozenset())
            if not isinstance(ref, jex_core.Literal):
                env[ref] = env.get(ref, frozenset()) | stored

    def visit(self, eqn, in_vals, out_vals, site):
        name = eqn.primitive.name
        if name in _REDUCTION_SINKS and eqn.invars:
            op_dtype = _dtype_of(getattr(eqn.invars[0], "aval", None))
            if _is_sub_f32(op_dtype):
                self.findings.append(
                    f"sub-f32 value ({op_dtype}) reaches {name} at {site} — "
                    f"softmax/accumulator reductions must execute in "
                    f"float32 [rule: f32-accumulator-flow]"
                )
            if _is_float(op_dtype) and any(INT8Q in v for v in in_vals):
                # a dropped QK^T dequant reaches the softmax max/exp first
                self.findings.append(
                    f"quantized int8 content reaches {name} without a "
                    f"dequantization scale at {site} [rule: int8-dequant]"
                )
            self.sinks_checked.append(f"{name}@{site}")
        if name == "dot_general":
            out_dtype = _dtype_of(eqn.outvars[0].aval)
            if _is_sub_f32(out_dtype):
                self.findings.append(
                    f"dot_general accumulates at {out_dtype} at {site} — "
                    f"matmul accumulation must target float32 "
                    f"(preferred_element_type) [rule: f32-accumulator-flow]"
                )
            operand_dtypes = [
                _dtype_of(getattr(a, "aval", None)) for a in eqn.invars[:2]
            ]
            if all(_is_int8(t) for t in operand_dtypes):
                # the int8 compute path's own matmul: int8 x int8 into an
                # f32 accumulator is the LEGAL quantized form — its output
                # is still quantized content (the taint propagates through
                # the transfer) until the per-row/per-block scale multiply
                # strips it; accumulating it unscaled is caught below
                pass
            elif any(INT8Q in v for v in in_vals[:2]) and _is_float(out_dtype):
                self.findings.append(
                    f"quantized int8 operand reaches dot_general without a "
                    f"dequantization scale at {site} [rule: int8-dequant]"
                )
            self.sinks_checked.append(f"{name}@{site}")
        elif name in ("add", "sub", "reduce_sum", "cumsum"):
            out_dtype = _dtype_of(eqn.outvars[0].aval) if eqn.outvars else None
            if _is_float(out_dtype) and any(INT8Q in v for v in in_vals):
                self.findings.append(
                    f"quantized int8 content accumulated ({name}) without a "
                    f"dequantization scale at {site} [rule: int8-dequant]"
                )

    # -- carry checks ------------------------------------------------------
    def _check_loop_carries(self, eqn, body, carry_vals, n_consts, n_carry,
                            sub_path, kind, body_offset=None):
        # body.outvars lead with the carries for both scan and while
        for ci in range(n_carry):
            outvar = body.outvars[ci]
            dtype = _dtype_of(getattr(outvar, "aval", None))
            if not _is_sub_f32(dtype):
                continue
            arith = _producing_arithmetic(body, outvar)
            if arith is not None:
                self.findings.append(
                    f"{dtype} loop carry #{ci} of {kind} at "
                    f"{'/'.join(sub_path)} accumulates through "
                    f"{arith.primitive.name} — (acc, m, l)/lse-class "
                    f"carries must be float32 [rule: f32-accumulator-flow]"
                )


def _producing_arithmetic(jaxpr, outvar, _depth: int = 0):
    """Backtrace ``outvar`` through structure-only primitives: the first
    arithmetic equation on the producing chain, or None when the value is
    a pure pass-through of the loop inputs (a rotating payload — a
    ``ppermute`` of the carry — is movement, not accumulation)."""
    from jax.extend import core as jex_core

    if _depth > 6:
        return None
    producers = {v: e for e in jaxpr.eqns for v in e.outvars}
    seen = set()
    stack = [outvar]
    while stack:
        v = stack.pop()
        if isinstance(v, jex_core.Literal) or id(v) in seen:
            continue
        seen.add(id(v))
        e = producers.get(v)
        if e is None:
            continue  # reached an invar/constvar: pass-through
        name = e.primitive.name
        if name in _ARITH_PRIMS:
            return e
        if name in _TRANSPARENT_PRIMS:
            stack.extend(a for a in e.invars
                         if not isinstance(a, jex_core.Literal))
            continue
        # control flow: look through the sub-jaxpr outputs feeding v
        subs = []
        for val in e.params.values():
            subs.extend(_sub_closed_jaxprs(val))
        if subs:
            try:
                pos = list(e.outvars).index(v)
            except ValueError:
                pos = None
            for sub in subs:
                if pos is not None and pos < len(sub.outvars):
                    hit = _producing_arithmetic(sub, sub.outvars[pos],
                                                _depth + 1)
                    if hit is not None:
                        return hit
            stack.extend(a for a in e.invars
                         if not isinstance(a, jex_core.Literal))
            continue
        # unknown leaf primitive (erf, sin, a future custom op): treat as
        # arithmetic — a carry produced by computation the walker cannot
        # classify must FLAG, not silently pass (only the listed
        # structure-only primitives are pass-through)
        return e
    return None


def count_int8_quantize_ops(closed_jaxpr, *, skip_pallas: bool = True) -> int:
    """Number of float→int8 quantization casts in a program.

    Counts ``convert_element_type`` equations whose input is float and
    whose output is int8 — the one cast every absmax codec in
    ``ops/quant.py`` ends with (bool/int flag casts don't match).  Kernel
    bodies are skipped by default (``skip_pallas``): the in-kernel ``p``
    quantization is per-tile tile math, not a payload quantization.

    This is the requant pin behind the dequant-free ring composition
    (``docs/precision.md``): a counter-rotated int8 ring with
    ``compute_dtype="int8"`` must quantize each KV payload exactly ONCE
    at ring entry (2 casts — k and v) plus one q cast per hop's launcher;
    a dequant→requant round trip would add two more per hop and fails the
    pinned count (``tests/test_quant.py``).
    """

    def walk(jaxpr) -> int:
        n = 0
        for eqn in jaxpr.eqns:
            name = eqn.primitive.name
            if name == "pallas_call" and skip_pallas:
                continue
            if name == "convert_element_type":
                in_dtype = _dtype_of(getattr(eqn.invars[0], "aval", None))
                out_dtype = _dtype_of(getattr(eqn.outvars[0], "aval", None))
                if _is_float(in_dtype) and _is_int8(out_dtype):
                    n += 1
            for v in eqn.params.values():
                for sub in _sub_closed_jaxprs(v):
                    n += walk(sub)
        return n

    return walk(_as_jaxpr(closed_jaxpr))


def audit_precision_flow(fn: Callable, *args, label: str | None = None,
                         ) -> list[str]:
    """Trace ``fn(*args)`` and run the precision-flow lattice over the
    jaxpr.  Returns one-line violations (empty = every reduction, dot
    accumulation, and loop carry on the traced paths executes at f32, and
    every quantized payload is dequantized before accumulation)."""
    import jax

    label = label or getattr(fn, "__name__", str(fn))
    closed = jax.make_jaxpr(fn)(*args)
    return PrecisionFlow().run(closed, label=label)


def run_precision_suite() -> list[tuple[str, list[str]]]:
    """The package-wide precision audit behind ``check_contracts.py
    --dataflow`` and the ``python -m ring_attention_tpu.analysis``
    self-run: both flash paths (XLA scan and Pallas kernels, forward AND
    backward through their custom_vjps), the int8 hop-compression
    quantize→hop→dequantize→accumulate chain, and the counter-rotation
    backward's packed circulation.  Mesh-free (the ring entry's
    collective structure is the divergence checker's job); tiny bf16
    shapes; make_jaxpr only — no compile, any backend.
    """
    import jax
    import jax.numpy as jnp

    from ..ops import flash, pallas_flash
    from ..parallel.collectives import (
        dequantize_ring_payload,
        quantize_ring_payload,
    )
    from ..parallel.ring import _pack_counter, _unpack_counter

    checks: list[tuple[str, list[str]]] = []
    b, h, hk, n, d = 1, 2, 1, 32, 8
    rng = jax.random.PRNGKey(0)
    q = jax.random.normal(rng, (b, h, n, d), jnp.bfloat16)
    kv = jax.random.normal(rng, (b, hk, n, d), jnp.bfloat16)

    def xla_step(q, k, v):
        return jax.grad(
            lambda q, k, v: flash.flash_attention(
                q, k, v, causal=True, bucket_size=8, window=16,
            ).astype(jnp.float32).sum(),
            argnums=(0, 1, 2),
        )(q, k, v)

    checks.append((
        "precision: xla flash fwd+bwd",
        audit_precision_flow(xla_step, q, kv, kv, label="flash_attention"),
    ))

    def pallas_step(q, k, v):
        return jax.grad(
            lambda q, k, v: pallas_flash.pallas_flash_attention(
                q, k, v, causal=True, interpret=True,
            ).astype(jnp.float32).sum(),
            argnums=(0, 1, 2),
        )(q, k, v)

    checks.append((
        "precision: pallas flash fwd+bwd (kernel jaxprs)",
        audit_precision_flow(pallas_step, q, kv, kv,
                             label="pallas_flash_attention"),
    ))

    def int8_hop(q, k, v):
        # the hop-compression chain: quantize once at ring entry, hop
        # (ppermute elided — movement is taint-neutral), dequantize,
        # accumulate — the int8-dequant rule's real positive path
        handle = quantize_ring_payload(k, v)
        kx, vx = dequantize_ring_payload(handle, q.dtype)
        carry = flash.init_carry(b, hk, h // hk, n, d, like=q)
        carry = flash.attend_blocks(
            q, kx, vx, carry, scale=d ** -0.5, bucket_size=8,
            causal_offset=0,
        )
        out, lse = flash.finalize(carry)
        return out.sum() + lse.sum()

    checks.append((
        "precision: int8 hop quantize->dequant->accumulate",
        audit_precision_flow(int8_hop, q, kv, kv, label="int8_hop"),
    ))

    def counter_pack(q, k, v, do):
        # the counter-rotation backward circulates ONE f32 pack
        # [q|acc|m|l]; prove the pack/unpack round-trip plus the
        # backward accumulation stay f32 under bf16 q/do
        acc = jnp.zeros((b, h, n, d), jnp.float32)
        m = jnp.zeros((b, h, n), jnp.float32)
        l = jnp.ones((b, h, n), jnp.float32)
        pack = _pack_counter(q, acc, m, l)
        qx, acc, m, l = _unpack_counter(pack, d, q.dtype)
        lse = (m + jnp.log(l)).reshape(b, hk, h // hk, n)
        delta = (do.astype(jnp.float32) * acc).sum(-1).reshape(
            b, hk, h // hk, n
        )
        dq, dk, dv = flash.flash_backward_blocks(
            do, qx, k, v, lse, delta, scale=d ** -0.5, bucket_size=8,
            causal_offset=0,
        )
        return dq.sum() + dk.sum() + dv.sum()

    checks.append((
        "precision: counter-rotation bwd pack",
        audit_precision_flow(counter_pack, q, kv, kv, q,
                             label="counter_bwd_pack"),
    ))

    checks.append((
        "precision: pallas decode q8 (quantized cache)",
        audit_precision_flow(
            lambda q, k, v: pallas_flash.pallas_flash_decode_q8(
                q[:, :, :1], pallas_flash.quantize_kv_cache(k, v),
                interpret=True,
            )[0].astype(jnp.float32).sum(),
            q, kv, kv, label="pallas_flash_decode_q8",
        ),
    ))

    # the int8 COMPUTE path (PR 13): quantized QK^T/PV inside the flash
    # kernels — the walker descends into the kernel jaxprs and must see
    # every int8 matmul output meet its scale multiply before any
    # reduction/accumulation, and the f32 (acc, m, l) refs untouched
    def pallas_q8_step(q, k, v):
        return jax.grad(
            lambda q, k, v: pallas_flash.pallas_flash_attention(
                q, k, v, causal=True, interpret=True, compute_dtype="int8",
            ).astype(jnp.float32).sum(),
            argnums=(0, 1, 2),
        )(q, k, v)

    checks.append((
        "precision: pallas flash int8 compute fwd + bf16 bwd",
        audit_precision_flow(pallas_q8_step, q, kv, kv,
                             label="pallas_flash_attention[int8]"),
    ))

    from ..ops import quant

    def q8_hop_feed(q, k, v):
        # the dequant-free ring composition: pack once with kernel-ready
        # v scales, feed the int8 kernel DIRECTLY (no dequant→requant),
        # finalize from the f32 partials
        payload = quant.pack_kv(k, v, v_block=8)
        feed = quant.payload_kernel_feed(payload, 8)
        p = pallas_flash.pallas_flash_partials(
            q, None, None, scale=d ** -0.5, causal_offset=0,
            compute_dtype="int8", kv_quantized=feed, block_q=8, block_k=8,
            interpret=True,
        )
        out, lse = pallas_flash.finalize_partials(p)
        return out.sum() + lse.sum()

    checks.append((
        "precision: int8 hop payload -> dequant-free kernel feed",
        audit_precision_flow(q8_hop_feed, q, kv, kv, label="q8_hop_feed"),
    ))
    return checks


# ---------------------------------------------------------------------------
# Pass 2: SPMD divergence
# ---------------------------------------------------------------------------

# Collective primitives whose cross-rank sequence must be convergent.
COLLECTIVE_PRIMS = {
    "ppermute", "pbroadcast", "all_to_all", "all_gather",
    "all_gather_invariant", "psum", "psum_invariant", "pmax", "pmin",
    "reduce_scatter", "psum_scatter", "pshuffle",
}

_COLLECTIVE_PARAM_KEYS = ("axis_name", "axes", "perm", "split_axis",
                          "concat_axis", "axis_index_groups", "tiled")


def _collective_element(eqn) -> tuple:
    aval = getattr(eqn.invars[0], "aval", None) if eqn.invars else None
    params = []
    for key in _COLLECTIVE_PARAM_KEYS:
        if key in eqn.params:
            params.append((key, repr(eqn.params[key])))
    return (
        eqn.primitive.name,
        tuple(params),
        tuple(getattr(aval, "shape", ())),
        str(getattr(aval, "dtype", "?")),
    )


@dataclass
class _DivergenceScan:
    findings: list[str] = field(default_factory=list)


def _signature(jaxpr, state: _DivergenceScan, path: tuple) -> tuple:
    """Ordered collective signature of one jaxpr, recursing into control
    flow.  Emits findings into ``state`` for divergent cond branches and
    communicating while loops as it goes; a cond whose branches agree
    contributes that agreed sequence to the enclosing signature."""
    out: list = []
    for idx, eqn in enumerate(jaxpr.eqns):
        name = eqn.primitive.name
        if name in COLLECTIVE_PRIMS:
            out.append(_collective_element(eqn))
        elif name == "scan":
            body_sig = _signature(_as_jaxpr(eqn.params["jaxpr"]), state,
                                  path + (f"scan#{idx}",))
            if body_sig:
                out.append(("scan", int(eqn.params["length"]), body_sig))
        elif name == "while":
            for key, leg in (("cond_jaxpr", "cond"), ("body_jaxpr", "body")):
                leg_sig = _signature(_as_jaxpr(eqn.params[key]), state,
                                     path + (f"while#{idx}/{leg}",))
                if leg_sig:
                    kinds = sorted(_sig_kinds(leg_sig))
                    state.findings.append(
                        f"collective(s) {kinds} inside a lax.while_loop "
                        f"{leg} at {'/'.join(path) or 'top'} — the trip "
                        f"count is rank-local, so the collective sequence "
                        f"cannot be proven convergent "
                        f"[rule: while-collective]"
                    )
        elif name == "cond":
            sigs = [
                _signature(_as_jaxpr(br), state,
                           path + (f"cond#{idx}/branch{bi}",))
                for bi, br in enumerate(eqn.params["branches"])
            ]
            for bi, sig in enumerate(sigs[1:], start=1):
                if sig != sigs[0]:
                    state.findings.append(
                        f"cond#{idx} at {'/'.join(path) or 'top'}: branch 0 "
                        f"issues {_sig_str(sigs[0])} but branch {bi} issues "
                        f"{_sig_str(sig)} — ranks taking different branches "
                        f"deadlock on the first mismatch "
                        f"[rule: branch-collective-divergence]"
                    )
            if sigs and sigs[0]:
                out.extend(sigs[0])
        else:
            for v in eqn.params.values():
                for sub in _sub_closed_jaxprs(v):
                    out.extend(_signature(sub, state,
                                          path + (f"{name}#{idx}",)))
    return tuple(out)


def _sig_kinds(sig: tuple) -> set[str]:
    """Collective primitive names in a signature, looking through the
    structural ``("scan", length, body_sig)`` wrappers so a diagnostic
    names the actual collective, never "scan"."""
    kinds: set[str] = set()
    for e in sig:
        if isinstance(e, tuple) and e and e[0] == "scan" and len(e) == 3 \
                and isinstance(e[2], tuple):
            kinds |= _sig_kinds(e[2])
        elif isinstance(e, tuple) and e and isinstance(e[0], str):
            kinds.add(e[0])
    return kinds


def _sig_str(sig: tuple) -> str:
    if not sig:
        return "[no collectives]"
    return "[" + ", ".join(
        e[0] if isinstance(e, tuple) and isinstance(e[0], str) else str(e)
        for e in sig[:4]
    ) + (", ..." if len(sig) > 4 else "") + "]"


def collective_signature(closed_jaxpr) -> tuple:
    """The program's ordered collective sequence (kind, axes/perm params,
    operand shape, dtype), with scan bodies kept structural
    (``("scan", length, body_sig)``) so equality is well-defined at any
    trip count.  Divergence findings raised during construction are
    discarded here — use :func:`check_spmd_divergence` for the audit."""
    return _signature(_as_jaxpr(closed_jaxpr), _DivergenceScan(), ())


def check_spmd_divergence(closed_jaxpr, label: str = "") -> list[str]:
    """Prove the program's collective schedule is branch-invariant: every
    ``lax.cond``'s branches issue identical collective sequences (kind,
    axes, operand shapes, order — scan-aware) and no ``lax.while_loop``
    communicates.  Returns one-line violations naming the cond/branch."""
    state = _DivergenceScan()
    _signature(_as_jaxpr(closed_jaxpr), state, ())
    findings = list(dict.fromkeys(state.findings))
    if label:
        findings = [f"{label}: {f}" for f in findings]
    return findings


def run_divergence_suite(strategies=None, directions=None,
                         ) -> list[tuple[str, list[str]]]:
    """The SPMD divergence proof over every sequence-parallel strategy:
    trace each contract entry (both impls where they differ) and require
    a branch-invariant collective sequence.  Needs multiple simulated
    devices (``--xla_force_host_platform_device_count``); pure
    ``make_jaxpr`` — no compile."""
    import jax

    from . import contracts

    checks: list[tuple[str, list[str]]] = []
    if strategies is None:
        strategies = list(contracts.CONTRACTS)
    for strategy in strategies:
        contract = contracts.CONTRACTS[strategy]
        mesh = contracts.default_mesh(strategy)
        dirs = directions or contract.get("directions", ("fwd", "fwdbwd"))
        impls = {contract["impl"]}
        if "scan" in contract:
            impls.add("xla")
        for impl in sorted(impls):
            fn, args, _ = contracts.build_entry(strategy, mesh, impl=impl)
            for direction in dirs:
                dfn = contracts._direction_fn(fn, direction)
                label = f"{strategy}/{impl}/{direction}"
                checks.append((
                    f"divergence: {label}",
                    check_spmd_divergence(jax.make_jaxpr(dfn)(*args), label),
                ))
    return checks
