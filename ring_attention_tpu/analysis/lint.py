"""Repo-native AST lint: the rules this codebase keeps re-learning by hand.

Every rule here encodes a failure mode that actually bit (or nearly bit) a
past PR, with the shim/convention that prevents it:

  RA001  ``jax.experimental.shard_map`` / ``jax.shard_map`` used directly —
         must go through ``utils/compat.shard_map``.  The jax-0.4.x
         container has neither ``check_vma`` nor a replication checker that
         understands ``checkpoint_name`` residuals; a direct call crashes
         the whole suite there (see ``utils/compat.py``).
  RA002  ``jax.jit`` used directly in library code — must go through
         ``utils/compat.jit``, which degrades ``donate_argnums`` gracefully
         on jax builds that reject it (and keeps the door open for
         package-wide jit policy).
  RA003  ``pl.pallas_call`` without ``name=`` — unnamed kernels show up in
         XProf as ``custom-call`` soup; every launch must carry its stable
         trace name (docs/observability.md).
  RA004  collective (``ppermute`` / ``all_to_all`` / ``all_gather`` /
         ``psum`` / ``pmax`` / ``pmin`` / ``psum_scatter``) issued outside a
         ``jax.named_scope`` block — unattributable communication time in
         traces.
  RA005  host-side entropy (``time.time`` / ``random.*`` / ``np.random.*``)
         in traced-code subpackages (``ops/``, ``parallel/``, ``models/``) —
         a host clock or RNG read inside a traced function is baked in at
         trace time and silently constant across steps (``jax.random`` is
         fine: it is traced).
  RA006  ``print`` in library code — library output goes through
         ``warnings`` / telemetry, never stdout.
  RA007  public attention entry point (module-level ``def f(q, k, v, ...)``)
         that never calls ``utils/validate.check_attention_args`` — layout
         bugs then surface as einsum errors deep in the kernels instead of
         a one-line ValueError at the API boundary.
  RA008  ``Telemetry.observe`` in library code outside a ``with
         ...collecting()`` block, or with a metric name lacking a unit
         suffix (``_bytes``/``_sec``/``_count``/``_frac``).  ``observe``
         only lands when a collector is active AT THE SAME TRACE LEVEL —
         a library-level call outside any ``collecting()`` silently drops
         every scalar it claims to record; and an unsuffixed name
         ("kv_hop") reads as whatever unit the dashboard author guesses.
  RA009  host ``np.`` / ``numpy.`` calls in traced-code subpackages.  A
         numpy function applied to a traced value either raises a
         TracerArrayConversionError deep in the call or silently
         constant-folds at trace time (the jaxpr then carries a baked-in
         literal — visible to ``analysis/dataflow.py``'s walker as a
         constant where an operation should be); a numpy call on
         genuinely static trace-time data (device topology, tile tables)
         is legitimate and carries a reasoned allow.  ``np.random.*``
         stays RA005's.
  RA010  Pallas grid tables or hop skip-predicates constructed outside
         the ``band_plan()`` / mask-algebra seam.  Calling the private
         table/offset/skip constructors (``_band_tables`` /
         ``_band_tile_count`` / ``_hop_offsets`` / ``_stream_offsets`` /
         ``_static_hop_band`` / ``_counter_static_band`` /
         ``_hop_has_work`` / ``_tile_has_work`` / ``_tile_is_edge``)
         from outside their home modules (``ops/pallas_flash.py``,
         ``parallel/ring.py``), ``masks.py`` (the algebra's lowering),
         or ``analysis/`` (the certifier) builds a skip grid the
         coverage prover never sees — the exact bypass that would dodge
         certification.  New grids go through ``band_plan()`` or the
         mask algebra, which certify; anything else carries a reasoned
         allow.
  RA011  signal/process-kill primitives (``signal.signal`` /
         ``signal.setitimer`` / ``os.kill`` / ``os.killpg`` /
         ``os._exit``) outside the elastic runtime (``elastic/``) or
         ``utils/resilience.py``.  Preemption semantics — drain the
         in-flight step, save, dump the incident, THEN exit — live in
         ``elastic.PreemptionGuard``; an ad-hoc ``signal.signal``
         elsewhere silently replaces the guard's handler and a stray
         ``os.kill``/``os._exit`` bypasses the drain entirely (the
         chaos harness's hard-death points are the ONE sanctioned
         user).  Legitimate uses elsewhere (liveness probes) carry a
         reasoned allow.
  RA012  raw int8 quant/dequant arithmetic outside the ``ops/quant.py``
         seam — any arithmetic use of the int8 full-scale constant 127
         (the absmax divide, the round-and-clip scale, the dequant
         multiply) in library code.  Three call sites grew three copies
         of this codec across PRs (decode cache, hop payload, kernel
         compute); PR 13 collapsed them into ``ops/quant.py`` and this
         rule keeps a fourth from forking the convention — a codec with
         a subtly different scale or clip silently breaks payload
         interchangeability and the precision auditor's dequant model.
         Quantize through the seam; a genuinely unrelated 127 carries a
         reasoned allow.

  RA013  remote-DMA / semaphore primitives (``make_async_remote_copy`` /
         ``make_async_copy`` / ``semaphore_signal`` / ``semaphore_wait`` /
         ``get_barrier_semaphore`` / ``SemaphoreType``) outside the fused
         ring kernel module (``ops/pallas_ring.py``).  The fused ring's
         correctness rests on ONE signal/wait protocol — the send-grant
         barrier and per-slot DMA semaphores that
         ``analysis/contracts.py::check_fused_ring_contract`` pins by
         exact count from the lowered module.  A second module issuing
         raw semaphore ops can deadlock the ring (an unmatched signal
         leaves a neighbor waiting forever) and silently invalidates the
         counted contract; new in-kernel communication goes through the
         fused module's seam, anything else carries a reasoned allow.

  RA014  raw host clocks (``time.time`` / ``time.monotonic`` /
         ``time.perf_counter`` / ...) in the observability-instrumented
         subpackages (``elastic/``, ``utils/``) outside the timestamp
         seam (``utils/tracing.py``).  Every emitted timestamp must
         route through the seam's ``monotonic_wall()``/``monotonic()``/
         ``perf_counter()`` helpers so the cluster-timeline merger can
         correct clocks consistently; a module stamping rows with its
         own ``time.*`` call produces offsets the merger never sees.
         Deadline arithmetic and filesystem-mtime comparisons carry a
         reasoned allow.

  RA015  remote-DMA / semaphore primitive call site inside the fused
         kernel module that no declared ``PROTOCOL`` row covers.  RA013
         fences the primitives to ``ops/pallas_ring.py``; RA015 tightens
         that file fence to a verified-seam fence: every
         ``make_async_*copy`` / ``semaphore_*`` / ``get_barrier_semaphore``
         call must live inside a function named by a ``PROTOCOL`` row's
         ``fn`` field, because ``analysis/schedverify.py`` model-checks
         exactly the declared rows (races, deadlock, semaphore drain) and
         cross-checks them site-by-site against the traced kernel.  A
         primitive issued from an undeclared function is protocol the
         model never saw — the exact blind spot PR 18's review bugs hid
         in.  Declare the row (and re-run the verifier) or carry a
         reasoned allow.  The table must stay a literal assignment
         (``PROTOCOL = (...)``): if it cannot be parsed from the AST,
         every site is flagged.

Silencing: append ``# ra: allow(RA00X reason...)`` to the flagged line
(for RA007, the ``def`` line).  The reason is mandatory — a bare allow is
itself a violation.  See docs/static_analysis.md.

Stdlib-only on purpose: on a box where jax itself cannot import, run this
module as a plain script (``python ring_attention_tpu/analysis/lint.py``)
— the ``-m`` form imports the package ``__init__`` chain, which needs jax.
"""

from __future__ import annotations

import ast
import re
from dataclasses import dataclass
from pathlib import Path

# Subpackages whose modules are traced code end-to-end (RA005 scope).
TRACED_SUBPACKAGES = ("ops", "parallel", "models")

# The shim module: the one place allowed to touch the raw APIs.
SHIM_MODULE = "utils/compat.py"

COLLECTIVE_CALLS = {
    "ppermute",
    "all_to_all",
    "all_gather",
    "all_gather_invariant",
    "psum",
    "pmax",
    "pmin",
    "psum_scatter",
    "pshuffle",
}

HOST_TIME_ATTRS = {"time", "time_ns", "perf_counter", "monotonic", "process_time"}

# RA010: the private grid-table / hop-skip constructors, and the modules
# that ARE the seam (their homes, the mask algebra's lowering, and the
# analysis passes that certify them).
GRID_SEAM_CALLS = {
    "_band_tables",
    "_band_tile_count",
    "_hop_offsets",
    "_stream_offsets",
    "_static_hop_band",
    "_counter_static_band",
    "_hop_has_work",
    "_tile_has_work",
    "_tile_is_edge",
}
GRID_SEAM_MODULES = (
    "ops/pallas_flash.py",
    "parallel/ring.py",
    "ring_attention_tpu/masks.py",
    "analysis/",
)

# RA008: metric-name unit suffixes (docs/observability.md glossary)
METRIC_UNIT_SUFFIXES = ("_bytes", "_sec", "_count", "_frac")

# RA011: signal-handling / process-kill primitives, and the modules that
# own preemption semantics (the elastic runtime + the resilience layer).
SIGNAL_CALLS = {
    "signal.signal",
    "signal.setitimer",
    "os.kill",
    "os.killpg",
    "os._exit",
}
SIGNAL_MODULES = (
    "ring_attention_tpu/elastic/",
    "utils/resilience.py",
)

# RA013: the remote-DMA / semaphore primitive surface, and the one module
# (the fused ring kernel) allowed to issue it — its signal/wait protocol
# is pinned by exact count in analysis/contracts.py.
REMOTE_DMA_CALLS = {
    "make_async_remote_copy",
    "make_async_copy",
    "semaphore_signal",
    "semaphore_wait",
    "get_barrier_semaphore",
}
FUSED_KERNEL_MODULE = "ops/pallas_ring.py"

# RA015: the declared-protocol seam inside the fused module — the literal
# table whose rows name (via their "fn" field) the only functions allowed
# to issue REMOTE_DMA_CALLS; analysis/schedverify.py model-checks exactly
# those rows.
PROTOCOL_TABLE_NAME = "PROTOCOL"


def _protocol_fns(tree: ast.Module) -> frozenset[str]:
    """Function names declared by the module's literal ``PROTOCOL`` table
    (empty when the assignment is missing or not a pure literal — which
    flags every primitive site, keeping the table honest)."""
    for node in tree.body:
        if not isinstance(node, ast.Assign):
            continue
        if not any(isinstance(t, ast.Name) and t.id == PROTOCOL_TABLE_NAME
                   for t in node.targets):
            continue
        try:
            rows = ast.literal_eval(node.value)
        except (ValueError, SyntaxError):
            return frozenset()
        return frozenset(
            row["fn"] for row in rows
            if isinstance(row, dict) and isinstance(row.get("fn"), str)
        )
    return frozenset()

# RA012: the one module allowed to spell the int8 full-scale constant in
# arithmetic (every quant/dequant codec lives there).
QUANT_SEAM_MODULE = "ops/quant.py"
INT8_FULL_SCALE = 127  # ra: allow(RA012 the rule's own definition of the constant)

# RA014: subpackages whose host-side timestamps must route through the
# tracing seam (the merger's clock-offset correction needs ONE source of
# wall/monotonic pairs), and the seam module itself.
TIMESTAMP_SCOPES = (
    "ring_attention_tpu/elastic/",
    "ring_attention_tpu/utils/",
)
TIMESTAMP_SEAM_MODULE = "utils/tracing.py"

_ALLOW_RE = re.compile(r"#\s*ra:\s*allow\(\s*(RA\d{3})\b([^)]*)\)")


@dataclass(frozen=True)
class Violation:
    path: str
    line: int
    rule: str
    message: str

    def __str__(self) -> str:  # the one-line diagnostic format
        return f"{self.path}:{self.line}: {self.rule}: {self.message}"


def _attr_chain(node: ast.AST) -> str:
    """Dotted name of an attribute chain (``jax.experimental.shard_map``)."""
    parts: list[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return ""


def _allowed(source_lines: list[str], lineno: int, rule: str) -> tuple[bool, bool]:
    """(allowed, bare) — whether the line carries an ``# ra: allow`` pragma
    for ``rule``, and whether the pragma is missing its reason."""
    if 1 <= lineno <= len(source_lines):
        m = _ALLOW_RE.search(source_lines[lineno - 1])
        if m and m.group(1) == rule:
            return True, not m.group(2).strip()
    return False, False


class _Linter(ast.NodeVisitor):
    def __init__(self, path: str, rel: str, source: str):
        self.path = path
        self.rel = rel
        self.lines = source.splitlines()
        self.violations: list[Violation] = []
        self.scope_depth = 0  # nesting inside `with jax.named_scope(...)`
        self.collecting_depth = 0  # nesting inside `with ....collecting()`
        self.is_shim = rel.replace("\\", "/").endswith(SHIM_MODULE)
        self.in_grid_seam = any(
            m in rel.replace("\\", "/") for m in GRID_SEAM_MODULES
        )
        self.in_signal_scope = any(
            m in rel.replace("\\", "/") for m in SIGNAL_MODULES
        )
        self.in_quant_seam = rel.replace("\\", "/").endswith(QUANT_SEAM_MODULE)
        self.in_fused_seam = rel.replace("\\", "/").endswith(
            FUSED_KERNEL_MODULE
        )
        self.fn_stack: list[str] = []  # enclosing FunctionDef names (RA015)
        self.protocol_fns: frozenset[str] = frozenset()
        self.traced_pkg = any(
            rel.replace("\\", "/").startswith(f"ring_attention_tpu/{p}/")
            or f"/{p}/" in rel.replace("\\", "/")
            for p in TRACED_SUBPACKAGES
        )
        self.in_time_scope = any(
            m in rel.replace("\\", "/") for m in TIMESTAMP_SCOPES
        ) and not rel.replace("\\", "/").endswith(TIMESTAMP_SEAM_MODULE)

    def flag(self, node: ast.AST, rule: str, message: str) -> None:
        lineno = getattr(node, "lineno", 1)
        allowed, bare = _allowed(self.lines, lineno, rule)
        if allowed and not bare:
            return
        if allowed and bare:
            message = f"bare '# ra: allow({rule})' — a reason is mandatory"
        self.violations.append(Violation(self.rel, lineno, rule, message))

    # -- RA001 / RA002: shim bypass -----------------------------------
    def visit_Import(self, node: ast.Import) -> None:
        for alias in node.names:
            if alias.name.startswith("jax.experimental.shard_map") and not self.is_shim:
                self.flag(node, "RA001",
                          "import of jax.experimental.shard_map bypasses "
                          "utils/compat.shard_map (breaks on jax 0.4.x)")
        self.generic_visit(node)

    def visit_ImportFrom(self, node: ast.ImportFrom) -> None:
        mod = node.module or ""
        if not self.is_shim:
            if mod.startswith("jax.experimental.shard_map") or (
                mod == "jax.experimental"
                and any(a.name == "shard_map" for a in node.names)
            ):
                self.flag(node, "RA001",
                          "import of jax.experimental.shard_map bypasses "
                          "utils/compat.shard_map (breaks on jax 0.4.x)")
            if mod == "jax" and any(a.name == "jit" for a in node.names):
                self.flag(node, "RA002",
                          "'from jax import jit' bypasses utils/compat.jit")
            if mod == "jax" and any(a.name == "shard_map" for a in node.names):
                self.flag(node, "RA001",
                          "'from jax import shard_map' bypasses "
                          "utils/compat.shard_map")
        self.generic_visit(node)

    def visit_Attribute(self, node: ast.Attribute) -> None:
        if not self.is_shim:
            chain = _attr_chain(node)
            if chain in ("jax.shard_map", "jax.experimental.shard_map",
                         "jax.experimental.shard_map.shard_map"):
                self.flag(node, "RA001",
                          f"{chain} bypasses utils/compat.shard_map "
                          "(breaks on jax 0.4.x)")
                return  # don't re-flag the chain's own sub-attributes
            if chain == "jax.jit":
                self.flag(node, "RA002",
                          "jax.jit bypasses utils/compat.jit "
                          "(donation degradation, package jit policy)")
        if (not self.in_fused_seam
                and "SemaphoreType" in _attr_chain(node).split(".")):
            self.flag(node, "RA013",
                      "SemaphoreType outside ops/pallas_ring.py — semaphore "
                      "scratch allocation belongs to the fused ring's "
                      "counted signal/wait protocol (contracts.py pins it)")
            return  # don't re-flag the chain's own sub-attributes
        self.generic_visit(node)

    # -- RA003..RA007: calls ------------------------------------------
    def visit_Call(self, node: ast.Call) -> None:
        func = node.func
        name = func.attr if isinstance(func, ast.Attribute) else (
            func.id if isinstance(func, ast.Name) else ""
        )

        if name == "pallas_call":
            if not any(kw.arg == "name" for kw in node.keywords):
                self.flag(node, "RA003",
                          "pl.pallas_call without name= — kernel is "
                          "unattributable in XProf traces")

        if name in GRID_SEAM_CALLS and not self.in_grid_seam:
            self.flag(node, "RA010",
                      f"grid/skip constructor {name}() outside the "
                      "band_plan()/mask-algebra seam — this skip grid "
                      "dodges the coverage certifier; lower through "
                      "band_plan() or ring_attention_tpu.masks")

        if isinstance(func, ast.Attribute) and not self.in_signal_scope:
            sig_chain = _attr_chain(func)
            if sig_chain in SIGNAL_CALLS:
                self.flag(node, "RA011",
                          f"{sig_chain}() outside elastic//resilience.py — "
                          "preemption semantics (drain, save, incident "
                          "dump) live in elastic.PreemptionGuard/chaos; "
                          "an ad-hoc handler or kill bypasses the drain")

        if name in REMOTE_DMA_CALLS and not self.in_fused_seam:
            self.flag(node, "RA013",
                      f"remote-DMA/semaphore primitive {name}() outside "
                      "ops/pallas_ring.py — the fused ring owns the one "
                      "counted signal/wait protocol (contracts.py pins "
                      "it); a stray semaphore op can deadlock the ring")
        elif (name in REMOTE_DMA_CALLS and self.in_fused_seam
                and not any(f in self.protocol_fns for f in self.fn_stack)):
            self.flag(node, "RA015",
                      f"remote-DMA/semaphore primitive {name}() outside a "
                      "declared PROTOCOL row — schedverify model-checks "
                      "only the rows' fn seams (races/deadlock/drain); "
                      "declare the row and re-run the verifier, or allow "
                      "with a reason")

        if name in COLLECTIVE_CALLS and self.scope_depth == 0:
            self.flag(node, "RA004",
                      f"collective lax.{name} outside jax.named_scope — "
                      "communication time unattributable in traces")

        if self.traced_pkg:
            chain = _attr_chain(func) if isinstance(func, ast.Attribute) else ""
            if chain.startswith(("time.",)) and name in HOST_TIME_ATTRS:
                self.flag(node, "RA005",
                          f"host clock {chain}() in traced code — constant "
                          "after trace; pass times in as arguments")
            elif chain.startswith(("random.", "np.random.", "numpy.random.")):
                self.flag(node, "RA005",
                          f"host RNG {chain}() in traced code — constant "
                          "after trace; use jax.random with an explicit key")
            elif chain.startswith(("np.", "numpy.")):
                self.flag(node, "RA009",
                          f"host numpy {chain}() in traced code — on a "
                          "traced value this raises or silently constant-"
                          "folds at trace time; use jnp, or allow with a "
                          "reason for provably static trace-time data")

        if self.in_time_scope and isinstance(func, ast.Attribute):
            chain = _attr_chain(func)
            if chain.startswith("time.") and name in HOST_TIME_ATTRS:
                self.flag(node, "RA014",
                          f"raw host clock {chain}() outside the "
                          "utils/tracing.py timestamp seam — emitted "
                          "timestamps must come from the seam's helpers "
                          "(monotonic_wall/monotonic/perf_counter) so "
                          "the cluster-timeline merger's clock-offset "
                          "correction covers them; deadline arithmetic "
                          "or mtime comparisons carry a reasoned allow")

        if (name == "print" and isinstance(func, ast.Name)
                and not self.rel.endswith("__main__.py")):  # __main__ IS a CLI
            self.flag(node, "RA006",
                      "print() in library code — use warnings or telemetry")

        if (name == "observe" and isinstance(func, ast.Attribute)
                and not self.rel.replace("\\", "/").endswith(
                    "utils/telemetry.py")):  # the registry's own module
            if self.collecting_depth == 0:
                self.flag(node, "RA008",
                          "Telemetry.observe outside a collecting() block — "
                          "observations only land when a collector is "
                          "active at the same trace level; this scalar "
                          "silently drops")
            metric = node.args[0] if node.args else None
            if (isinstance(metric, ast.Constant)
                    and isinstance(metric.value, str)
                    and not metric.value.endswith(METRIC_UNIT_SUFFIXES)):
                self.flag(node, "RA008",
                          f"metric name {metric.value!r} lacks a unit "
                          f"suffix ({'/'.join(METRIC_UNIT_SUFFIXES)}) — "
                          "an unitless series reads as whatever the "
                          "dashboard author guesses")

        self.generic_visit(node)

    # -- RA012: int8 quant arithmetic outside the seam ------------------
    def visit_Constant(self, node: ast.Constant) -> None:
        if (not self.in_quant_seam
                and isinstance(node.value, (int, float))
                and not isinstance(node.value, bool)
                and abs(node.value) == INT8_FULL_SCALE):
            self.flag(node, "RA012",
                      "int8 full-scale constant 127 outside ops/quant.py — "
                      "raw quant/dequant arithmetic forks the codec seam; "
                      "quantize through ops.quant (or allow with a reason "
                      "if this 127 is unrelated to quantization)")
        self.generic_visit(node)

    def visit_With(self, node: ast.With) -> None:
        def _ctx_is(call_name: str) -> bool:
            return any(
                isinstance(item.context_expr, ast.Call)
                and (
                    (isinstance(item.context_expr.func, ast.Attribute)
                     and item.context_expr.func.attr == call_name)
                    or (isinstance(item.context_expr.func, ast.Name)
                        and item.context_expr.func.id == call_name)
                )
                for item in node.items
            )

        named = _ctx_is("named_scope")
        collecting = _ctx_is("collecting")
        if named:
            self.scope_depth += 1
        if collecting:
            self.collecting_depth += 1
        self.generic_visit(node)
        if named:
            self.scope_depth -= 1
        if collecting:
            self.collecting_depth -= 1

    # -- RA007: entry points must validate ----------------------------
    def _check_entry_point(self, node: ast.FunctionDef) -> None:
        if node.name.startswith("_"):
            return
        first3 = [a.arg for a in node.args.args[:3]]
        if first3 != ["q", "k", "v"]:
            return
        validates = any(
            isinstance(n, ast.Call)
            and (
                (isinstance(n.func, ast.Name)
                 and n.func.id == "check_attention_args")
                or (isinstance(n.func, ast.Attribute)
                    and n.func.attr == "check_attention_args")
            )
            for n in ast.walk(node)
        )
        if not validates:
            self.flag(node, "RA007",
                      f"public entry point {node.name}(q, k, v, ...) never "
                      "calls utils/validate.check_attention_args — layout "
                      "bugs will surface deep in the kernels instead")

    def visit_Module(self, node: ast.Module) -> None:
        if self.in_fused_seam:
            self.protocol_fns = _protocol_fns(node)
        for child in node.body:
            if isinstance(child, ast.FunctionDef):
                self._check_entry_point(child)
        self.generic_visit(node)

    # -- RA015: enclosing-function tracking ----------------------------
    def visit_FunctionDef(self, node: ast.FunctionDef) -> None:
        self.fn_stack.append(node.name)
        self.generic_visit(node)
        self.fn_stack.pop()

    visit_AsyncFunctionDef = visit_FunctionDef


def lint_source(source: str, rel: str, path: str = "") -> list[Violation]:
    """Lint one module's source text; returns violations (possibly empty)."""
    try:
        tree = ast.parse(source)
    except SyntaxError as e:  # a file that cannot parse is its own finding
        return [Violation(rel, e.lineno or 1, "RA000", f"syntax error: {e.msg}")]
    linter = _Linter(path or rel, rel, source)
    linter.visit(tree)
    return sorted(linter.violations, key=lambda v: (v.path, v.line, v.rule))


def lint_file(path: str | Path, root: str | Path | None = None) -> list[Violation]:
    path = Path(path)
    rel = str(path.relative_to(root)) if root else str(path)
    return lint_source(path.read_text(), rel, str(path))


def lint_package(root: str | Path | None = None) -> list[Violation]:
    """Lint every module under ``ring_attention_tpu/`` (the library scope:
    tools/, examples/, benchmarks/ and tests/ are host-side and exempt)."""
    if root is None:
        root = Path(__file__).resolve().parents[2]
    root = Path(root)
    pkg = root / "ring_attention_tpu"
    out: list[Violation] = []
    for path in sorted(pkg.rglob("*.py")):
        if "__pycache__" in path.parts:
            continue
        out.extend(lint_file(path, root))
    return out


def main(argv: list[str] | None = None) -> int:
    import argparse

    parser = argparse.ArgumentParser(
        description="ring-attention-tpu repo-native lint (rules RA001-RA015)"
    )
    parser.add_argument("paths", nargs="*",
                        help="files to lint (default: the whole package)")
    args = parser.parse_args(argv)
    if args.paths:
        violations = []
        for p in args.paths:
            violations.extend(lint_file(p))
    else:
        violations = lint_package()
    for v in violations:
        print(str(v))  # ra: allow(RA006 the lint CLI's own report output)
    if violations:
        print(f"{len(violations)} violation(s)")  # ra: allow(RA006 CLI output)
    return 1 if violations else 0


if __name__ == "__main__":
    raise SystemExit(main())
