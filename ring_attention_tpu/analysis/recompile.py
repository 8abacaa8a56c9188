"""Retrace sentinel + accumulator-dtype / memory-placement audits.

A steady-state train/decode loop must compile each entry point exactly
once; a retrace-per-step (a Python scalar changing dtype, a fresh closure
per step, an unhashable static arg) turns a multi-hour run into a
compile benchmark and is invisible in small tests — each step still
*works*.  The sentinel here counts real XLA compilations two ways:

  - :func:`assert_compiles_once` — drives a jitted callable through a
    multi-step loop with fresh same-shaped inputs and asserts its compile
    cache holds exactly one entry afterwards;
  - :class:`CompileCounter` — a context manager counting backend
    compilations process-wide (via jax's compilation logging), for loops
    that call through several entry points at once.

The dtype audit (:func:`audit_accumulator_dtypes`) pins the numerics
contract the flash kernels are built on: the online-softmax running state
``(acc, m, l)`` accumulates in float32 regardless of the input dtype —
bf16 inputs with bf16 accumulation drift visibly over 262k-token sweeps.
Both the XLA carry and the Pallas partials are checked via ``eval_shape``
(abstract: no kernel runs, works on any backend).

The memory audits close the loop on the million-token knobs
(docs/memory.md), because every one of them fails *silently*: a remat
policy that quietly saves the ``mult*dim`` FFN intermediate still
computes the right numbers, a donated buffer that double-allocates still
trains, and an "offloaded" optimizer state that lands back in HBM still
converges — each just OOMs at the context length the knob was supposed
to unlock.

  - :func:`audit_remat_residuals` — walks the differentiated
    ``remat2`` blocks of a grad jaxpr and flags any saved residual whose
    shape the policy promised to recompute;
  - :func:`audit_donation` — donated inputs must actually alias outputs
    in the compiled executable (``input_output_alias``), not silently
    double-allocate;
  - :func:`audit_host_offload` — outputs declared host-resident must
    report the host memory kind in the compiled output shardings
    (vacuous on backends without a host space, where offload is a
    documented no-op — ``utils/compat.host_memory_kind``).

All run on CPU; ``tools/check_contracts.py --memory`` is the CLI.
"""

from __future__ import annotations

import logging
import re
from dataclasses import dataclass, field

# Loggers that announce an actual backend compilation (cache miss) in the
# jax versions this repo supports; record format pinned by _COMPILE_RE.
_COMPILE_LOGGERS = (
    "jax._src.interpreters.pxla",
    "jax._src.dispatch",
)
_COMPILE_RE = re.compile(r"^Compiling ([^\s]+) with global shapes")


class RetraceError(AssertionError):
    """An entry point compiled more than once across a steady-state loop."""


@dataclass
class CompileCounter:
    """Counts XLA compilations (trace-cache misses) under the context.

    >>> with CompileCounter() as counter:
    ...     for step in range(3):
    ...         train_step(params, batch)
    >>> counter.total  # 1 for a healthy loop
    """

    names: list[str] = field(default_factory=list)

    @property
    def total(self) -> int:
        return len(self.names)

    def __enter__(self) -> "CompileCounter":
        self._handlers = []
        for name in _COMPILE_LOGGERS:
            logger = logging.getLogger(name)
            handler = logging.Handler(level=logging.DEBUG)
            handler.emit = self._emit  # type: ignore[method-assign]
            self._old_levels = getattr(self, "_old_levels", {})
            self._old_levels[name] = logger.level
            if logger.level > logging.DEBUG or logger.level == logging.NOTSET:
                logger.setLevel(logging.DEBUG)
            logger.addHandler(handler)
            self._handlers.append((logger, handler))
        return self

    def _emit(self, record: logging.LogRecord) -> None:
        m = _COMPILE_RE.match(record.getMessage())
        if m:
            self.names.append(m.group(1))

    def __exit__(self, *exc) -> None:
        for logger, handler in self._handlers:
            logger.removeHandler(handler)
            logger.setLevel(self._old_levels[logger.name])


def _cache_size(jitted) -> int | None:
    """Trace-cache entry count of a ``jax.jit``-wrapped callable (None when
    the running jax build does not expose it — callers then fall back to
    the logging counter)."""
    fn = getattr(jitted, "_cache_size", None)
    return fn() if callable(fn) else None


def assert_compiles_once(jitted, make_args, steps: int = 3,
                         label: str | None = None):
    """Drive ``jitted`` through ``steps`` calls with fresh same-shaped args
    and fail unless it compiled exactly once.

    ``make_args(step) -> tuple`` builds each step's arguments — fresh
    arrays each call, the way a real data loader feeds a train loop (a
    sentinel fed the identical array object would miss dtype/weak-type
    churn).  Raises :class:`RetraceError` with a one-line diagnostic
    naming the entry point; returns the loop's compile count on success
    (1 for a cold callable, 0 when this shape was already warmed before
    the loop — both are healthy steady states; pre-existing cache entries
    for *other* shapes are not charged to this loop).
    """
    label = label or getattr(jitted, "__name__", str(jitted))
    # build every step's args up front: array construction can itself
    # trigger tiny compiles that would pollute the fallback counter
    all_args = [make_args(step) for step in range(steps)]
    cache_before = _cache_size(jitted)
    with CompileCounter() as counter:
        for args in all_args:
            jitted(*args)
    cache_after = _cache_size(jitted)
    if cache_before is not None and cache_after is not None:
        compiles = cache_after - cache_before
    else:
        compiles = counter.total
    if compiles > 1:
        raise RetraceError(
            f"{label}: {compiles} compilations across {steps} same-shape "
            f"steps (expected at most 1) — a static arg, weak-typed "
            f"scalar, or fresh closure is forcing a retrace per step "
            f"[rule: compile-once]"
        )
    return compiles


_REMAT_PRIMS = ("remat2", "checkpoint")


def audit_remat_residuals(fn, *args, forbidden, label: str | None = None
                          ) -> list[str]:
    """Flag saved remat residuals the policy claims are recomputed.

    Traces ``jax.grad`` of the scalar-valued ``fn(*args)`` (grad wrt
    argument 0) and walks every *differentiated* ``remat2`` block in the
    jaxpr — the backward half of a checkpointed region, whose operands
    are exactly the residuals the forward saved for it.  Any operand
    whose shape appears in ``forbidden`` (a collection of shape tuples)
    is a policy leak: e.g. a ``(b, n, mult*dim)`` FFN intermediate
    surviving under ``nothing_saveable`` means the config's memory claim
    is fiction even though every value it computes is correct.  Returns
    one-line violations (empty = the forbidden shapes are all recomputed,
    never saved).  Runs at trace level — no compile, any backend.
    """
    import jax

    label = label or getattr(fn, "__name__", str(fn))
    forbidden = {tuple(s) for s in forbidden}
    jaxpr = jax.make_jaxpr(jax.grad(fn))(*args)
    violations: list[str] = []

    def walk(jx) -> None:
        for eqn in jx.eqns:
            if (eqn.primitive.name in _REMAT_PRIMS
                    and eqn.params.get("differentiated")):
                for var in eqn.invars:
                    shape = tuple(getattr(var.aval, "shape", ()))
                    if shape in forbidden:
                        violations.append(
                            f"{label}: rematted backward holds a saved "
                            f"residual of shape {shape} — the remat policy "
                            f"keeps an activation this configuration "
                            f"claims is recomputed [rule: remat-residual]"
                        )
            for v in eqn.params.values():
                for sub in _sub_jaxprs(v):
                    walk(sub)

    walk(jaxpr.jaxpr)
    # several residuals of one shape are one policy bug: one line each
    return list(dict.fromkeys(violations))


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


def audit_donation(jitted, *args, donate_argnums=(0, 1),
                   label: str | None = None) -> list[str]:
    """Donated inputs must actually alias outputs in the compiled program.

    Donation is a *hint*: XLA silently ignores it when shapes/dtypes or a
    backend quirk block the alias, and the program then double-allocates
    exactly the buffers (params + Adam moments) the donation existed to
    fold.  Compiles ``jitted`` (already wrapped with ``donate_argnums`` —
    e.g. ``make_train_step(jit_donate=True)``) and counts the
    ``input_output_alias`` entries in the executable's HLO header against
    the number of donated argument leaves.  (The header survives
    persistent-compile-cache hits; ``memory_analysis().alias_size_in_
    bytes`` reports 0 on a deserialized executable and would
    false-alarm.)  Returns one-line violations; a program exposing no HLO
    text reports itself rather than silently passing.
    """
    import jax

    label = label or getattr(jitted, "__name__", str(jitted))
    compiled = jitted.lower(*args).compile()
    try:
        txt = compiled.as_text()
    except Exception:  # noqa: BLE001 — absence must be reported, not raised
        txt = None
    if not txt:
        return [
            f"{label}: compiled executable exposes no HLO text — donation "
            f"cannot be verified on this build [rule: donation-alias]"
        ]
    # one "(param, {index}, may|must-alias)" clause per aliased buffer,
    # all in the module header (nested braces defeat a bracket regex)
    entries = len(re.findall(r"\(\d+, \{[^}]*\}, (?:may|must)-alias\)", txt))
    donated = sum(
        len(jax.tree.leaves(args[i])) for i in donate_argnums
    )
    if entries < donated:
        return [
            f"{label}: {entries} input/output aliases for {donated} "
            f"donated argument leaves — donated buffers are "
            f"double-allocating instead of updating in place "
            f"[rule: donation-alias]"
        ]
    return []


def audit_host_offload(jitted, *args, out_index: int = 1,
                       label: str | None = None) -> list[str]:
    """Outputs declared host-resident must compile to host-space buffers.

    Checks output ``out_index`` of the compiled ``jitted(*args)`` (the
    optimizer state, in ``make_train_step``'s layout): every array leaf's
    output sharding must carry the backend's host memory kind — an
    offloaded buffer whose output sharding says device memory has silently
    aliased back into HBM, which is exactly the failure mode that turns
    "offload" into a no-op that still OOMs.  On backends with no host
    memory space (jax 0.4.x CPU) offload is a documented identity and the
    audit passes vacuously — gate on
    ``utils.compat.host_memory_kind()`` for a hard guarantee.
    """
    import jax

    from ..utils import compat

    label = label or getattr(jitted, "__name__", str(jitted))
    kind = compat.host_memory_kind()
    if kind is None:
        return []  # no host space: offload degrades to the identity
    compiled = jitted.lower(*args).compile()
    try:
        shardings = compiled.output_shardings
    except Exception:  # noqa: BLE001 — absence must be reported, not raised
        return [
            f"{label}: compiled executable exposes no output shardings — "
            f"host placement cannot be verified [rule: host-offload]"
        ]
    out = shardings[out_index]
    violations = []
    for leaf in jax.tree.leaves(out):
        got = getattr(leaf, "memory_kind", None)
        if got != kind:
            violations.append(
                f"{label}: output {out_index} leaf landed in "
                f"{got or 'device'} memory, expected {kind} — the "
                f"offloaded state aliased back into HBM "
                f"[rule: host-offload]"
            )
    return violations


def run_memory_suite() -> list[tuple[str, list[str]]]:
    """The memory-axis audit suite behind ``check_contracts.py --memory``.

    Returns ``(check name, violations)`` pairs — all empty lists = the
    memory contracts hold.  Covers: the f32 accumulator audit, the
    remat-residual audit on the chunked-FFN path, a negative toy proving
    the residual audit actually catches a saved ``mult*dim`` activation
    (a checker that cannot fail its toy is a no-op wearing a green
    checkmark), the donation audit on the composed chunked train step,
    the host-offload placement audit, and the compiled peak-temp-bytes
    relation (chunked FFN strictly below dense at equal shape).  Small
    shapes; CPU-runnable end to end.
    """
    import jax
    import jax.numpy as jnp
    import optax

    from ..models import FeedForward, RingTransformer
    from ..utils import compat, make_train_step
    from ..utils.telemetry import compiled_memory

    checks: list[tuple[str, list[str]]] = []
    b, n, d, mult = 1, 128, 32, 4
    forbidden = [(b, n, mult * d)]

    checks.append(("accumulator-dtypes", audit_accumulator_dtypes()))

    ff = FeedForward(d, mult, chunk_size=32)
    x = jnp.ones((b, n, d))
    ff_params = ff.init(jax.random.PRNGKey(0), x)
    checks.append((
        "remat-residuals: blockwise ffn",
        audit_remat_residuals(
            lambda p: ff.apply(p, x).astype(jnp.float32).sum(), ff_params,
            forbidden=forbidden, label="blockwise_ffn",
        ),
    ))

    # negative toy: a remat that SAVES the mult*dim activation while the
    # config claims nothing_saveable — the audit must flag it, one line
    w1, w2 = jnp.ones((d, mult * d)), jnp.ones((mult * d, d))
    bad = jax.checkpoint(
        lambda x: ((jax.nn.gelu(x @ w1)) @ w2).sum(),
        policy=jax.checkpoint_policies.everything_saveable,
    )
    caught = audit_remat_residuals(
        bad, x, forbidden=forbidden, label="negative-toy",
    )
    checks.append((
        "remat-residuals: negative toy caught",
        [] if caught else [
            "negative toy: a saved (b, n, mult*dim) activation went "
            "unflagged — the residual audit is not live "
            "[rule: remat-residual]"
        ],
    ))

    # the composed step every knob feeds: chunked FFN + chunked CE +
    # nothing_saveable remat, donated and (where supported) offloaded
    model = RingTransformer(
        num_tokens=64, dim=d, depth=1, heads=2, dim_head=16, bucket_size=32,
        causal=True, use_ring=False, remat=True,
        remat_policy="nothing_saveable", ff_chunk_size=32,
        loss_chunk_size=32,
    )
    tokens = jnp.zeros((1, n + 1), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), tokens)
    opt = optax.adam(1e-3)
    opt_state = opt.init(params)

    def loss_fn(p, t):
        return model.apply(p, t, return_loss=True)

    step = make_train_step(loss_fn, opt, jit_donate=True)
    checks.append((
        "donation-alias: chunked train step",
        audit_donation(step, params, opt_state, tokens, label="train_step"),
    ))

    off_step = make_train_step(
        loss_fn, opt, jit_donate=True, offload_opt_state=True,
    )
    kind = compat.host_memory_kind()
    name = (
        "host-offload placement"
        if kind else
        "host-offload placement (no host space: no-op fallback verified)"
    )
    checks.append((
        name,
        audit_host_offload(
            off_step, params, opt_state, tokens, label="offload_step",
        ),
    ))

    # the headline relation, from the compiler's own accounting: the
    # chunked step's scratch high-water mark strictly below the dense
    # step's at equal shape
    dense_model = RingTransformer(
        num_tokens=64, dim=d, depth=1, heads=2, dim_head=16, bucket_size=32,
        causal=True, use_ring=False, remat=True,
        remat_policy="nothing_saveable",
    )

    def temp_bytes(m):
        fn = compat.jit(jax.value_and_grad(
            lambda p: m.apply(p, tokens, return_loss=True)
        ))
        return compiled_memory(fn.lower(params).compile()).get("temp_bytes")

    t_chunk, t_dense = temp_bytes(model), temp_bytes(dense_model)
    if t_chunk is None or t_dense is None:
        mem_violations = [
            "backend exposes no memory analysis — peak temp bytes "
            "unverifiable on this build [rule: chunked-peak]"
        ]
    elif t_chunk >= t_dense:
        mem_violations = [
            f"chunked-FFN step temp bytes {t_chunk} NOT below the dense "
            f"step's {t_dense} at equal shape [rule: chunked-peak]"
        ]
    else:
        mem_violations = []
    checks.append((
        f"chunked peak temp bytes < dense ({t_chunk} < {t_dense})",
        mem_violations,
    ))
    return checks


def audit_accumulator_dtypes() -> list[str]:
    """Verify the flash kernels accumulate in float32 for sub-f32 inputs.

    Returns a list of one-line violations (empty = clean): checks the XLA
    path's online-softmax carry (``ops/flash.init_carry``) and the Pallas
    partials' ``(acc, m, l)`` output dtypes, both under bf16 inputs, via
    ``eval_shape`` — abstract evaluation only, no kernel runs.
    """
    import jax
    import jax.numpy as jnp

    from ..ops import flash, pallas_flash

    violations: list[str] = []
    b, h, n, d = 1, 2, 32, 8
    q = jax.ShapeDtypeStruct((b, h, n, d), jnp.bfloat16)

    carry = jax.eval_shape(
        lambda q: flash.init_carry(b, h, 1, n, d, like=q), q
    )
    for name, leaf in zip(("acc", "m", "l"), jax.tree_util.tree_leaves(carry)):
        if leaf.dtype != jnp.float32:
            violations.append(
                f"ops/flash.init_carry: {name} accumulates in {leaf.dtype}, "
                f"contract says float32 [rule: f32-accumulator]"
            )

    parts = jax.eval_shape(
        lambda q, k, v: pallas_flash.pallas_flash_partials(
            q, k, v, scale=1.0, block_q=16, block_k=16, interpret=True,
        ),
        q, q, q,
    )
    for name, leaf in zip(("acc", "m", "l"), jax.tree_util.tree_leaves(parts)):
        if leaf.dtype != jnp.float32:
            violations.append(
                f"ops/pallas_flash.pallas_flash_partials: {name} is "
                f"{leaf.dtype}, contract says float32 [rule: f32-accumulator]"
            )
    return violations
