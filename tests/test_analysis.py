"""The static-analysis subsystem, tier-1: contracts, lint, retrace sentinel.

Three layers of coverage:

  - **positive contracts**: every sequence-parallel strategy's compiled
    collective signature matches the declarative table on CPU meshes —
    the generalized replacement for the old one-off HLO pins;
  - **negative toys**: deliberately broken functions (an accidental
    all-gather in a ring hot path, a collective under ``lax.cond``, a
    retrace-per-step static arg, a compat-shim bypass) must each fail
    their pass with a one-line diagnostic naming the violated rule;
  - **self-runs**: the repo lint over ``ring_attention_tpu/`` and the f32
    accumulator audit pin ZERO violations — the package stays clean by
    construction.
"""

import textwrap

import jax
import jax.numpy as jnp
import pytest
from jax import lax
from jax.sharding import PartitionSpec as P

from ring_attention_tpu.analysis import (
    RetraceError,
    assert_compiles_once,
    audit_accumulator_dtypes,
    lint_package,
    lint_source,
)
from ring_attention_tpu.analysis import contracts
from ring_attention_tpu.parallel.mesh import SEQ_AXIS, create_mesh
from ring_attention_tpu.parallel.ring import ring_flash_attention
from ring_attention_tpu.utils import compat


# ----------------------------------------------------------------------
# Positive contracts: the strategy matrix on CPU meshes
# ----------------------------------------------------------------------


def _assert_ok(reports):
    bad = [v for r in reports for v in r.violations]
    assert not bad, "\n".join(bad)


@pytest.mark.parametrize("strategy", ["ring", "zigzag", "ulysses", "hybrid"])
def test_contract_fwd_and_bwd(devices, strategy):
    """Forward AND backward collective counts, axis discipline, and the
    no-undeclared-collective rule on the canonical 8-device mesh."""
    _assert_ok(contracts.check_strategy(strategy))


def test_contract_counter(devices):
    """The TokenRing counter-rotation row: exact hop counts fwd AND bwd
    from compiled HLO, permute pairs in BOTH ring directions (the
    both-directions rule), zero undeclared collective kinds, and the
    scan-multiplied jaxpr counts — all on 8 virtual CPU devices."""
    _assert_ok(contracts.check_strategy("counter"))
    _assert_ok(contracts.check_scan_contract("counter"))


@pytest.mark.parametrize(
    "strategy", ["ring_compressed", "counter_compressed"]
)
def test_contract_compressed(devices, strategy):
    """The int8-compressed rows: compressed bytes/hop pinned from the
    traced ppermute avals (the hop-bytes rule) plus forward HLO counts;
    the fwd+bwd hop counts are pinned at the jaxpr level by the scan
    contract (backward recomputes from exact residuals, so its HLO is
    the ring/counter contract already compiled above — kept out of the
    fast tier; tools/check_contracts.py --strategy all runs it)."""
    _assert_ok(contracts.check_strategy(strategy, directions=("fwd",)))
    _assert_ok(contracts.check_scan_contract(strategy))


def test_counter_collective_budget(devices):
    """Acceptance: the counter-rotated step issues NO MORE collectives
    than the unidirectional baseline, proven from compiled HLO — fwd pays
    one extra (the out/lse catch-up: ring vs ring-1) and the resident-KV
    backward repays it (2*ring vs 3*ring-2 per step)."""
    report = contracts.check_counter_collective_budget()
    assert report.ok, "\n".join(report.violations)
    ring = report.dims["ring"]
    assert report.counts["counter_step"] == 2 * ring
    assert report.counts["baseline_step"] == 3 * ring - 2
    assert report.counts["counter_step"] < report.counts["baseline_step"]


def test_counter_contract_catches_missing_direction(devices):
    """The both-directions rule is live: verifying the UNIDIRECTIONAL
    ring's HLO against the counter contract (which demands permute pairs
    in both ring directions) must fail naming the rule."""
    mesh = contracts.default_mesh("ring")
    fn, args, dims = contracts.build_entry("ring", mesh)
    txt = compat.jit(fn).lower(*args).compile().as_text()
    violations = contracts.verify_hlo(
        "counter", "fwd", txt, dims, tuple(mesh.shape.values()),
        list(mesh.shape.keys()),
    )
    assert any("both-directions" in v for v in violations), violations


@pytest.mark.parametrize(
    "strategy", ["striped", "ulysses_gqa", "tree_decode", "counter_q8"]
)
def test_contract_fwd_only(devices, strategy):
    """Single-direction strategies (striped shares the ring's backward
    formula — its forward already pins the permutation-vs-count claim;
    counter_q8 shares counter_compressed's schedule and has no scan
    table)."""
    _assert_ok(contracts.check_strategy(strategy, directions=("fwd",)))


def test_contract_ring_on_data_parallel_mesh(devices):
    """A (data=2, seq=4) mesh: the ppermute pairs must keep the data
    coordinate fixed — the axis rule with a non-trivial second axis."""
    _assert_ok(contracts.check_strategy(
        "ring", create_mesh(ring_size=4, data_size=2), directions=("fwd",),
    ))


def test_contract_hybrid_alternate_factoring(devices):
    """ring=2 x ulysses=4: the other 8-device factoring (the table's count
    expressions must track the mesh, not hard-code 4x2)."""
    _assert_ok(contracts.check_strategy(
        "hybrid", create_mesh(ulysses_size=4, ring_size=2),
        directions=("fwd",),
    ))


def test_hybrid_hop_reduction_relation(devices):
    """Acceptance: the hybrid contract PROVES ulysses-x fewer ring hops
    than the pure ring at equal world size, from two compiled programs."""
    report = contracts.check_hybrid_hop_reduction(world=8, ulysses=2)
    assert report.ok, "\n".join(report.violations)
    assert report.counts == {"hybrid_hops": 3, "pure_ring_hops": 7}


@pytest.mark.parametrize("strategy", ["ring", "hybrid"])
def test_scan_contract(devices, strategy):
    """The traced (scanned-XLA) side: jaxpr collective counts with scan
    bodies multiplied by trip count.  No XLA compile — make_jaxpr only."""
    _assert_ok(contracts.check_scan_contract(strategy))


def test_contract_table_is_documentation():
    """The count expressions evaluate for arbitrary dims — the table can
    be rendered straight into docs and stays arithmetic-only."""
    dims = {"data": 1, "ring": 16, "ulysses": 4, "world": 64, "passes": 16}
    assert contracts.expected_counts("ring", "fwd", dims) == {
        "collective-permute": 15,
    }
    assert contracts.expected_counts("ring", "fwdbwd", dims) == {
        "collective-permute": 46,  # (ring-1 fwd) + (ring-1 kv + ring dkv bwd)
    }
    assert contracts.expected_counts("hybrid", "fwd", dims) == {
        "all-to-all": 4, "collective-permute": 15,
    }


# ----------------------------------------------------------------------
# Negative toys: each pass must fail loudly, one line, naming its rule
# ----------------------------------------------------------------------


def test_accidental_all_gather_fails_contract(devices):
    """A ring entry that also all-gathers K (the exact regression the
    global no-undeclared-gather rule exists for) must fail with a one-line
    diagnostic naming the collective-contract rule."""
    mesh = create_mesh(ring_size=8)
    spec = P("data", None, "seq", None)

    def leaky(q, k, v):
        out = ring_flash_attention(
            q, k, v, None, SEQ_AXIS, causal=True, bucket_size=4,
            impl="pallas",
        )
        # accidental O(seq) activation gather in the hot path
        k_all = lax.all_gather(k, SEQ_AXIS, axis=2, tiled=True)
        return out + k_all.mean() * 1e-9

    fn = compat.shard_map(leaky, mesh=mesh, in_specs=(spec,) * 3,
                          out_specs=spec, check_vma=False)
    x = jnp.ones((1, 8, 64, 8), jnp.float32)
    txt = compat.jit(fn).lower(x, x, x).compile().as_text()
    dims = {"data": 1, "ring": 8, "ulysses": 1, "world": 8, "passes": 8}
    violations = contracts.verify_hlo(
        "ring", "fwd", txt, dims, mesh_shape=(1, 8),
        axis_names=["data", "seq"],
    )
    assert len(violations) == 1
    line = violations[0]
    assert "\n" not in line
    assert "all-gather" in line and "[rule: collective-contract]" in line


def test_collective_inside_cond_fails(devices):
    """A ppermute under lax.cond (a data-dependent collective schedule —
    the SPMD deadlock hazard) is caught from jaxpr structure alone."""
    mesh = create_mesh(ring_size=8)
    spec = P("data", None, "seq", None)

    def divergent(q):
        rank = lax.axis_index(SEQ_AXIS)
        perm = [(j, (j + 1) % 8) for j in range(8)]
        return lax.cond(
            rank % 2 == 0,
            lambda x: lax.ppermute(x, SEQ_AXIS, perm),
            lambda x: x,
            q,
        )

    fn = compat.shard_map(divergent, mesh=mesh, in_specs=(spec,),
                          out_specs=spec, check_vma=False)
    x = jnp.ones((1, 8, 64, 8), jnp.float32)
    jc = contracts.jaxpr_collectives(jax.make_jaxpr(fn)(x))
    assert jc.in_cond == ["ppermute"]


def test_collective_inside_while_fails(devices):
    """A ppermute under lax.while_loop: the trip count is unknown
    statically, so the checker must flag it (never undercount it)."""
    mesh = create_mesh(ring_size=8)
    spec = P("data", None, "seq", None)

    def dynamic(q):
        perm = [(j, (j + 1) % 8) for j in range(8)]
        return lax.while_loop(
            lambda carry: carry[1] < 3,
            lambda carry: (lax.ppermute(carry[0], SEQ_AXIS, perm),
                           carry[1] + 1),
            (q, 0),
        )[0]

    fn = compat.shard_map(dynamic, mesh=mesh, in_specs=(spec,),
                          out_specs=spec, check_vma=False)
    x = jnp.ones((1, 8, 64, 8), jnp.float32)
    jc = contracts.jaxpr_collectives(jax.make_jaxpr(fn)(x))
    assert jc.in_while == ["ppermute"] and jc.dynamic


def test_replica_groups_iota_form_parsed():
    """The iota (v2) replica_groups spelling some XLA builds print must
    parse to the same groups as the brace form — and an unknown format
    must surface as a violation, never a silent pass."""
    brace = "all-to-all.1 = f32[] all-to-all(x), replica_groups={{0,2},{1,3}}"
    iota = "all-to-all.1 = f32[] all-to-all(x), replica_groups=[2,2]<=[4]"
    iota_t = ("all-to-all.1 = f32[] all-to-all(x), "
              "replica_groups=[2,2]<=[2,2]T(1,0)")
    assert contracts._parse_replica_groups(brace) == [[0, 2], [1, 3]]
    assert contracts._parse_replica_groups(iota) == [[0, 1], [2, 3]]
    assert contracts._parse_replica_groups(iota_t) == [[0, 2], [1, 3]]
    assert contracts._parse_replica_groups("all-to-all.1 = f32[] ...") is None

    weird = "all-to-all.1 = f32[] all-to-all(x), replica_groups=<opaque>"
    out = contracts.check_groups_axis(weird, "all-to-all", (2, 2), 1, "seq")
    assert len(out) == 1 and "unrecognized replica_groups" in out[0]
    # and the iota spelling passes/fails the axis rule like the brace one:
    # groups [[0,1],[2,3]] on a (2, 2) mesh span exactly axis 1
    assert contracts.check_groups_axis(iota, "all-to-all", (2, 2), 1, "seq") == []
    assert contracts.check_groups_axis(iota, "all-to-all", (2, 2), 0, "data")


def test_retrace_per_step_fails():
    """A static arg that changes per step forces a recompile every call;
    the sentinel names the entry point and the compile-once rule."""
    bad = compat.jit(lambda x, n: x * n, static_argnums=(1,))
    with pytest.raises(RetraceError) as err:
        assert_compiles_once(bad, lambda step: (jnp.ones(8), step),
                             steps=3, label="toy_step")
    line = str(err.value)
    assert "\n" not in line
    assert "toy_step" in line and "[rule: compile-once]" in line
    assert "3 compilations" in line


def test_prewarmed_other_shape_not_charged():
    """A cache entry from an earlier call at a DIFFERENT shape must not
    count against the loop (the sentinel audits this loop's compiles, not
    the callable's history); same-shape pre-warm is a healthy 0."""
    f = compat.jit(lambda x: x * 2)
    f(jnp.ones(4))  # pre-warm at another shape
    assert assert_compiles_once(f, lambda s: (jnp.ones(8),), steps=3) == 1
    assert assert_compiles_once(f, lambda s: (jnp.ones(8),), steps=3) == 0


def test_entry_point_compiles_once():
    """A real entry point (flash_attention) through the sentinel: three
    same-shape steps with fresh arrays, exactly one compilation."""
    from functools import partial

    from ring_attention_tpu.ops.flash import flash_attention

    step = compat.jit(partial(flash_attention, causal=True, bucket_size=16))

    def make_args(step_i):
        x = jnp.full((1, 2, 32, 8), 1.0 + step_i, jnp.float32)
        return (x, x, x)

    assert assert_compiles_once(step, make_args, steps=3) == 1


def test_shim_bypass_fails_lint():
    """The three shim-bypass spellings each produce exactly one RA001/2."""
    src = textwrap.dedent("""
        import jax
        from jax.experimental.shard_map import shard_map

        def f(fn, mesh, specs):
            return jax.experimental.shard_map.shard_map(
                fn, mesh=mesh, in_specs=specs, out_specs=specs)

        g = jax.jit(lambda x: x)
    """)
    violations = lint_source(src, "ring_attention_tpu/parallel/toy.py")
    rules = [v.rule for v in violations]
    assert rules.count("RA001") == 2 and rules.count("RA002") == 1
    for v in violations:
        assert "\n" not in str(v)
        assert "compat" in v.message


def test_lint_toy_violations_each_rule():
    """One toy module tripping RA003-RA007, each a one-line diagnostic."""
    src = textwrap.dedent("""
        import time
        from jax import lax
        from jax.experimental import pallas as pl

        def launch(x, kernel, spec):
            return pl.pallas_call(kernel, out_shape=spec)(x)

        def rotate(x):
            return lax.ppermute(x, "seq", [(0, 1)])

        def stamp(x):
            print("step", time.time())
            return x

        def attention(q, k, v):
            return q
    """)
    violations = lint_source(src, "ring_attention_tpu/ops/toy.py")
    rules = sorted(v.rule for v in violations)
    assert rules == ["RA003", "RA004", "RA005", "RA006", "RA007"]


def test_lint_ra008_observe_guard_and_unit_suffix():
    """RA008: a library-level ``Telemetry.observe`` outside a
    ``collecting()`` block silently drops its scalar; an unsuffixed
    metric name has no unit.  Both flag; the guarded, suffixed form and
    the reasoned allow are clean."""
    bad = textwrap.dedent("""
        from ring_attention_tpu.utils.telemetry import telemetry

        def f(x):
            telemetry.observe("kv_hop", x)
            return x
    """)
    violations = lint_source(bad, "ring_attention_tpu/parallel/toy.py")
    assert [v.rule for v in violations] == ["RA008", "RA008"]
    assert any("collecting()" in v.message for v in violations)
    assert any("unit" in v.message for v in violations)
    good = textwrap.dedent("""
        from ring_attention_tpu.utils.telemetry import telemetry

        def f(x):
            with telemetry.collecting() as col:
                telemetry.observe("kv_hop_bytes", x)
            return x, col.values()
    """)
    assert lint_source(good, "ring_attention_tpu/parallel/toy.py") == []
    allowed = textwrap.dedent("""
        from ring_attention_tpu.utils.telemetry import telemetry

        def f(x):
            telemetry.observe("kv_hop", x)  # ra: allow(RA008 collected by caller at this trace level; name pinned by dashboard)
            return x
    """)
    assert lint_source(allowed, "ring_attention_tpu/parallel/toy.py") == []


def test_lint_pragma_silences_with_reason():
    src = 'from jax import lax\n' \
          'def f(x):\n' \
          '    return lax.psum(x, "seq")  # ra: allow(RA004 toy reason)\n'
    assert lint_source(src, "ring_attention_tpu/parallel/toy.py") == []
    bare = src.replace(" toy reason", "")
    violations = lint_source(bare, "ring_attention_tpu/parallel/toy.py")
    assert len(violations) == 1 and "reason is mandatory" in violations[0].message


def test_lint_named_scope_satisfies_ra004():
    src = textwrap.dedent("""
        import jax
        from jax import lax

        def f(x):
            with jax.named_scope("toy/rotate"):
                return lax.ppermute(x, "seq", [(0, 1)])
    """)
    assert lint_source(src, "ring_attention_tpu/parallel/toy.py") == []


def test_corrupted_band_table_fails_soundness():
    """A band table missing a live tile (the exact silent-wrong-attention
    regression the prover exists for) fails with a one-line diagnostic
    naming the tile and the soundness rule."""
    import numpy as np

    from ring_attention_tpu.analysis import coverage
    from ring_attention_tpu.ops.pallas_flash import _TF_WORK, band_plan

    n, blk = 32, 8
    plan = band_plan((n, n), (blk, blk), 0)
    truth = coverage.oracle_mask(np.arange(n), np.arange(n), None)
    inst = [coverage.HopInstance(
        rank=0, q_origin=0, kv_origin=0, oracle=truth, static_live=truth,
        hi=0, lo=None, has_work=True, full=False, kpos=np.arange(n),
    )]
    assert coverage.verify_plan(plan, inst, "toy") == []
    flags = plan.flags.copy()
    live = [t for t in range(len(flags)) if flags[t] & _TF_WORK][2]
    flags[live] &= ~_TF_WORK  # drop a live tile from the grid
    violations = coverage.verify_plan(plan._replace(flags=flags), inst,
                                      "toy")
    line = violations[0]
    assert "\n" not in line
    assert "live tile" in line and "[rule: tile-coverage-sound]" in line
    assert "q-tile" in line  # names the offending tile


def test_widened_band_table_fails_tightness():
    """A table built from a too-wide WORK bound visits dead tiles —
    silent perf loss — and fails the tightness rule naming each tile."""
    import numpy as np

    from ring_attention_tpu.analysis import coverage
    from ring_attention_tpu.ops.pallas_flash import band_plan

    n, blk = 32, 8
    truth = coverage.oracle_mask(np.arange(n), np.arange(n), None)
    inst = [coverage.HopInstance(
        rank=0, q_origin=0, kv_origin=0, oracle=truth, static_live=truth,
        hi=0, lo=None, has_work=True, full=False, kpos=np.arange(n),
    )]
    wide = band_plan((n, n), (blk, blk), (blk, 0, 0, 0), windowed=False)
    violations = coverage.verify_plan(wide, inst, "toy")
    assert violations and all("\n" not in v for v in violations)
    assert all("[rule: tile-coverage-tight]" in v for v in violations)
    assert "dead tile" in violations[0]


def test_bf16_accumulator_toy_fails_precision_flow():
    """A bf16 accumulator carried through a scan (the drift bug the f32
    contract forbids) fails the precision-flow pass in one line."""
    from ring_attention_tpu.analysis import dataflow

    def bad(x):
        def body(acc, xi):
            return acc + xi, None
        acc, _ = lax.scan(body, jnp.zeros((8,), jnp.bfloat16), x)
        return acc.astype(jnp.float32).sum()

    violations = dataflow.audit_precision_flow(
        bad, jnp.ones((4, 8), jnp.bfloat16), label="bf16_toy",
    )
    [line] = [v for v in violations if "loop carry" in v]
    assert "\n" not in line
    assert "bf16_toy" in line and "[rule: f32-accumulator-flow]" in line


def test_int8_without_dequant_toy_fails_precision_flow():
    """Quantized int8 content reaching a dot without its scale multiply
    (the hop-compression hazard) is flagged; the real dequant pattern —
    scale multiply first — is clean."""
    from ring_attention_tpu.analysis import dataflow

    y = jnp.ones((8, 8), jnp.float32)

    def no_dequant(xq, y):
        return (xq.astype(jnp.float32) @ y).sum()

    violations = dataflow.audit_precision_flow(
        no_dequant, jnp.ones((8, 8), jnp.int8), y, label="q_toy",
    )
    assert any("[rule: int8-dequant]" in v and "\n" not in v
               for v in violations)

    def dequant(xq, scale, y):
        return ((xq.astype(jnp.float32) * scale) @ y).sum()

    assert dataflow.audit_precision_flow(
        dequant, jnp.ones((8, 8), jnp.int8), jnp.float32(0.1), y,
        label="q_toy",
    ) == []


def test_branch_divergent_collective_toy_fails(devices):
    """A cond whose branches issue DIFFERENT collective sequences (one
    rank ppermutes, the other doesn't — the deadlock) fails the
    divergence checker naming the branch; branches issuing the SAME
    sequence pass — the proof-level upgrade over the PR-5 blanket ban."""
    from ring_attention_tpu.analysis import dataflow

    mesh = create_mesh(ring_size=8)
    spec = P("data", None, "seq", None)
    perm = [(j, (j + 1) % 8) for j in range(8)]

    def divergent(q):
        rank = lax.axis_index(SEQ_AXIS)
        return lax.cond(
            rank % 2 == 0,
            lambda x: lax.ppermute(x, SEQ_AXIS, perm),
            lambda x: x,
            q,
        )

    fn = compat.shard_map(divergent, mesh=mesh, in_specs=(spec,),
                          out_specs=spec, check_vma=False)
    x = jnp.ones((1, 8, 64, 8), jnp.float32)
    [line] = dataflow.check_spmd_divergence(jax.make_jaxpr(fn)(x), "toy")
    assert "\n" not in line
    assert "branch 1" in line
    assert "[rule: branch-collective-divergence]" in line

    def convergent(q):
        rank = lax.axis_index(SEQ_AXIS)
        return lax.cond(
            rank % 2 == 0,
            lambda x: lax.ppermute(x * 2, SEQ_AXIS, perm),
            lambda x: lax.ppermute(x + 1, SEQ_AXIS, perm),
            q,
        )

    fn2 = compat.shard_map(convergent, mesh=mesh, in_specs=(spec,),
                           out_specs=spec, check_vma=False)
    assert dataflow.check_spmd_divergence(jax.make_jaxpr(fn2)(x)) == []


def test_lint_ra009_host_numpy_in_traced_code():
    """RA009: a host numpy call in a traced subpackage flags; the
    reasoned allow and non-traced modules are clean (np.random stays
    RA005's)."""
    import textwrap as tw

    bad = tw.dedent("""
        import numpy as np

        def f(x):
            return np.exp(x)
    """)
    violations = lint_source(bad, "ring_attention_tpu/ops/toy.py")
    assert [v.rule for v in violations] == ["RA009"]
    assert "jnp" in violations[0].message

    allowed = bad.replace(
        "np.exp(x)",
        "np.exp(x)  # ra: allow(RA009 static trace-time constant)",
    )
    assert lint_source(allowed, "ring_attention_tpu/ops/toy.py") == []
    # utils/ is host-side: not in RA009 scope
    assert lint_source(bad, "ring_attention_tpu/utils/toy.py") == []
    rng = "import numpy as np\ndef f():\n    return np.random.rand(3)\n"
    assert [v.rule for v in
            lint_source(rng, "ring_attention_tpu/ops/toy.py")] == ["RA005"]


def test_lint_ra010_grid_seam_bypass():
    """RA010: constructing Pallas grid tables or hop skip-predicates
    outside the band_plan()/mask-algebra seam flags (the bypass that
    would dodge certification); the seam modules themselves, the
    certifier, and a reasoned allow are clean."""
    bad = (
        "from ring_attention_tpu.ops.pallas_flash import _band_tables\n"
        "def my_grid():\n"
        "    return _band_tables(4, 4, 8, 8, (0, 0, 0, 0), False, True)\n"
    )
    violations = lint_source(bad, "ring_attention_tpu/parallel/newpath.py")
    assert [v.rule for v in violations] == ["RA010"]
    assert "band_plan" in violations[0].message
    # hop skip-predicates are part of the seam too
    skip = ("def f(hi, lo):\n"
            "    return _hop_has_work(hi, lo, 16, 16)\n")
    assert [v.rule for v in lint_source(
        skip, "ring_attention_tpu/models/custom.py")] == ["RA010"]
    # the seam's home modules, the algebra, and the certifier are exempt
    for seam in ("ring_attention_tpu/ops/pallas_flash.py",
                 "ring_attention_tpu/parallel/ring.py",
                 "ring_attention_tpu/masks.py",
                 "ring_attention_tpu/analysis/coverage.py"):
        assert lint_source(bad, seam) == [], seam
    allowed = bad.replace(
        "(0, 0, 0, 0), False, True)",
        "(0, 0, 0, 0), False, True)  "
        "# ra: allow(RA010 prototyping a grid the prover covers in-test)",
    )
    assert lint_source(allowed,
                       "ring_attention_tpu/parallel/newpath.py") == []
    bare = bad.replace(
        "(0, 0, 0, 0), False, True)",
        "(0, 0, 0, 0), False, True)  # ra: allow(RA010)",
    )
    [v] = lint_source(bare, "ring_attention_tpu/parallel/newpath.py")
    assert "reason is mandatory" in v.message


def test_lint_ra011_signal_outside_elastic():
    """RA011: signal handlers / process-kill primitives outside the
    elastic runtime or utils/resilience.py flag (an ad-hoc handler
    silently replaces PreemptionGuard's drain); the owning modules and
    a reasoned allow are clean."""
    bad = (
        "import os, signal\n"
        "def install():\n"
        "    signal.signal(signal.SIGTERM, lambda *_: None)\n"
        "def die(pid):\n"
        "    os.kill(pid, 9)\n"
        "    os._exit(1)\n"
    )
    violations = lint_source(bad, "ring_attention_tpu/utils/train.py")
    assert [v.rule for v in violations] == ["RA011"] * 3
    assert "PreemptionGuard" in violations[0].message
    # the owners of preemption semantics are exempt
    for home in ("ring_attention_tpu/elastic/preemption.py",
                 "ring_attention_tpu/elastic/chaos.py",
                 "ring_attention_tpu/utils/resilience.py"):
        assert lint_source(bad, home) == [], home
    allowed = bad.replace(
        "os.kill(pid, 9)",
        "os.kill(pid, 0)  # ra: allow(RA011 liveness probe, signal 0)",
    ).replace(
        "signal.signal(signal.SIGTERM, lambda *_: None)",
        "signal.signal(signal.SIGTERM, h)  "
        "# ra: allow(RA011 restoring a saved handler)",
    ).replace(
        "os._exit(1)",
        "os._exit(1)  # ra: allow(RA011 post-fork child must not atexit)",
    )
    assert lint_source(allowed, "ring_attention_tpu/utils/train.py") == []
    bare = bad.replace(
        "os.kill(pid, 9)", "os.kill(pid, 9)  # ra: allow(RA011)"
    )
    assert any("reason is mandatory" in v.message for v in lint_source(
        bare, "ring_attention_tpu/utils/train.py"
    ))


def test_lint_ra013_remote_dma_outside_fused_kernel():
    """RA013: remote-DMA / semaphore primitives outside the fused ring
    kernel module flag with a one-line diagnostic (a second module
    issuing raw semaphore ops can deadlock the ring and invalidates the
    counted contract); the owning module and a reasoned allow are
    clean."""
    bad = (
        "def hop(src, dst, s, r):\n"
        "    copy = pltpu.make_async_remote_copy(src, dst, s, r,\n"
        "                                        device_id=(1,))\n"
        "    barrier = pltpu.get_barrier_semaphore()\n"
        "    pltpu.semaphore_signal(barrier, inc=1, device_id=(0,))\n"
        "    pltpu.semaphore_wait(barrier, 1)\n"
        "    sem = pltpu.SemaphoreType.DMA\n"
    )
    violations = lint_source(bad, "ring_attention_tpu/parallel/newhop.py")
    assert [v.rule for v in violations] == ["RA013"] * 5
    assert "ops/pallas_ring.py" in violations[0].message
    # the fused kernel module IS the seam — provided the function is a
    # declared PROTOCOL row (RA015 fences the seam to the verified table)
    declared = (
        'PROTOCOL = (\n'
        '    {"row": "hop", "fn": "hop", "op": "remote_copy",\n'
        '     "sites": {"dma_start": 1}},\n'
        ')\n' + bad
    )
    assert lint_source(declared, "ring_attention_tpu/ops/pallas_ring.py") == []
    allowed = bad.replace(
        "    pltpu.semaphore_wait(barrier, 1)\n",
        "    pltpu.semaphore_wait(barrier, 1)  "
        "# ra: allow(RA013 local-only probe, no ring peer waits on it)\n",
    )
    assert [v.rule for v in lint_source(
        allowed, "ring_attention_tpu/parallel/newhop.py"
    )] == ["RA013"] * 4
    bare = bad.replace(
        "    barrier = pltpu.get_barrier_semaphore()\n",
        "    barrier = pltpu.get_barrier_semaphore()  # ra: allow(RA013)\n",
    )
    assert any("reason is mandatory" in v.message for v in lint_source(
        bare, "ring_attention_tpu/parallel/newhop.py"
    ))


def test_lint_ra014_raw_clock_outside_tracing_seam():
    """RA014: a raw ``time.*`` clock read in the observability-
    instrumented subpackages (elastic/, utils/) flags — emitted
    timestamps must route through the ``utils/tracing.py`` seam so the
    cluster-timeline merger's clock-offset correction covers them.  The
    seam module itself, a reasoned allow, and out-of-scope packages are
    clean."""
    bad = (
        "import time\n"
        "def stamp():\n"
        "    wall = time.time()\n"
        "    mono = time.monotonic()\n"
        "    return {'time': wall, 'mono': mono}\n"
    )
    violations = lint_source(bad, "ring_attention_tpu/elastic/toy.py")
    assert [v.rule for v in violations] == ["RA014"] * 2
    assert "utils/tracing.py" in violations[0].message
    assert [v.rule for v in lint_source(
        bad, "ring_attention_tpu/utils/toy.py"
    )] == ["RA014"] * 2
    # the seam module IS the allowed home of the raw reads
    assert lint_source(bad, "ring_attention_tpu/utils/tracing.py") == []
    # models/ etc. stay RA005's concern, not RA014's
    assert [v.rule for v in lint_source(
        bad, "ring_attention_tpu/models/toy.py"
    )] == ["RA005"] * 2
    allowed = bad.replace(
        "time.monotonic()",
        "time.monotonic()  # ra: allow(RA014 deadline arithmetic, "
        "not an emitted timestamp)",
    )
    assert [v.rule for v in lint_source(
        allowed, "ring_attention_tpu/elastic/toy.py"
    )] == ["RA014"]
    bare = bad.replace(
        "time.monotonic()", "time.monotonic()  # ra: allow(RA014)"
    )
    assert any("reason is mandatory" in v.message for v in lint_source(
        bare, "ring_attention_tpu/elastic/toy.py"
    ))


# ----------------------------------------------------------------------
# Self-runs: the package itself is clean
# ----------------------------------------------------------------------


def test_lint_self_run_zero_violations():
    """The whole package tree passes its own lint — every fix that landed
    with these rules stays landed."""
    violations = lint_package()
    assert violations == [], "\n".join(str(v) for v in violations)


def test_accumulator_dtype_audit_clean():
    """Both flash paths accumulate (acc, m, l) in f32 under bf16 inputs."""
    assert audit_accumulator_dtypes() == []


def test_collective_fingerprint_shape(devices):
    """The fingerprint ``__graft_entry__.dryrun_multichip`` prints:
    per-strategy fwd collective counts.  Since PR 18 the ring
    row brings the fused-ring rows with it: the in-kernel remote-DMA /
    semaphore counts from the lowered module, with ``ppermute: 0`` — the
    launch-free-hops pin — for plain and int8-fed variants."""
    fp = contracts.collective_fingerprint(strategies=("ring",))
    fused_counts = dict(sorted(contracts.FUSED_RING_EXPECTED.items()))
    assert fp == {
        "ring": {"ppermute": 7},
        "fused_ring": fused_counts,
        "fused_ring_q8": fused_counts,
        "contract_ok": True,
    }


# ----------------------------------------------------------------------
# DCN isolation: the pod-scale placement contract (PR 15)
# ----------------------------------------------------------------------


def test_contract_dcn_isolation(devices):
    """The hierarchical-mesh rows: ring and hybrid compiled over a
    ``(dcn_data, ...)`` mesh hold their ordinary collective contracts
    AND provably issue zero sequence-parallel collectives over the dcn
    axis — from optimized HLO and the jaxpr walk, fwd and fwdbwd."""
    _assert_ok(contracts.check_dcn_isolation())


def test_dcn_isolation_negative_toy(devices):
    """A deliberate collective OVER the dcn axis must be flagged by both
    halves of the proof — the HLO permute-pair scan and the traced
    axis-name walk — each with a one-line diagnostic naming the rule."""
    from ring_attention_tpu.parallel.mesh import DCN_DATA_AXIS, create_mesh

    mesh = create_mesh(dcn_data_size=2, ring_size=4)

    def bad(x):
        # a "ring hop" straight over the slow inter-slice links
        return lax.ppermute(
            x, DCN_DATA_AXIS, [(i, (i + 1) % 2) for i in range(2)]
        )

    fn = compat.shard_map(
        bad, mesh=mesh, in_specs=P(DCN_DATA_AXIS),
        out_specs=P(DCN_DATA_AXIS),
    )
    x = jnp.arange(8.0)
    txt = compat.jit(fn).lower(x).compile().as_text()
    violations = contracts.hlo_dcn_isolation(
        txt, tuple(mesh.shape.values()), list(mesh.shape.keys())
    )
    assert violations, "cross-dcn permute escaped the HLO scan"
    assert all("dcn-isolation" in v for v in violations)
    axes_by_prim = contracts.jaxpr_collective_axis_names(
        jax.make_jaxpr(fn)(x)
    )
    assert DCN_DATA_AXIS in axes_by_prim.get("ppermute", set())
    # a mesh with no dcn axis has nothing to prove — reported, not passed
    flat = create_mesh(ring_size=8)
    note = contracts.hlo_dcn_isolation(
        txt, tuple(flat.shape.values()), list(flat.shape.keys())
    )
    assert note and "nothing to prove" in note[0]


def test_dcn_collective_fingerprint_deterministic(devices):
    """Per-row fwd collective counts over the hierarchical mesh + the
    machine-checked verdict, exact and deterministic across calls."""
    fp = contracts.dcn_collective_fingerprint()
    assert fp["dcn_ok"] is True
    assert fp["ring_dcn"] == {"ppermute": 3}
    assert fp["hybrid_dcn"] == {"all_to_all": 4, "ppermute": 1}
    assert contracts.dcn_collective_fingerprint() == fp
