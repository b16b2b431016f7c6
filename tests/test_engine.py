"""Cross-validation: compiled vector engine vs the interpreter oracle.

Every observable of :func:`repro.core.simulate` must be *identical* across
``engine="interp"`` and ``engine="vector"``: cycle counts, per-op and
per-node fire counts, load/store/flop totals, queue-occupancy telemetry,
network hop/stall stats, and bit-identical output grids — on single-op
mappings of every rank, temporal layers, program pipelines (including the
imux re-interleave fallback), ideal and routed, bounded and unbounded
queues, plus the failure paths (deadlock, max_cycles)."""
import numpy as np
import pytest

from repro.core import CGRA, SimDeadlock, map_1d, map_2d, map_3d, simulate
from repro.core.spec import (StencilSpec, heat_2d, heat_3d, paper_stencil_1d,
                             paper_stencil_2d)
from repro.fabric import FabricTopology, place, route
from repro.program import (CombineOp, StencilOp, StencilProgram,
                           hdiff_program, lower, two_stage_heat)

ENGINES = ("interp", "vector")


def _coeffs(rng, r):
    return tuple((rng.normal(size=2 * r + 1) / (2 * r + 1)).tolist())


def run_both(mk_plan, x, routed=False, **kw):
    """Simulate a freshly-built plan once per engine (+ fresh routes)."""
    out = []
    for engine in ENGINES:
        plan = mk_plan()
        fab = None
        if routed:
            fab = route(place(plan, FabricTopology.mesh(16, 16), seed=0))
        out.append((plan, simulate(plan, x, CGRA, fabric=fab, engine=engine,
                                   **kw)))
    return out


def assert_identical(case):
    (plan_i, a), (plan_v, b) = case
    assert a.cycles == b.cycles
    assert a.fires == b.fires
    assert (a.loads, a.stores, a.flops) == (b.loads, b.stores, b.flops)
    assert a.max_queue_total == b.max_queue_total
    assert a.output.shape == b.output.shape
    assert a.output.tobytes() == b.output.tobytes()      # bit-identical
    # per-node fire counts (PE utilization) must agree node-for-node
    fa = {n.name: n.fires for n in plan_i.dfg.nodes}
    fb = {n.name: n.fires for n in plan_v.dfg.nodes}
    assert fa == fb
    if a.fabric is not None:
        assert a.fabric["token_hops"] == b.fabric["token_hops"]
        assert a.fabric["stall_cycles"] == b.fabric["stall_cycles"]


@pytest.mark.parametrize("routed", [False, True])
@pytest.mark.parametrize("n,r,w", [(120, 1, 3), (240, 2, 4), (510, 8, 6)])
def test_1d_identical(rng, n, r, w, routed):
    spec = StencilSpec((n,), (r,), (_coeffs(rng, r),), dtype="float64")
    assert_identical(run_both(lambda: map_1d(spec, workers=w),
                              rng.normal(size=n), routed=routed))


@pytest.mark.parametrize("routed", [False, True])
def test_2d_identical(rng, routed):
    spec = paper_stencil_2d(ny=30, nx=48, r=12)
    assert_identical(run_both(lambda: map_2d(spec, workers=8),
                              rng.normal(size=(30, 48)), routed=routed))


@pytest.mark.parametrize("routed", [False, True])
def test_3d_identical(rng, routed):
    spec = heat_3d(10, 12, 16, dtype="float64")
    assert_identical(run_both(lambda: map_3d(spec, workers=8),
                              rng.normal(size=(10, 12, 16)), routed=routed))


def test_temporal_identical(rng):
    spec = StencilSpec((360,), (2,), (_coeffs(rng, 2),), dtype="float64",
                       timesteps=3)
    assert_identical(run_both(lambda: map_1d(spec, workers=3),
                              rng.normal(size=360)))


def test_bounded_queues_identical(rng):
    """auto_capacity plans exercise the bounded-queue (out_free) path."""
    spec = heat_2d(18, 24, dtype="float64")
    assert_identical(run_both(
        lambda: map_2d(spec, workers=3, auto_capacity=True),
        rng.normal(size=(18, 24))))


def test_mem_efficiency_identical(rng):
    spec = StencilSpec((300,), (3,), (_coeffs(rng, 3),), dtype="float64")
    assert_identical(run_both(lambda: map_1d(spec, workers=5),
                              rng.normal(size=300), mem_efficiency=0.8))


@pytest.mark.parametrize("routed", [False, True])
@pytest.mark.parametrize("mk", [lambda: two_stage_heat(24, 32),
                                lambda: hdiff_program(24, 32)])
def test_program_identical(mk, routed):
    prog = mk()
    rng = np.random.default_rng(1)
    ins = {f: rng.normal(size=prog.grid_shape) for f in prog.in_fields}
    x = lower(prog, workers=4).pack_inputs(ins)
    assert_identical(run_both(lambda: lower(prog, workers=4), x,
                              routed=routed))


@pytest.mark.parametrize("routed", [False, True])
def test_program_remux_identical(routed):
    """Mismatched per-op worker counts insert the imux re-interleave."""
    prog = two_stage_heat(24, 32)
    rng = np.random.default_rng(1)
    ins = {f: rng.normal(size=prog.grid_shape) for f in prog.in_fields}
    workers = {"heat1": 2, "heat2": 4}
    x = lower(prog, workers=workers).pack_inputs(ins)
    assert_identical(run_both(lambda: lower(prog, workers=workers), x,
                              routed=routed))


def test_program_multi_output_identical():
    """Fan-out + two output fields: several cmp completion nodes."""
    lap = StencilOp("lap", heat_2d(20, 24, dtype="float64"), "inp", "lapf")
    mix = CombineOp("mix", ("inp", "lapf"), (1.0, -4.0), "mixf")
    prog = StencilProgram("twoout", [lap, mix], outputs=["lapf", "mixf"],
                          grid_shape=(20, 24), dtype="float64")
    rng = np.random.default_rng(2)
    ins = {f: rng.normal(size=prog.grid_shape) for f in prog.in_fields}
    x = lower(prog, workers=4).pack_inputs(ins)
    assert_identical(run_both(lambda: lower(prog, workers=4), x))


def test_wpc2_fabric_identical(rng):
    """words_per_cycle > 1 links exercise the general (word-counting)
    booking path instead of the wpc==1 fast path."""
    spec = paper_stencil_2d(ny=30, nx=48, r=12)
    x = rng.normal(size=(30, 48))
    out = []
    for engine in ENGINES:
        plan = map_2d(spec, workers=8)
        topo = FabricTopology.mesh(16, 16, words_per_cycle=2)
        fab = route(place(plan, topo, seed=0))
        out.append((plan, simulate(plan, x, CGRA, fabric=fab,
                                   engine=engine)))
    assert_identical(out)


def test_deadlock_identical(rng):
    """Starved queue capacities deadlock both engines at the same cycle
    with the same blocked-node diagnostic."""
    spec = heat_2d(18, 24, dtype="float64")
    x = rng.normal(size=(18, 24))
    msgs = []
    for engine in ENGINES:
        plan = map_2d(spec, workers=3, queue_capacity=1)
        with pytest.raises(SimDeadlock) as ei:
            simulate(plan, x, CGRA, max_cycles=200_000, engine=engine)
        msgs.append(str(ei.value))
    assert msgs[0] == msgs[1]
    assert "deadlock at cycle" in msgs[0]


def test_max_cycles_identical(rng):
    spec = StencilSpec((120,), (1,), ((0.25, 0.5, 0.25),), dtype="float64")
    x = rng.normal(size=120)
    for engine in ENGINES:
        plan = map_1d(spec, workers=3)
        with pytest.raises(SimDeadlock, match="exceeded max_cycles=10"):
            simulate(plan, x, CGRA, max_cycles=10, engine=engine)


def test_vector_faster_on_routed_program():
    """The point of the compiled engine: wall-clock on a routed program
    pipeline.  Deliberately loose (best-of-2, 1.2x) so a loaded CI host
    cannot flake it."""
    import time
    prog = two_stage_heat(24, 32)
    rng = np.random.default_rng(1)
    ins = {f: rng.normal(size=prog.grid_shape) for f in prog.in_fields}
    x = lower(prog, workers=4).pack_inputs(ins)
    walls = {}
    for engine in ENGINES:
        best = float("inf")
        for _ in range(2):
            plan = lower(prog, workers=4)
            fab = route(place(plan, FabricTopology.mesh(16, 16), seed=0))
            t0 = time.perf_counter()
            simulate(plan, x, CGRA, fabric=fab, engine=engine)
            best = min(best, time.perf_counter() - t0)
        walls[engine] = best
    assert walls["interp"] > 1.2 * walls["vector"], walls


# ---------------------------------------------------------------------------
# exact simulated counts: a change to the simulator's semantics that moves
# every engine alike still fails here
# ---------------------------------------------------------------------------
def _routed_pair(mk_plan, x):
    """Ideal and routed runs of fresh plans; the routed one placed on the
    16x16 mesh with placement seed 0."""
    ideal_plan, routed_plan = mk_plan(), mk_plan()
    fab = route(place(routed_plan, FabricTopology.mesh(16, 16), seed=0))
    ideal = simulate(ideal_plan, x, CGRA, engine="vector")
    routed = simulate(routed_plan, x, CGRA, fabric=fab, engine="vector")
    assert np.array_equal(ideal.output, routed.output)
    return ideal, routed, fab, len(ideal_plan.dfg.nodes)


def _counts(mk_plan, x):
    ideal, routed, _, n_nodes = _routed_pair(mk_plan, x)
    return (ideal.cycles, routed.cycles, n_nodes,
            routed.fabric["stall_cycles"], routed.fabric["token_hops"])


def _spec_counts(spec, mapper):
    x = np.random.default_rng(0).normal(size=spec.grid_shape)
    return _counts(lambda: mapper(spec, workers=8), x)


def _program_counts(prog):
    ins = {f: np.random.default_rng(0).normal(size=prog.grid_shape)
           for f in prog.in_fields}
    return _counts(lambda: lower(prog, workers=8),
                   lower(prog, workers=8).pack_inputs(ins))


def _fusion_counts(prog, w=4):
    """The program fused into one DAG vs each op as its own sweep through
    memory, ideal and routed cycles summed over the ops."""
    rng = np.random.default_rng(0)
    ins = {f: rng.normal(size=prog.grid_shape) for f in prog.in_fields}
    mk = lambda: lower(prog, workers=w)                 # noqa: E731
    ideal, routed, fab, _ = _routed_pair(mk, mk().pack_inputs(ins))
    sep_ideal = sep_routed = 0
    for op in prog.schedule():
        solo = StencilProgram(f"solo_{op.name}", [op],
                              grid_shape=prog.grid_shape, dtype=prog.dtype)
        solo_ins = {f: rng.normal(size=prog.grid_shape)
                    for f in solo.in_fields}
        mk_solo = lambda: lower(solo, workers=w)        # noqa: E731
        a, b, _, _ = _routed_pair(mk_solo, mk_solo().pack_inputs(solo_ins))
        sep_ideal += a.cycles
        sep_routed += b.cycles
    assert ideal.cycles < sep_ideal and routed.cycles < sep_routed
    return (ideal.cycles, routed.cycles, sep_ideal, sep_routed,
            fab.stats()["max_channel_load"])


# The integers come from the smoke snapshots of the pre-chip benchmark,
# reproduced with the vector engine at commit 08b0e3b.
@pytest.mark.parametrize("counts,pinned", [
    # (cycles ideal, cycles routed, PE instructions, stall cycles, token hops)
    pytest.param(lambda: _spec_counts(paper_stencil_1d(n=1200, rx=8), map_1d),
                 (239, 1886, 313, 3187499, 131693), id="1d"),
    pytest.param(lambda: _spec_counts(paper_stencil_2d(ny=30, nx=48, r=12),
                                      map_2d),
                 (189, 642, 833, 716046, 109809), id="2d"),
    pytest.param(lambda: _spec_counts(heat_3d(10, 12, 16, dtype="float64"),
                                      map_3d),
                 (319, 978, 169, 1151131, 58089), id="3d"),
    pytest.param(lambda: _program_counts(hdiff_program(48, 64)),
                 (566, 3317, 249, 8047371, 231149), id="hdiff"),
    pytest.param(lambda: _program_counts(two_stage_heat(48, 64)),
                 (567, 3370, 217, 9281674, 203861), id="heat2_pipeline"),
    pytest.param(lambda: _program_counts(two_stage_heat(96, 128)),
                 (2311, 12793, 217, 160809767, 841724),
                 id="large_heat2_pipeline"),
    # (fused ideal, fused routed, separate ideal, separate routed,
    #  max channel load)
    pytest.param(lambda: _fusion_counts(hdiff_program(24, 32)),
                 (204, 1668, 629, 4251, 9), id="hdiff_fused"),
    pytest.param(lambda: _fusion_counts(two_stage_heat(24, 32)),
                 (202, 1411, 398, 3078, 8), id="heat2_pipeline_fused"),
])
def test_simulated_counts_are_pinned(counts, pinned):
    assert counts() == pinned


# ---------------------------------------------------------------------------
# compile-then-mutate hazard (PR 5): recapacity after compile_plan() must
# invalidate the cached tables, never silently simulate with stale ones
# ---------------------------------------------------------------------------
def test_stale_compiled_plan_detected_and_recompiled(rng):
    from repro.core.engine import (StaleCompiledPlanError, compile_plan,
                                   compiled_for)
    from repro.core.mapping import apply_min_capacities

    spec = StencilSpec((96,), (2,), (_coeffs(rng, 2),), dtype="float64")
    plan = map_1d(spec, workers=3)
    cp = compiled_for(plan)
    assert cp.is_current()
    assert compiled_for(plan) is cp                   # cache hit, same tables

    apply_min_capacities(plan.dfg, plan.min_capacities)
    assert not cp.is_current()                        # version bump caught
    with pytest.raises(StaleCompiledPlanError):
        cp.require_current()
    cp2 = compiled_for(plan)                          # transparent recompile
    assert cp2 is not cp and cp2.is_current()

    # raw capacity writes without mark_mutated() are caught by the
    # capacity-signature check, not just the version counter
    cp3 = compile_plan(plan)
    next(plan.dfg.edges()).capacity = 9
    assert not cp3.is_current()


def test_interp_vector_parity_after_recapacity(rng):
    """Simulate unbounded with the vector engine (populating the compile
    cache), then apply the analytic minimum capacities to the *same* plan
    and re-simulate: the second run must see the bounded queues — identical
    to a fresh interp run of an identically-recapacitied plan."""
    from repro.core.mapping import apply_min_capacities

    spec = StencilSpec((140,), (2,), (_coeffs(rng, 2),), dtype="float64")
    x = rng.normal(size=140)

    def mk_bounded():
        p = map_1d(spec, workers=4)
        apply_min_capacities(p.dfg, p.min_capacities)
        return p

    plan = map_1d(spec, workers=4)
    unbounded_cycles = simulate(plan, x, CGRA, engine="vector").cycles
    apply_min_capacities(plan.dfg, plan.min_capacities)    # mutate in place
    res_mutated = simulate(plan, x, CGRA, engine="vector")

    res_interp = simulate(mk_bounded(), x, CGRA, engine="interp")
    res_vector = simulate(mk_bounded(), x, CGRA, engine="vector")
    assert res_mutated.cycles == res_interp.cycles == res_vector.cycles
    # (max_queue_total deliberately accumulates across runs of one plan
    # object, so only the fresh-plan runs are compared on it)
    assert res_interp.max_queue_total == res_vector.max_queue_total
    assert res_mutated.output.tobytes() == res_interp.output.tobytes()
    # the recapacity actually changed the timing (the hazard was observable)
    assert res_mutated.cycles != unbounded_cycles
