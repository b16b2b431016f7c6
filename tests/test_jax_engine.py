"""Cross-validation + batching: jitted jax engine vs the vector engine.

The jax engine (``engine="jax"``) must be *bit-identical* to the vector
engine on every ideal-mode observable — cycle counts, per-node fires,
load/store/flop totals, queue-occupancy telemetry and output grids — on
single-op mappings of every rank, temporal layers, program pipelines
(including the imux re-interleave), bounded and unbounded queues, derated
memory bandwidth, and the failure paths (deadlock, max_cycles).  On top of
that, the *batched* entry point (``simulate_batch`` / ``Budget.batch_size``)
must pad mixed-shape configs to a common shape without changing any lane's
result, report per-lane failures as values (one deadlocking lane never
poisons its siblings), refuse what it can't express (fabric, telemetry),
and key its EvalCache entries under its own engine semantics so batched
results are never replayed as vector results or vice versa.
"""
import numpy as np
import pytest

from repro.core import CGRA, SimDeadlock, map_1d, map_2d, map_3d, simulate
from repro.core.simulator import simulate_batch
from repro.core.spec import (StencilSpec, heat_2d, heat_3d, paper_stencil_2d)
from repro.program import (CombineOp, StencilOp, StencilProgram,
                           hdiff_program, lower, two_stage_heat)

ENGINES = ("vector", "jax")


def _coeffs(rng, r):
    return tuple((rng.normal(size=2 * r + 1) / (2 * r + 1)).tolist())


def run_both(mk_plan, x, **kw):
    """Simulate a freshly-built plan once per engine (ideal mode only —
    the jax engine cannot route)."""
    return [(plan, simulate(plan, x, CGRA, engine=engine, **kw))
            for engine in ENGINES
            for plan in (mk_plan(),)]


def assert_identical(case):
    (plan_v, a), (plan_j, b) = case
    assert a.cycles == b.cycles
    assert a.fires == b.fires
    assert (a.loads, a.stores, a.flops) == (b.loads, b.stores, b.flops)
    assert a.max_queue_total == b.max_queue_total
    assert a.output.shape == b.output.shape
    assert a.output.tobytes() == b.output.tobytes()      # bit-identical
    fa = {n.name: n.fires for n in plan_v.dfg.nodes}
    fb = {n.name: n.fires for n in plan_j.dfg.nodes}
    assert fa == fb


@pytest.mark.parametrize("n,r,w", [(120, 1, 3), (240, 2, 4), (510, 8, 6)])
def test_1d_identical(rng, n, r, w):
    spec = StencilSpec((n,), (r,), (_coeffs(rng, r),), dtype="float64")
    assert_identical(run_both(lambda: map_1d(spec, workers=w),
                              rng.normal(size=n)))


def test_2d_identical(rng):
    spec = paper_stencil_2d(ny=30, nx=48, r=12)
    assert_identical(run_both(lambda: map_2d(spec, workers=8),
                              rng.normal(size=(30, 48))))


def test_3d_identical(rng):
    spec = heat_3d(10, 12, 16, dtype="float64")
    assert_identical(run_both(lambda: map_3d(spec, workers=8),
                              rng.normal(size=(10, 12, 16))))


def test_temporal_identical(rng):
    spec = StencilSpec((360,), (2,), (_coeffs(rng, 2),), dtype="float64",
                       timesteps=3)
    assert_identical(run_both(lambda: map_1d(spec, workers=3),
                              rng.normal(size=360)))


def test_bounded_queues_identical(rng):
    """auto_capacity plans exercise the bounded-queue (out_ok) path."""
    spec = heat_2d(18, 24, dtype="float64")
    assert_identical(run_both(
        lambda: map_2d(spec, workers=3, auto_capacity=True),
        rng.normal(size=(18, 24))))


def test_mem_efficiency_identical(rng):
    spec = StencilSpec((300,), (3,), (_coeffs(rng, 3),), dtype="float64")
    assert_identical(run_both(lambda: map_1d(spec, workers=5),
                              rng.normal(size=300), mem_efficiency=0.8))


@pytest.mark.parametrize("mk", [lambda: two_stage_heat(24, 32),
                                lambda: hdiff_program(24, 32)])
def test_program_identical(mk):
    prog = mk()
    rng = np.random.default_rng(1)
    ins = {f: rng.normal(size=prog.grid_shape) for f in prog.in_fields}
    x = lower(prog, workers=4).pack_inputs(ins)
    assert_identical(run_both(lambda: lower(prog, workers=4), x))


def test_program_remux_identical():
    """Mismatched per-op worker counts insert the imux re-interleave."""
    prog = two_stage_heat(24, 32)
    rng = np.random.default_rng(1)
    ins = {f: rng.normal(size=prog.grid_shape) for f in prog.in_fields}
    workers = {"heat1": 2, "heat2": 4}
    x = lower(prog, workers=workers).pack_inputs(ins)
    assert_identical(run_both(lambda: lower(prog, workers=workers), x))


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


def test_deadlock_and_timeout_identical(rng):
    """Failure paths: message text, cycle count and flags must match the
    vector engine byte for byte."""
    spec = heat_2d(18, 24, dtype="float64")
    x = rng.normal(size=(18, 24))

    def deadlock(engine):
        with pytest.raises(SimDeadlock) as ei:
            simulate(map_2d(spec, workers=4, queue_capacity=1), x, CGRA,
                     engine=engine)
        return str(ei.value), ei.value.cycles, ei.value.timed_out

    assert deadlock("vector") == deadlock("jax")

    def timeout(engine):
        with pytest.raises(SimDeadlock) as ei:
            simulate(map_2d(spec, workers=4), x, CGRA, engine=engine,
                     max_cycles=50)
        return str(ei.value), ei.value.cycles, ei.value.timed_out

    msg, cycles, timed_out = timeout("jax")
    assert timeout("vector") == (msg, cycles, timed_out)
    assert "exceeded max_cycles=50" in msg and timed_out


def test_unsupported_paths_raise(rng):
    """The jax engine is ideal-mode only: fabric and telemetry raise."""
    from repro.fabric import FabricTopology, place, route
    from repro.telemetry import Telemetry
    spec = heat_2d(18, 24, dtype="float64")
    x = rng.normal(size=(18, 24))
    plan = map_2d(spec, workers=4)
    rf = route(place(plan, FabricTopology.mesh(16, 16), seed=0))
    with pytest.raises(NotImplementedError):
        simulate(plan, x, CGRA, fabric=rf, engine="jax")
    with pytest.raises(NotImplementedError):
        simulate(map_2d(spec, workers=4), x, CGRA, engine="jax",
                 telemetry=Telemetry())


# ---------------------------------------------------------------------------
# padded-batch correctness (satellite)
# ---------------------------------------------------------------------------
def test_batch_mixed_sizes_matches_sequential(rng):
    """A vmap batch mixing node/edge counts (padded to a common shape) must
    produce per-config results identical to B independent vector runs —
    including a deadlocking config, whose lane reports the deadlock as a
    value without poisoning its siblings."""
    spec = heat_2d(18, 24, dtype="float64")
    x = rng.normal(size=(18, 24))

    def mk_items():
        return [(map_2d(spec, workers=2), x),
                (map_2d(spec, workers=4, queue_capacity=1), x),  # deadlocks
                (map_2d(spec, workers=8), x),
                (map_2d(spec, workers=3, auto_capacity=True), x)]

    got_j = simulate_batch(mk_items(), CGRA, engine="jax")
    got_v = simulate_batch(mk_items(), CGRA, engine="vector")
    assert len(got_j) == len(got_v) == 4
    for i, (a, b) in enumerate(zip(got_j, got_v)):
        if i == 1:
            assert isinstance(a, SimDeadlock)
            assert isinstance(b, SimDeadlock)
            assert str(a) == str(b) and a.cycles == b.cycles
            assert not a.timed_out
        else:
            assert a.cycles == b.cycles
            assert a.output.tobytes() == b.output.tobytes()


def test_batch_of_one_matches_single(rng):
    spec = heat_2d(18, 24, dtype="float64")
    x = rng.normal(size=(18, 24))
    (res,) = simulate_batch([(map_2d(spec, workers=4), x)], CGRA,
                            engine="jax")
    ref = simulate(map_2d(spec, workers=4), x, CGRA, engine="vector")
    assert res.cycles == ref.cycles
    assert res.output.tobytes() == ref.output.tobytes()


# ---------------------------------------------------------------------------
# explore integration: Budget.batch_size
# ---------------------------------------------------------------------------
def test_explore_batched_stage1_matches_sequential():
    from repro.explore import Budget, SpaceOptions, explore
    spec = heat_2d(18, 24, dtype="float64")
    opts = SpaceOptions(fabrics=())
    seq = explore(spec, CGRA, options=opts, budget=Budget(), verify=True)
    bat = explore(spec, CGRA, options=opts, budget=Budget(batch_size=8),
                  verify=True)
    key = lambda p: sorted(p.config.canonical().items(),      # noqa: E731
                           key=str)
    s = {str(key(p)): (p.cycles, p.pes) for p in seq.ideal_points}
    b = {str(key(p)): (p.cycles, p.pes) for p in bat.ideal_points}
    assert s == b and s
    assert seq.best().objectives() == bat.best().objectives()
    assert bat.stats["n_host_fallback"] == 0


def test_explore_batched_counts_host_fallback(monkeypatch):
    """A lane the jax lowering rejects is re-run on the host engine and
    counted, so a sweep that left the device shows it in its stats."""
    from repro.core.engine import jax_engine
    from repro.explore import Budget, SpaceOptions, explore
    real = jax_engine.run_compiled_batch
    rejected = []

    def reject_first_lane(items, max_cycles):
        out = real(items, max_cycles=max_cycles)
        out[0] = jax_engine.JaxLoweringError("rejected by the test")
        rejected.append(1)
        return out

    monkeypatch.setattr(jax_engine, "run_compiled_batch", reject_first_lane)
    spec = heat_2d(18, 24, dtype="float64")
    opts = SpaceOptions(fabrics=())
    bat = explore(spec, CGRA, options=opts, budget=Budget(batch_size=8))
    seq = explore(spec, CGRA, options=opts, budget=Budget())
    assert rejected and bat.stats["n_host_fallback"] == len(rejected)
    assert seq.stats["n_host_fallback"] == 0
    assert ({p.config: p.cycles for p in bat.ideal_points}
            == {p.config: p.cycles for p in seq.ideal_points})


def test_explore_batched_respects_max_evals():
    from repro.explore import Budget, SpaceOptions, explore
    spec = heat_2d(18, 24, dtype="float64")
    res = explore(spec, CGRA, options=SpaceOptions(fabrics=()),
                  budget=Budget(max_evals=3, batch_size=8))
    assert res.stats["n_measured"] <= 3
    assert res.stats["n_budget_skipped"] > 0


def test_explore_batched_routes_finalists_with_vector_engine():
    """Stage 2 (routed finalists) always uses the sequential engine; the
    batched stage 1 must not change what the tuner ultimately picks."""
    from repro.explore import Budget, SpaceOptions, explore
    spec = heat_2d(18, 24, dtype="float64")
    opts = SpaceOptions(fabrics=((16, 16, "mesh"),))
    bat = explore(spec, CGRA, options=opts, budget=Budget(batch_size=8))
    seq = explore(spec, CGRA, options=opts, budget=Budget())
    assert bat.points and all(p.routed for p in bat.points)
    assert bat.best().objectives() == seq.best().objectives()


# ---------------------------------------------------------------------------
# EvalCache engine scoping (satellite)
# ---------------------------------------------------------------------------
def test_cache_cross_engine_miss():
    """Batched-jax results are keyed under the jax engine + semantics
    version, so a sequential vector run on the same cache re-measures
    every config (cross-engine replay is a correctness bug: the scopes
    must never collide)."""
    from repro.explore import Budget, EvalCache, SpaceOptions, explore
    spec = heat_2d(18, 24, dtype="float64")
    opts = SpaceOptions(fabrics=())
    cache = EvalCache(None)
    bat = explore(spec, CGRA, options=opts, budget=Budget(batch_size=8),
                  cache=cache)
    n = bat.stats["n_measured"]
    assert n > 0
    entries_after_batch = len(cache)

    # same cache, same configs, batched again: all replayed, zero measured
    bat2 = explore(spec, CGRA, options=opts, budget=Budget(batch_size=8),
                   cache=cache)
    assert bat2.stats["n_measured"] == 0
    assert len(cache) == entries_after_batch

    # same cache, sequential vector: every config must MISS and re-measure
    seq = explore(spec, CGRA, options=opts, budget=Budget(), cache=cache)
    assert seq.stats["n_measured"] == n
    assert len(cache) == 2 * entries_after_batch
    # and the two engines' measurements agree, each under its own key
    key = lambda p: str(sorted(p.config.canonical().items(),  # noqa: E731
                               key=str))
    assert ({key(p): p.cycles for p in bat.ideal_points}
            == {key(p): p.cycles for p in seq.ideal_points})


def test_engine_semantics_registry():
    """ENGINE_SEMANTICS names every engine and mirrors the jax module."""
    from repro.core.engine import ENGINE_SEMANTICS
    from repro.core.engine import jax_engine
    from repro.core.simulator import ENGINES as ALL_ENGINES
    assert set(ENGINE_SEMANTICS) == set(ALL_ENGINES)
    assert ENGINE_SEMANTICS["jax"] == jax_engine.SEMANTICS
