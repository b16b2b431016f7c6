"""The mapping auto-tuner (repro.explore): lattice, pruning, Pareto, search.

Covers the subsystem contract end to end: canonical config hashing (the
cache key), roofline/feasibility pruning with recorded reasons, budgeted
vector-engine evaluation with failure capture (deadlocks, fabric overflow),
Pareto-front soundness, the always-measured analytical baseline, the
persistent eval cache that makes reruns free, and the pinned fronts of three
routed searches.
"""
import json

import pytest

from repro.core import CGRA, Machine
from repro.core.spec import StencilSpec, heat_2d, star_3d
from repro.explore import (Budget, EvalCache, EvalPoint, MappingConfig,
                           SpaceOptions, SpecTarget, analytic_config,
                           assert_non_dominated, best_point, dominates,
                           enumerate_space, explore, pareto_front,
                           prune_reason, prune_space, tile_candidates)


def small_1d(n=60, r=1):
    coeffs = tuple([1.0 / (2 * r + 1)] * (2 * r + 1))
    return StencilSpec((n,), (r,), (coeffs,), dtype="float64")


# ---------------------------------------------------------------------------
# pareto.py
# ---------------------------------------------------------------------------
def test_dominates_semantics():
    assert dominates((1, 1, 1), (2, 2, 2))
    assert dominates((1, 2, 3), (1, 2, 4))
    assert not dominates((1, 2, 3), (1, 2, 3))      # equal: no domination
    assert not dominates((1, 5), (2, 4))            # trade-off: incomparable
    with pytest.raises(ValueError):
        dominates((1, 2), (1, 2, 3))


def test_pareto_front_and_best():
    pts = [(10, 5, 0), (8, 9, 0), (10, 5, 0), (12, 4, 0), (11, 9, 9)]
    front = pareto_front(pts)
    assert front == [(10, 5, 0), (8, 9, 0), (10, 5, 0), (12, 4, 0)]
    assert_non_dominated(front)
    assert best_point(front) == (8, 9, 0)           # lexicographic: cycles
    with pytest.raises(AssertionError):
        assert_non_dominated(pts)                   # (11,9,9) is dominated
    assert pareto_front([]) == []
    with pytest.raises(ValueError):
        best_point([])


# ---------------------------------------------------------------------------
# space.py
# ---------------------------------------------------------------------------
def test_config_canonical_key_scopes():
    scope = {"target": "t", "machine": "m"}
    a = MappingConfig(workers=4, fabric=(16, 16, "mesh"), place_seed=1)
    b = MappingConfig(workers=4, fabric=(8, 8, "torus"), place_seed=2)
    c = MappingConfig(workers=5)
    # ideal keys ignore physical knobs -> routed variants share one ideal eval
    assert a.key(scope, ideal=True) == b.key(scope, ideal=True)
    assert a.key(scope) != b.key(scope)
    assert a.key(scope, ideal=True) != c.key(scope, ideal=True)
    assert a.key(scope) != a.key({"target": "other", "machine": "m"})
    with pytest.raises(ValueError):
        MappingConfig(workers=2, capacity="bogus")
    with pytest.raises(ValueError):
        MappingConfig(workers=2, capacity=0)


def test_enumerate_space_seeds_analytic():
    target = SpecTarget(heat_2d(12, 24, dtype="float64"))
    configs, analytic = enumerate_space(
        target, CGRA, SpaceOptions(workers=(1, 2)))
    assert analytic in configs                      # seeded even if missing
    assert analytic.workers not in (1, 2) or configs[0].workers in (1, 2)
    # analytical choice is feasible: divides the innermost extent
    assert 24 % analytic.workers == 0


def test_analytic_config_clamps_to_divisor():
    # inner extent 26: the roofline pick (4 for this spec) doesn't divide it,
    # so the seed clamps down to the largest feasible worker count
    spec = heat_2d(12, 26, dtype="float64")
    cfg = analytic_config(SpecTarget(spec), CGRA)
    assert 26 % cfg.workers == 0 and cfg.workers >= 1


def test_tile_candidates_ladder():
    spec = heat_2d(64, 128, dtype="float64")
    tiles = tile_candidates(spec, (1, 4096, 16384, 1 << 30))
    assert len(tiles) == len(set(tiles))            # distinct
    for t in tiles:
        if t is not None:
            assert len(t) == 2 and all(b >= 1 for b in t)
    assert None in tiles                            # 1<<30 holds the grid


# ---------------------------------------------------------------------------
# prune.py
# ---------------------------------------------------------------------------
def test_prune_reasons():
    target = SpecTarget(heat_2d(12, 24, dtype="float64"),
                        workload_timesteps=2)
    opts = SpaceOptions()
    assert prune_reason(target, CGRA,
                        MappingConfig(workers=5), opts) == "indivisible"
    assert prune_reason(target, CGRA, MappingConfig(workers=24),
                        opts) == "no-interior"
    assert prune_reason(target, CGRA, MappingConfig(workers=2, temporal=3),
                        opts) == "temporal"
    assert prune_reason(target, CGRA,
                        MappingConfig(workers=2, tile=(2, 24)),
                        opts) == "tile-degenerate"   # 2 - 2*1*1 < 1
    small = Machine("m", clock_ghz=1.0, num_macs=8, bw_gbps=100.0,
                    peak_gflops=16.0)
    assert prune_reason(target, small, MappingConfig(workers=4),
                        opts) == "mac-overflow"
    ok = MappingConfig(workers=4)
    assert prune_reason(target, CGRA, ok, opts) is None


def test_prune_roofline_excess_exempts_analytic():
    target = SpecTarget(small_1d(200, 2))
    opts = SpaceOptions(worker_slack=0)
    analytic = analytic_config(target, CGRA)
    big = MappingConfig(workers=analytic.workers + 1)
    kept, log = prune_space(target, CGRA, [analytic, big], opts,
                            keep=analytic)
    assert analytic in kept
    assert ("roofline-excess" in log.reasons) == (big not in kept)
    assert log.as_dict() == log.reasons


# ---------------------------------------------------------------------------
# cache.py
# ---------------------------------------------------------------------------
def test_eval_cache_roundtrip(tmp_path):
    p = tmp_path / "cache.json"
    c = EvalCache(p)
    assert c.get("k") is None and c.misses == 1
    c.put("k", {"cycles": 7})
    c.save()
    c2 = EvalCache(p)
    assert c2.get("k") == {"cycles": 7} and c2.hits == 1
    # corrupted file degrades to an empty cache, never raises
    p.write_text("{not json")
    assert len(EvalCache(p)) == 0
    # schema mismatch likewise
    p.write_text(json.dumps({"schema": "other/v9", "entries": {"k": {}}}))
    assert len(EvalCache(p)) == 0


# ---------------------------------------------------------------------------
# search.py — ideal mode
# ---------------------------------------------------------------------------
def test_explore_ideal_end_to_end():
    res = explore(small_1d(), CGRA,
                  options=SpaceOptions(workers=(1, 2, 3, 4)), verify=True)
    # 4 requested + the always-seeded analytical config (w*=6 here)
    assert res.stats["n_measured"] == len(res.points) == 5
    assert_non_dominated(res.front, key=EvalPoint.objectives)
    assert res.analytic is not None
    assert res.best().cycles <= res.analytic.cycles
    assert res.best().cycles == min(p.cycles for p in res.points)
    # more workers strictly reduces cycles on this memory-light case
    by_w = {p.config.workers: p.cycles for p in res.points}
    assert by_w[4] < by_w[1]
    # every point carries the instruction count as its PE objective
    assert all(p.pes > 0 and p.max_channel_load == 0 for p in res.points)


def test_explore_verifies_numerics_against_oracle():
    """verify=True cross-checks each measured output against the reference
    oracle — exercised here both for plain and temporal configs."""
    res = explore(small_1d(80), CGRA, workload_timesteps=2,
                  options=SpaceOptions(workers=(2, 3), temporal=(1, 2)),
                  verify=True)
    temporals = {p.config.temporal for p in res.points}
    assert temporals == {1, 2}
    # a fused pass covers two sweeps: workload cycles halve-ish vs repeats
    one = min(p.cycles for p in res.points if p.config.temporal == 1)
    two = min(p.cycles for p in res.points if p.config.temporal == 2)
    assert two < one


def test_explore_budget_stops_after_analytic():
    res = explore(small_1d(), CGRA,
                  options=SpaceOptions(workers=(1, 2, 3, 4)),
                  budget=Budget(max_evals=1))
    assert res.stats["n_measured"] == 1
    assert res.stats["n_budget_skipped"] >= 3
    assert res.analytic is not None            # the baseline spends first
    assert res.front == [res.analytic]


def test_explore_cache_makes_rerun_free(tmp_path):
    p = tmp_path / "evals.json"
    kw = dict(options=SpaceOptions(workers=(1, 2, 3)))
    first = explore(small_1d(), CGRA, cache=EvalCache(p), **kw)
    n0 = first.stats["n_measured"]
    assert n0 == len(first.points) > 0
    again = explore(small_1d(), CGRA, cache=EvalCache(p), **kw)
    assert again.stats["n_measured"] == 0
    assert again.stats["n_cached"] == n0
    assert [p2.objectives() for p2 in again.points] == \
        [p1.objectives() for p1 in first.points]
    # a different machine must not hit the same entries
    other = explore(small_1d(), Machine("m2", 1.0, 128, 50.0, 256.0),
                    cache=EvalCache(p), **kw)
    assert other.stats["n_measured"] > 0


def test_explore_records_deadlock_as_failure():
    """A fixed queue capacity below the mandatory-buffering bound is doomed;
    the static gate (default on) rejects it before any simulation — with a
    repair hint — and the failure is cached; with the gate off the engine
    discovers the same deadlock dynamically."""
    spec = heat_2d(10, 20, dtype="float64")    # 2D: outer-axis gate >> 1
    cache = EvalCache()
    opts = SpaceOptions(workers=(2,), capacities=(1, "auto"))
    res = explore(spec, CGRA, options=opts, cache=cache)
    reasons = [f["reason"] for f in res.failures]
    assert any(r.startswith("static-capacity") for r in reasons), reasons
    assert res.stats["static_pruned"] > 0
    assert res.front                          # the auto config still wins
    assert all(p.config.capacity == "auto" for p in res.front)
    # the failure is cached: a rerun skips the doomed config entirely,
    # replaying the capacity-repair hint from the cache record
    res2 = explore(spec, CGRA, options=opts, cache=cache)
    cached = [f for f in res2.failures if f.get("cached")]
    assert cached and all(f["suggested_capacities"] for f in cached)
    # gate off: the engine pays for the same discovery dynamically
    res3 = explore(spec, CGRA, options=opts, cache=EvalCache(),
                   static_verify=False)
    reasons3 = [f["reason"] for f in res3.failures]
    assert any(r.startswith("deadlock") for r in reasons3), reasons3
    assert res3.stats["static_pruned"] == 0
    # the gate rejects exactly the configs the engine finds doomed
    assert res.stats["static_pruned"] == sum(
        r.startswith(("deadlock", "timeout")) for r in reasons3)
    # and the gate never changes the search outcome
    assert sorted(p.objectives() for p in res3.points) == \
        sorted(p.objectives() for p in res.points)


def test_static_gate_hint_replays_onto_rebuilt_plan():
    """eids are deterministic per config: the JSON-string hint a cached
    failure replays applies cleanly to a freshly rebuilt plan and makes it
    complete."""
    from repro.analysis import apply_suggested_capacities
    from repro.core import map_2d, simulate

    spec = heat_2d(10, 20, dtype="float64")
    res = explore(spec, CGRA,
                  options=SpaceOptions(workers=(2,), capacities=(1, "auto")),
                  cache=EvalCache())
    fail = next(f for f in res.failures
                if f["reason"].startswith("static-capacity"))
    hint = fail["suggested_capacities"]
    assert all(isinstance(k, str) for k in hint)   # JSON-stable form
    plan = map_2d(spec, workers=2, queue_capacity=1)
    assert apply_suggested_capacities(plan, hint) > 0
    import numpy as np
    x = np.random.default_rng(0).normal(size=spec.grid_shape)
    simulate(plan, x, CGRA, max_cycles=2_000_000)  # deadlock would raise


def test_static_paranoia_mode():
    """static_paranoia simulates every statically-rejected config and
    asserts it really deadlocks — it must pass on a true deadlock and the
    results must match the non-paranoid run."""
    spec = heat_2d(10, 20, dtype="float64")
    opts = SpaceOptions(workers=(2,), capacities=(1, "auto"))
    res = explore(spec, CGRA, options=opts, cache=EvalCache(),
                  static_paranoia=True)
    assert res.stats["static_pruned"] > 0
    base = explore(spec, CGRA, options=opts, cache=EvalCache())
    assert sorted(p.objectives() for p in res.points) == \
        sorted(p.objectives() for p in base.points)


def test_static_gate_batched_stage1():
    """The batched jax stage 1 applies the same static gate at lane-build
    time: same pruned reasons, same survivors as the sequential path."""
    spec = heat_2d(10, 20, dtype="float64")
    opts = SpaceOptions(workers=(2,), capacities=(1, "auto"))
    seq = explore(spec, CGRA, options=opts, cache=EvalCache())
    bat = explore(spec, CGRA, options=opts, cache=EvalCache(),
                  budget=Budget(batch_size=4))
    assert bat.stats["static_pruned"] == seq.stats["static_pruned"] > 0
    assert sorted(p.sim_cycles for p in bat.ideal_points) == \
        sorted(p.sim_cycles for p in seq.ideal_points)


def test_static_semantics_scopes_cache(tmp_path):
    """Entries taken under the static gate must not replay for a run with
    the gate off (and vice versa): static_semantics is part of the scope,
    exactly like a verifier version bump would be."""
    p = str(tmp_path / "cache.json")
    spec = heat_2d(10, 20, dtype="float64")
    opts = SpaceOptions(workers=(2,), capacities=(1, "auto"))
    first = explore(spec, CGRA, options=opts, cache=EvalCache(p))
    assert first.stats["n_measured"] > 0
    # same gate: full replay
    again = explore(spec, CGRA, options=opts, cache=EvalCache(p))
    assert again.stats["n_measured"] == 0
    # gate off = different verifier semantics: nothing replays
    off = explore(spec, CGRA, options=opts, cache=EvalCache(p),
                  static_verify=False)
    assert off.stats["n_measured"] > 0


# ---------------------------------------------------------------------------
# search.py — routed mode
# ---------------------------------------------------------------------------
def test_explore_routed_finalists():
    res = explore(heat_2d(12, 24, dtype="float64"), CGRA,
                  options=SpaceOptions(workers=(2, 4),
                                       fabrics=((12, 12, "mesh"),),
                                       place_seeds=(0, 1)),
                  budget=Budget(routed_finalists=2))
    assert res.points and all(p.routed for p in res.points)
    assert all(p.max_channel_load > 0 for p in res.points)
    assert_non_dominated(res.front, key=EvalPoint.objectives)
    assert res.analytic is not None and res.analytic.routed
    assert res.best().cycles <= res.analytic.cycles
    # routed PEs-used is a physical count, below the instruction total
    ideal_pes = {p.config.workers: p.pes for p in res.ideal_points}
    for p in res.points:
        assert p.pes <= ideal_pes[p.config.workers]
    # the ideal stage still ran (and is reported) for every kept config
    # (the analytical w=4 coincides with a requested worker count)
    assert len(res.ideal_points) == 2


def test_explore_fabric_overflow_recorded():
    """A fabric too small for the plan must surface as a recorded failure,
    not a crash — and leave the front empty when nothing fits."""
    res = explore(small_1d(40), CGRA,
                  options=SpaceOptions(workers=(3,),
                                       fabrics=((2, 2, "mesh"),)))
    assert res.points == [] and res.front == []
    assert any("fabric-slots" in f["reason"] for f in res.failures)


# ---------------------------------------------------------------------------
# program targets
# ---------------------------------------------------------------------------
def test_explore_program_target():
    from repro.program import two_stage_heat

    prog = two_stage_heat(12, 24)
    res = explore(prog, CGRA, options=SpaceOptions(workers=(2, 4)),
                  verify=True)
    assert res.target == prog.name
    assert len(res.points) == 2
    assert_non_dominated(res.front, key=EvalPoint.objectives)
    assert res.best().cycles <= res.analytic.cycles
    # temporal/tile knobs are inert for programs: enumerating them anyway
    # must not change the lattice
    res2 = explore(prog, CGRA,
                   options=SpaceOptions(workers=(2, 4), temporal=(1, 2),
                                        tiles=(None, (4, 8))))
    assert res2.stats["n_kept"] == res.stats["n_kept"]


def test_explore_star3d_smoke():
    res = explore(star_3d(8, 10, 12, r=1), CGRA,
                  options=SpaceOptions(workers=(1, 2, 4)))
    assert len(res.points) == 4      # + the analytical seed (w*=3 here)
    assert res.best().cycles <= res.analytic.cycles


def test_explore_timeout_not_poisoned_across_budgets(tmp_path):
    """A max_cycles timeout under a tiny per-sim guard must not be replayed
    from cache as a permanent failure once the guard is raised — the guard
    is part of the cache scope (code-review regression)."""
    cache_path = tmp_path / "evals.json"
    spec = small_1d(120)
    opts = SpaceOptions(workers=(2,))
    starved = explore(spec, CGRA, options=opts,
                      budget=Budget(sim_max_cycles=5),
                      cache=EvalCache(cache_path))
    reasons = [f["reason"] for f in starved.failures]
    assert starved.points == [] and any(
        r.startswith("timeout") for r in reasons), reasons
    # the starved timeouts consumed budget: they are not free retries
    assert starved.stats["sim_cycles_total"] > 0
    # same cache, sane guard: the config is re-measured, not replayed failed
    healthy = explore(spec, CGRA, options=opts,
                      cache=EvalCache(cache_path))
    assert healthy.points and not healthy.failures
    assert healthy.stats["n_measured"] == len(healthy.points)


# ---------------------------------------------------------------------------
# pinned fronts: routed searches on the 16x16 mesh, no cache
# ---------------------------------------------------------------------------
def _heat2d_search():
    heat = heat_2d(24, 48, dtype="float64")
    tiles = tuple(t for t in tile_candidates(heat, (2048, 8192))
                  if t is not None)
    return heat, 2, SpaceOptions(temporal=(1, 2),
                                 capacities=("auto", "unbounded"),
                                 tiles=(None,) + tiles,
                                 fabrics=((16, 16, "mesh"),),
                                 place_seeds=(0, 1))


def _star3d_search():
    return star_3d(10, 12, 16), 1, SpaceOptions(
        workers=(1, 2, 4, 8), capacities=("auto",),
        fabrics=((16, 16, "mesh"),), place_seeds=(0,))


def _hdiff_search():
    from repro.program import hdiff_program
    return hdiff_program(24, 32), 1, SpaceOptions(
        workers=(2, 4, 8), capacities=("auto", "unbounded"),
        fabrics=((16, 16, "mesh"),), place_seeds=(0,))


# The integers come from the smoke snapshot of the pre-chip benchmark's
# tuner fronts, reproduced at commit 08b0e3b.
@pytest.mark.parametrize("search,analytic,best", [
    # (cycles, PEs, max channel load) of the analytical baseline and the best
    pytest.param(_heat2d_search, (3276, 23, 6), (906, 59, 6), id="heat2d"),
    pytest.param(_star3d_search, (2978, 28, 8), (1418, 61, 8), id="star_3d"),
    pytest.param(_hdiff_search, (1614, 51, 9), (1614, 51, 9), id="hdiff"),
])
def test_tuner_front_is_pinned(search, analytic, best):
    target, timesteps, options = search()
    res = explore(target, CGRA, options=options,
                  budget=Budget(routed_finalists=4),
                  workload_timesteps=timesteps, verify=True)
    assert_non_dominated(res.front, key=EvalPoint.objectives)
    assert res.analytic.objectives() == analytic
    assert res.best().objectives() == best
