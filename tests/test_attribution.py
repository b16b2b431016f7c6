"""Attribution + metrics gates (PR 8, docs/telemetry.md).

The cycle-accounting profiler's two hard contracts:

* **exactness** — ``fill + steady + drain == SimResult.cycles`` and the
  fired/inactive/stall-cause node-cycles tile ``cycles * n_nodes``, on
  every case, both modes.
* **engine bit-identity** — ``attribute()`` is a pure function of the
  parity-gated telemetry sink, so the whole accounting (phases, causes,
  stage table, critical path, bottleneck label) must serialize identically
  for the interpreter and the compiled vector engine.

Plus the tuner's bottleneck labels and the routed auto-capacity regression
gate (satellite 1).
"""
import json

import numpy as np
import pytest

from repro.core import CGRA, map_1d, map_2d, simulate
from repro.core.spec import StencilSpec, heat_2d, paper_stencil_2d
from repro.fabric import (FabricTopology, apply_routed_capacities, place,
                          route)
from repro.program import hdiff_program, lower, two_stage_heat
from repro.telemetry import (STALL_CAUSES, CycleAccounting, Telemetry,
                             attribute, render_attribution, stage_label)
from repro.telemetry.attribution import STAGE_ORDER

ENGINES = ("interp", "vector")


def _accounts(mk_plan, x, routed, timeline=False):
    """attribute() both engines' runs of the same case."""
    out = []
    for engine in ENGINES:
        plan = mk_plan()
        fab = None
        if routed:
            fab = route(place(plan, FabricTopology.mesh(16, 16), seed=0))
        tel = Telemetry(timeline=timeline)
        res = simulate(plan, x, CGRA, fabric=fab, engine=engine,
                       telemetry=tel)
        out.append((attribute(tel, res), res, tel))
    return out


def _assert_exact(acct: CycleAccounting, res):
    assert sum(acct.phases.values()) == res.cycles == acct.cycles
    assert all(v >= 0 for v in acct.phases.values())
    tiled = acct.fired + acct.inactive + sum(acct.causes.values())
    assert tiled == acct.cycles * acct.n_nodes
    for row in acct.stages.values():
        per_stage = (row["fired"] + row["inactive"]
                     + sum(row[c] for c in STALL_CAUSES))
        assert per_stage == acct.cycles * row["nodes"]
    assert sum(r["nodes"] for r in acct.stages.values()) == acct.n_nodes


CASES = {}


def _case_1d(rng):
    spec = StencilSpec((240,), (2,),
                       (tuple((rng.normal(size=5) / 5).tolist()),),
                       dtype="float64")
    return lambda: map_1d(spec, workers=4), rng.normal(size=240)


def _case_2d(rng):
    spec = paper_stencil_2d(ny=30, nx=48, r=12)
    return lambda: map_2d(spec, workers=8), rng.normal(size=(30, 48))


def _case_program(rng):
    prog = two_stage_heat(24, 32)
    ins = {f: rng.normal(size=prog.grid_shape) for f in prog.in_fields}
    x = lower(prog, workers=4).pack_inputs(ins)
    return lambda: lower(prog, workers=4), x


@pytest.mark.parametrize("case", ["1d", "2d", "program"])
@pytest.mark.parametrize("routed", [False, True])
def test_attribution_exact_and_engine_identical(rng, case, routed):
    mk, x = {"1d": _case_1d, "2d": _case_2d, "program": _case_program}[
        case](rng)
    (aa, ra, _), (ab, rb, _) = _accounts(mk, x, routed)
    # bit-identical across engines, including through JSON serialization
    assert aa.as_dict() == ab.as_dict()
    assert json.dumps(aa.as_dict(), sort_keys=True) == \
        json.dumps(ab.as_dict(), sort_keys=True)
    for acct, res in ((aa, ra), (ab, rb)):
        _assert_exact(acct, res)
    # routed runs attribute network time; ideal runs never can
    if not routed:
        assert aa.causes["network_contention"] == 0
    # counter-only sinks (timeline=False) reach the same accounting
    (ac, _, _), _ = _accounts(mk, x, routed, timeline=False)
    assert ac.as_dict() == aa.as_dict()


def test_phase_decomposition_semantics(rng):
    """fill ends before the first store; drain starts after the last load;
    a pipeline long enough to stream has nonzero steady state (ideal)."""
    mk, x = _case_2d(rng)
    (acct, res, tel), _ = _accounts(mk, x, routed=False)
    stores = [nid for nid, op in enumerate(tel.node_ops) if op == "store"
              and tel.fires_total[nid] > 0]
    first_store = min(int(tel.first_fire[nid]) for nid in stores)
    assert acct.phases["fill"] == first_store - 1
    assert acct.phases["steady"] > 0
    loads = [nid for nid, op in enumerate(tel.node_ops) if op == "load"]
    last_load = max(int(tel.last_fire[nid]) for nid in loads)
    assert acct.phases["drain"] == res.cycles - last_load


def test_stage_labels_cover_pipeline(rng):
    mk, x = _case_2d(rng)
    (acct, _, _), _ = _accounts(mk, x, routed=False)
    assert set(acct.stages) == set(STAGE_ORDER)
    assert stage_label("compute", "add") == "AddTree"
    assert stage_label("compute", "mac") == "TapChain"
    assert stage_label("compute", "imux") == "TapChain"
    assert stage_label("reader", "load") == "ReaderBank"
    assert stage_label("writer", "store") == "WriterBank"
    assert stage_label("sync", "cmp") == "SyncTree"


def test_critical_path_is_causal_chain(rng):
    mk, x = _case_2d(rng)
    (acct, res, tel), _ = _accounts(mk, x, routed=True)
    path = acct.critical_path
    assert len(path) >= 3
    # source -> sink: starts at a reader, ends at the completion side
    assert path[-1]["stage"] == "SyncTree"
    assert path[0]["stage"] == "ReaderBank"
    assert path[-1]["last_fire"] == res.cycles
    # every consecutive pair is a real DFG edge (the chain is causal in
    # graph structure; last_fire need not be monotone — a producer can
    # keep firing after its consumer retires)
    by_name = {n.name: n for n in tel.plan.dfg.nodes}
    for a, b in zip(path, path[1:]):
        dst = by_name[b["name"]]
        assert any(e.src.name == a["name"] for e in dst.in_edges)
    for d in path:
        assert d["fires"] > 0
        if d["stalled"]:
            assert d["cause"] in STALL_CAUSES


def test_bottleneck_labels():
    from repro.telemetry.attribution import _bottleneck
    ph = {"fill": 10, "steady": 80, "drain": 10}
    assert _bottleneck(100, {"fill": 60, "steady": 20, "drain": 20},
                       {}) == "fill-bound"
    assert _bottleneck(100, ph, {c: 0 for c in STALL_CAUSES}) == \
        "compute-bound"
    assert _bottleneck(100, ph, {"input_starved": 5}) == "starved"
    assert _bottleneck(100, ph, {"output_blocked": 9,
                                 "input_starved": 2}) == "capacity-bound"
    assert _bottleneck(100, ph, {"memory_arbitration": 9}) == "memory-bound"
    assert _bottleneck(100, ph, {"network_contention": 9,
                                 "input_starved": 3}) == "network-bound"


def test_attribute_rejects_unfinished_and_mismatched(rng):
    tel = Telemetry()
    with pytest.raises(ValueError, match="observed a run"):
        attribute(tel)
    mk, x = _case_1d(rng)
    (acct, res, tel), _ = _accounts(mk, x, routed=False)

    class FakeRes:
        cycles = res.cycles + 1
    with pytest.raises(AssertionError, match="SimResult says"):
        attribute(tel, FakeRes())


def test_render_attribution_smoke(rng):
    mk, x = _case_2d(rng)
    (acct, _, _), _ = _accounts(mk, x, routed=True)
    text = render_attribution(acct)
    assert "cycle accounting" in text and "critical path" in text
    for stage in STAGE_ORDER:
        assert stage in text


# ---------------------------------------------------------------------------
# tuner threading: bottleneck labels on evaluations (tentpole)
# ---------------------------------------------------------------------------
def test_explore_labels_bottlenecks(tmp_path):
    from repro.core.spec import heat_2d as _heat
    from repro.explore import Budget, SpaceOptions, explore

    res = explore(_heat(18, 36, dtype="float64"), CGRA,
                  options=SpaceOptions(workers=(2, 4), capacities=("auto",),
                                       fabrics=((8, 8, "mesh"),),
                                       place_seeds=(0,)),
                  budget=Budget(routed_finalists=2),
                  cache=str(tmp_path / "c.json"),
                  telemetry=Telemetry())
    labels = {"fill-bound", "compute-bound", "starved", "capacity-bound",
              "memory-bound", "network-bound"}
    assert res.front
    for pt in res.front + res.ideal_points:
        assert pt.bottleneck in labels
        assert pt.as_dict()["bottleneck"] == pt.bottleneck
    # cached replays carry the label too
    res2 = explore(_heat(18, 36, dtype="float64"), CGRA,
                   options=SpaceOptions(workers=(2, 4), capacities=("auto",),
                                        fabrics=((8, 8, "mesh"),),
                                        place_seeds=(0,)),
                   budget=Budget(routed_finalists=2),
                   cache=str(tmp_path / "c.json"))
    assert res2.stats["cache"]["hits"] > 0
    for pt in res2.front:
        assert pt.cached and pt.bottleneck in labels
    assert {p.config: p.bottleneck for p in res2.front} == \
        {p.config: p.bottleneck for p in res.front}


def test_point_from_cache_tolerates_old_entries():
    """Cache entries written before PR 8 have no bottleneck key."""
    from repro.explore.search import _point_from_cache
    from repro.explore.space import MappingConfig
    ent = {"cycles": 10, "pes": 5, "chan": 2, "gflops": 1.0,
           "sim_cycles": 10}
    pt = _point_from_cache(MappingConfig(workers=2), ent, routed=False)
    assert pt.bottleneck == "" and pt.cached


# ---------------------------------------------------------------------------
# satellite 1: routed auto-capacity from hop depths
# ---------------------------------------------------------------------------
def test_apply_routed_capacities_grows_bounded_edges_only():
    prog = hdiff_program(20, 28)
    plan = lower(prog, workers=4, auto_capacity=True)
    rf = route(place(plan, FabricTopology.mesh(16, 16), seed=0))
    from repro.fabric.route import edge_key
    before = {edge_key(e): e.capacity for e in plan.dfg.edges()}
    grown = apply_routed_capacities(rf, slack=1)
    assert grown > 0
    hops_max = rf.stats()["hops_max"]
    for e in plan.dfg.edges():
        old = before[edge_key(e)]
        hops = len(rf.routes.get(edge_key(e), ()))
        if old is None:
            assert e.capacity is None            # unbounded stays unbounded
        elif hops:
            assert e.capacity == old + hops + 1  # hop depth + slack
            assert e.capacity - old <= hops_max + 1   # no overshoot
        else:
            assert e.capacity == old             # local edges untouched


def test_routed_hdiff_auto_capacity_regression(rng):
    """Satellite 1 regression gate: routed hdiff with auto (bounded)
    capacities must complete without deadlock in bounded cycles, match the
    unbounded-capacity output bit-for-bit, and not run slower than the
    un-grown bounded mapping (the back-pressure the hop term removes)."""
    prog = hdiff_program(20, 28)
    ins = {f: rng.normal(size=prog.grid_shape) for f in prog.in_fields}

    def run(auto, grow, engine="vector"):
        plan = lower(prog, workers=4, auto_capacity=auto)
        x = plan.pack_inputs(ins)
        rf = route(place(plan, FabricTopology.mesh(16, 16), seed=0))
        if grow:
            apply_routed_capacities(rf)
        return simulate(plan, x, CGRA, fabric=rf, engine=engine,
                        max_cycles=100_000)

    unbounded = run(False, False)
    plain = run(True, False)
    grown = run(True, True)
    assert np.array_equal(grown.output, unbounded.output)
    assert grown.cycles <= plain.cycles          # hop term only helps
    assert grown.cycles < 100_000                # no deadlock/timeout
    # engine parity holds for the grown capacities too
    grown_i = run(True, True, engine="interp")
    assert grown_i.cycles == grown.cycles
    assert np.array_equal(grown_i.output, grown.output)


def test_compile_presize_is_hop_aware(rng):
    """The vector engine's ring presize accounts for transit depth; the
    simulation semantics must not change (presize is an allocation hint)."""
    spec = heat_2d(18, 24, dtype="float64")
    x = rng.normal(size=(18, 24))

    def run(routed):
        plan = map_2d(spec, workers=3, auto_capacity=True)
        fab = None
        if routed:
            fab = route(place(plan, FabricTopology.mesh(16, 16), seed=0))
        return simulate(plan, x, CGRA, fabric=fab, engine="vector")

    ideal, routed = run(False), run(True)
    assert ideal.cycles > 0 and routed.cycles >= ideal.cycles
