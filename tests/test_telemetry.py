"""Telemetry subsystem gates (docs/telemetry.md).

Three contracts, each load-bearing:

* **engine parity** — the interpreter and the compiled vector engine must
  leave *identical* telemetry: per-node fire timelines, per-cycle stall
  attribution (including through the vector engine's event-skip), and
  per-link words/waits/occupancy.  A drift here means one engine's stall
  story is fiction.
* **exactness** — ``Telemetry.totals()`` must equal the ``SimResult``
  aggregates bit-for-bit, and every node must have exactly one state per
  observed cycle (states partition ``cycles * n_nodes``).
* **harmlessness** — attaching a sink must not change the simulation, and
  the exported Perfetto JSON must validate (schema + monotonic
  timestamps).

Plus the satellites: SimDeadlock stall-attribution diagnostics, tuner
search spans and EvalCache.stats() replay hits.
"""
import json

import numpy as np
import pytest

from repro.core import CGRA, SimDeadlock, map_1d, map_2d, simulate
from repro.core.spec import StencilSpec, heat_2d, paper_stencil_2d
from repro.fabric import FabricTopology, place, route
from repro.program import lower, two_stage_heat
from repro.telemetry import (STALL_CAUSES, STATE_NAMES, Telemetry,
                             trace_events, validate_trace, write_trace)

ENGINES = ("interp", "vector")


def _coeffs(rng, r):
    return tuple((rng.normal(size=2 * r + 1) / (2 * r + 1)).tolist())


def run_both_tel(mk_plan, x, routed=False, **kw):
    """One fresh plan + fresh Telemetry sink per engine."""
    out = []
    for engine in ENGINES:
        plan = mk_plan()
        fab = None
        if routed:
            fab = route(place(plan, FabricTopology.mesh(16, 16), seed=0))
        tel = Telemetry()
        res = simulate(plan, x, CGRA, fabric=fab, engine=engine,
                       telemetry=tel, **kw)
        out.append((plan, res, tel))
    return out


def assert_tel_identical(case):
    """The parity gate: both engines' sinks hold the same telemetry."""
    (_, ra, ta), (_, rb, tb) = case
    assert np.array_equal(ta.fires_total, tb.fires_total)
    assert np.array_equal(ta.first_fire, tb.first_fire)
    assert np.array_equal(ta.last_fire, tb.last_fire)
    assert np.array_equal(ta.stall_totals, tb.stall_totals)
    assert ta.intervals == tb.intervals          # full per-node timelines
    assert np.array_equal(ta.link_words, tb.link_words)
    assert np.array_equal(ta.link_stalls, tb.link_stalls)
    assert ta.link_occ == tb.link_occ
    assert ta.totals() == tb.totals()
    for tel, res in ((ta, ra), (tb, rb)):
        assert_tel_exact(tel, res)


def assert_tel_exact(tel, res):
    """The exactness gate: counters sum to the simulator's own stats."""
    t = tel.totals()
    assert t["cycles"] == res.cycles
    assert t["fires"] == res.fires
    assert (t["loads"], t["stores"], t["flops"]) == \
        (res.loads, res.stores, res.flops)
    if res.fabric is not None:
        assert t["token_hops"] == res.fabric["token_hops"]
        assert t["stall_cycles"] == res.fabric["stall_cycles"]
    else:
        assert t["token_hops"] == t["stall_cycles"] == 0
    # exclusive states partition every observed (node, cycle) slot
    observed = int(tel.fires_total.sum() + tel.stall_totals.sum())
    assert observed <= res.cycles * tel.n_nodes
    per_node = np.zeros(tel.n_nodes, dtype=np.int64)
    for nid, _s, t0, t1 in tel.intervals:
        assert 1 <= t0 < t1 <= res.cycles + 1
        per_node[nid] += t1 - t0
    assert (per_node == res.cycles).all()        # intervals tile every cycle


@pytest.mark.parametrize("routed", [False, True])
def test_1d_telemetry_parity(rng, routed):
    spec = StencilSpec((240,), (2,), (_coeffs(rng, 2),), dtype="float64")
    assert_tel_identical(run_both_tel(lambda: map_1d(spec, workers=4),
                                      rng.normal(size=240), routed=routed))


@pytest.mark.parametrize("routed", [False, True])
def test_2d_telemetry_parity(rng, routed):
    spec = paper_stencil_2d(ny=30, nx=48, r=12)
    assert_tel_identical(run_both_tel(lambda: map_2d(spec, workers=8),
                                      rng.normal(size=(30, 48)),
                                      routed=routed))


@pytest.mark.parametrize("routed", [False, True])
def test_program_telemetry_parity(routed):
    prog = two_stage_heat(24, 32)
    rng = np.random.default_rng(1)
    ins = {f: rng.normal(size=prog.grid_shape) for f in prog.in_fields}
    x = lower(prog, workers=4).pack_inputs(ins)
    assert_tel_identical(run_both_tel(lambda: lower(prog, workers=4), x,
                                      routed=routed))


def test_bounded_queue_telemetry_parity(rng):
    """auto_capacity exercises the output_blocked attribution path."""
    spec = heat_2d(18, 24, dtype="float64")
    case = run_both_tel(lambda: map_2d(spec, workers=3, auto_capacity=True),
                        rng.normal(size=(18, 24)))
    assert_tel_identical(case)
    tel = case[0][2]
    i_blocked = STALL_CAUSES.index("output_blocked")
    assert tel.stall_totals[:, i_blocked].sum() > 0


def test_routed_telemetry_has_network_attribution(rng):
    spec = paper_stencil_2d(ny=30, nx=48, r=12)
    case = run_both_tel(lambda: map_2d(spec, workers=8),
                        rng.normal(size=(30, 48)), routed=True)
    tel, res = case[1][2], case[1][1]
    i_net = STALL_CAUSES.index("network_contention")
    assert tel.stall_totals[:, i_net].sum() > 0
    assert tel.link_words.sum() == res.fabric["token_hops"]
    assert tel.link_stalls.sum() == res.fabric["stall_cycles"]
    assert len(tel.link_occ) > 0                 # per-slot occupancy captured


def test_fire_cycles_timeline(rng):
    spec = StencilSpec((120,), (1,), (_coeffs(rng, 1),), dtype="float64")
    (plan, res, tel), _ = run_both_tel(lambda: map_1d(spec, workers=2),
                                       rng.normal(size=120))
    for node in plan.dfg.nodes:
        runs = tel.fire_cycles(node.nid)
        assert sum(t1 - t0 for t0, t1 in runs) == node.fires
        assert runs == sorted(runs)


def test_telemetry_does_not_perturb(rng):
    spec = paper_stencil_2d(ny=30, nx=48, r=12)
    x = rng.normal(size=(30, 48))
    for routed in (False, True):
        mk = lambda: map_2d(spec, workers=8)            # noqa: E731
        plans = [mk(), mk()]
        fabs = [route(place(p, FabricTopology.mesh(16, 16), seed=0))
                if routed else None for p in plans]
        bare = simulate(plans[0], x, CGRA, fabric=fabs[0], engine="vector")
        inst = simulate(plans[1], x, CGRA, fabric=fabs[1], engine="vector",
                        telemetry=Telemetry())
        assert bare.cycles == inst.cycles
        assert bare.fires == inst.fires
        assert bare.output.tobytes() == inst.output.tobytes()
        if routed:
            assert bare.fabric["token_hops"] == inst.fabric["token_hops"]
            assert bare.fabric["stall_cycles"] == inst.fabric["stall_cycles"]


# ---------------------------------------------------------------------------
# trace export
# ---------------------------------------------------------------------------
def test_trace_export_validates(rng, tmp_path):
    spec = paper_stencil_2d(ny=30, nx=48, r=12)
    plan = map_2d(spec, workers=8)
    fab = route(place(plan, FabricTopology.mesh(16, 16), seed=0))
    tel = Telemetry()
    simulate(plan, rng.normal(size=(30, 48)), CGRA, fabric=fab,
             engine="vector", telemetry=tel)
    path = tmp_path / "run.trace.json"
    obj = write_trace(tel, str(path))
    n = validate_trace(obj)
    assert n > 0
    reread = json.loads(path.read_text())
    assert validate_trace(reread) == n
    evs = reread["traceEvents"]
    # metadata first, then globally monotonic timestamps
    body = [e for e in evs if e["ph"] != "M"]
    ts = [e["ts"] for e in body]
    assert ts == sorted(ts)
    assert all(e["ph"] in ("M", "X", "C", "i") for e in evs)
    groups = {e["args"]["name"] for e in evs
              if e["ph"] == "M" and e["name"] == "process_name"}
    assert any(g.startswith("PE(") for g in groups)    # one group per PE
    threads = {e["args"]["name"] for e in evs
               if e["ph"] == "M" and e["name"] == "thread_name"}
    assert threads                                     # one track per node


def test_validate_trace_rejects_garbage():
    with pytest.raises(ValueError):
        validate_trace({"traceEvents": [{"ph": "X"}]})   # missing keys
    with pytest.raises(ValueError, match="not monotonic"):
        validate_trace({"traceEvents": [
            {"ph": "X", "name": "a", "pid": 1, "tid": 1, "ts": 5, "dur": 1,
             "cat": "c"},
            {"ph": "X", "name": "b", "pid": 1, "tid": 1, "ts": 4, "dur": 1,
             "cat": "c"}]})                              # non-monotonic


def test_trace_routed_program_roundtrip(tmp_path):
    """Satellite: a routed *program-DAG* run (remux/imux nodes, contended
    links) exports a trace that round-trips the validator from disk."""
    prog = two_stage_heat(24, 32)
    rng = np.random.default_rng(2)
    ins = {f: rng.normal(size=prog.grid_shape) for f in prog.in_fields}
    plan = lower(prog, workers=4)
    fab = route(place(plan, FabricTopology.mesh(16, 16), seed=0))
    tel = Telemetry()
    simulate(plan, plan.pack_inputs(ins), CGRA, fabric=fab,
             engine="vector", telemetry=tel)
    path = tmp_path / "prog.trace.json"
    obj = write_trace(tel, str(path))
    n = validate_trace(obj)
    assert n > 0
    assert validate_trace(json.loads(path.read_text())) == n
    # the link counter tracks declare their inventory and tag samples
    evs = obj["traceEvents"]
    decl = [e for e in evs if e["ph"] == "M"
            and "links" in e.get("args", {})]
    assert decl and decl[0]["args"]["links"] == len(tel.link_names)
    c_lids = {e["args"]["lid"] for e in evs if e["ph"] == "C"}
    assert c_lids and all(0 <= lid < len(tel.link_names) for lid in c_lids)


def test_validate_trace_overlapping_exclusive_intervals():
    """fire/stall slices on one node track are exclusive by contract;
    tuner spans (other cats) may legitimately overlap after rounding."""
    overlap = [
        {"ph": "X", "name": "fire", "pid": 10, "tid": 0, "ts": 1, "dur": 5,
         "cat": "fire"},
        {"ph": "X", "name": "input_starved", "pid": 10, "tid": 0, "ts": 3,
         "dur": 2, "cat": "stall"}]
    with pytest.raises(ValueError, match="overlapping exclusive intervals"):
        validate_trace({"traceEvents": overlap})
    # same shape on different tracks: fine
    ok = [dict(overlap[0]), {**overlap[1], "tid": 1}]
    assert validate_trace({"traceEvents": ok}) == 2
    # same shape but span-cat events: fine (wall-clock spans can overlap)
    spans = [{**overlap[0], "cat": "tuner"}, {**overlap[1], "cat": "tuner"}]
    assert validate_trace({"traceEvents": spans}) == 2


def test_validate_trace_unknown_link_id():
    decl = {"ph": "M", "pid": 2, "ts": 0, "name": "process_name",
            "args": {"name": "links (contended)", "links": 3}}
    sample = {"ph": "C", "pid": 2, "ts": 1, "name": "link x",
              "args": {"words": 1, "lid": 7}}
    with pytest.raises(ValueError, match="unknown link id 7"):
        validate_trace({"traceEvents": [decl, sample]})
    # a sample with no inventory declared at all is just as invalid
    with pytest.raises(ValueError, match="unknown link id 7"):
        validate_trace({"traceEvents": [sample]})
    assert validate_trace({"traceEvents": [
        decl, {**sample, "args": {"words": 1, "lid": 2}}]}) == 1


def test_link_book_rejects_unknown_lid(rng):
    """The probe itself names the error when an engine books against a
    link outside the attached fabric's inventory."""
    spec = paper_stencil_2d(ny=30, nx=48, r=12)
    plan = map_2d(spec, workers=8)
    fab = route(place(plan, FabricTopology.mesh(16, 16), seed=0))
    tel = Telemetry()
    simulate(plan, rng.normal(size=(30, 48)), CGRA, fabric=fab,
             engine="vector", telemetry=tel)
    with pytest.raises(ValueError, match="unknown link id"):
        tel.link_book(len(tel.link_names) + 5, slot=1, waited=0)


# ---------------------------------------------------------------------------
# failure diagnostics (satellite: deadlock stall attribution)
# ---------------------------------------------------------------------------
def test_deadlock_stall_attribution(rng):
    spec = heat_2d(18, 24, dtype="float64")
    x = rng.normal(size=(18, 24))
    msgs = []
    for engine in ENGINES:
        plan = map_2d(spec, workers=3, queue_capacity=1)
        tel = Telemetry()
        with pytest.raises(SimDeadlock) as ei:
            simulate(plan, x, CGRA, max_cycles=200_000, engine=engine,
                     telemetry=tel)
        e = ei.value
        assert e.stall_summary is not None
        assert e.stall_summary["window_cycles"] == 64
        assert sum(e.stall_summary["cause_counts"].values()) > 0
        assert e.stall_summary["nodes"]          # names the blocked nodes
        assert "stall attribution (last 64 cycles)" in str(e)
        assert not e.timed_out
        msgs.append(str(e))
    assert msgs[0] == msgs[1]                    # engine-parity diagnostic


def test_deadlock_summary_without_sink(rng):
    """No telemetry attached: engines still attribute the final cycle."""
    spec = heat_2d(18, 24, dtype="float64")
    x = rng.normal(size=(18, 24))
    msgs = []
    for engine in ENGINES:
        plan = map_2d(spec, workers=3, queue_capacity=1)
        with pytest.raises(SimDeadlock) as ei:
            simulate(plan, x, CGRA, max_cycles=200_000, engine=engine)
        assert "stall attribution (final cycle)" in str(ei.value)
        assert ei.value.stall_summary is not None
        msgs.append(str(ei.value))
    assert msgs[0] == msgs[1]


def test_timeout_stall_attribution(rng):
    spec = StencilSpec((120,), (1,), ((0.25, 0.5, 0.25),), dtype="float64")
    x = rng.normal(size=120)
    for engine in ENGINES:
        plan = map_1d(spec, workers=3)
        with pytest.raises(SimDeadlock, match="exceeded max_cycles=10") as ei:
            simulate(plan, x, CGRA, max_cycles=10, engine=engine,
                     telemetry=Telemetry())
        assert ei.value.timed_out
        assert ei.value.stall_summary is not None


# ---------------------------------------------------------------------------
# tuner spans + cache stats (satellites)
# ---------------------------------------------------------------------------
def _tiny_search(tmp_path, tel=None):
    from repro.explore import Budget, SpaceOptions, explore
    return explore(
        heat_2d(18, 24, dtype="float64"), CGRA,
        options=SpaceOptions(workers=(2, 3), capacities=("auto",)),
        budget=Budget(), cache=str(tmp_path / "cache.json"),
        telemetry=tel)


def test_explore_records_spans(tmp_path):
    tel = Telemetry()
    res = _tiny_search(tmp_path, tel)
    evals = [s for s in tel.spans if s["cat"] == "tuner"
             and s["track"].startswith("search/")
             and s["track"] != "search/prune"]
    assert len(evals) == res.stats["n_measured"] > 0
    for s in evals:
        assert s["args"]["outcome"] == "measured"
        assert s["args"]["cycles"] > 0
        assert s["args"]["key"] and s["args"]["config"]
        assert s["dur"] >= 0 and s["t0"] >= 0
    assert validate_trace(trace_events(tel)) >= len(evals)

    # second search, same cache: every eval replays as a cache hit
    tel2 = Telemetry()
    _tiny_search(tmp_path, tel2)
    outcomes = {s["args"]["outcome"] for s in tel2.spans
                if s["cat"] == "tuner" and s["track"] != "search/prune"}
    assert outcomes == {"cached"}


def test_eval_cache_stats_replay(tmp_path):
    """Regression gate: a rerun over a warm cache must report hits > 0."""
    res1 = _tiny_search(tmp_path)
    cs1 = res1.stats["cache"]
    assert cs1["hits"] == 0 and cs1["misses"] > 0
    assert cs1["entries"] == cs1["misses"]

    res2 = _tiny_search(tmp_path)
    cs2 = res2.stats["cache"]
    assert cs2["hits"] > 0 and cs2["misses"] == 0
    assert res1.best().cycles == res2.best().cycles


def test_eval_cache_stats_counts_failure_replay(tmp_path):
    from repro.explore import EvalCache
    path = str(tmp_path / "c.json")
    c = EvalCache(path)
    c.put("good", {"cycles": 5})
    c.put("bad", {"failed": "deadlock: x"})
    c.save()
    c2 = EvalCache(path)
    assert c2.get("good") and c2.get("bad") and c2.get("gone") is None
    assert c2.stats() == {"hits": 2, "misses": 1, "failures_replayed": 1,
                          "entries": 2}


def test_stall_summary_and_report_crash_proofing(rng):
    """Satellite: empty/window-less summaries and unattached sinks render
    stubs instead of raising — these run on failure/cleanup codepaths."""
    from repro.telemetry import format_stall_summary, render_report
    from repro.telemetry.report import bottleneck_table, utilization_grid

    assert format_stall_summary(None) == ""
    assert format_stall_summary({}) == ""
    empty = {"window_cycles": None,
             "cause_counts": {c: 0 for c in STALL_CAUSES}, "nodes": []}
    assert "no stalls recorded" in format_stall_summary(empty)
    windowed = {"window_cycles": 64,
                "cause_counts": {c: 0 for c in STALL_CAUSES}, "nodes": []}
    assert "no stalls recorded" in format_stall_summary(windowed)
    assert "last 64 cycles" in format_stall_summary(windowed)

    tel = Telemetry()                          # never attached to a run
    assert tel.stall_summary()["window_cycles"] is None
    assert tel.stall_summary()["nodes"] == []
    assert "no run attached" in utilization_grid(tel)
    assert "no stalls recorded" in bottleneck_table(tel)
    assert "no run attached" in render_report(tel)

    # an attached run with zero stalls still renders a stub row
    spec = StencilSpec((60,), (1,), ((0.25, 0.5, 0.25),), dtype="float64")
    plan = map_1d(spec, workers=1)
    tel2 = Telemetry()
    simulate(plan, rng.normal(size=60), CGRA, engine="vector",
             telemetry=tel2)
    if not tel2.stall_totals.sum():
        assert "(no stalls recorded)" in bottleneck_table(tel2)


def test_state_names_cover_constants():
    from repro.telemetry import (ST_FIRED, ST_INACTIVE, ST_INPUT_STARVED,
                                 ST_MEM_ARB, ST_NET_WAIT, ST_OUTPUT_BLOCKED)
    assert len(STATE_NAMES) == 6
    assert STATE_NAMES[ST_INACTIVE] == "inactive"
    assert STATE_NAMES[ST_FIRED] == "fire"
    assert STATE_NAMES[ST_INPUT_STARVED] == "input_starved"
    assert STATE_NAMES[ST_OUTPUT_BLOCKED] == "output_blocked"
    assert STATE_NAMES[ST_MEM_ARB] == "memory_arbitration"
    assert STATE_NAMES[ST_NET_WAIT] == "network_contention"
    assert STALL_CAUSES == STATE_NAMES[2:]


def test_every_profiler_span_is_listed_and_recorded():
    """``SPANS`` lists exactly the spans the program's code records."""
    import re
    from pathlib import Path

    from repro.telemetry import SPANS
    src = Path(__file__).resolve().parents[1] / "src" / "repro"
    used = set()
    for path in src.rglob("*.py"):
        used |= set(re.findall(r'span\("(repro\.[\w.]+)"\)',
                               path.read_text()))
    assert used == set(SPANS)
    assert all(name.startswith("repro.") and SPANS[name] for name in SPANS)
