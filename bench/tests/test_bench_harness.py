"""Tests of the benchmark harness on the CPU.

They resolve every cell of ``BENCHMARK.json`` to its files, drive each cell
at a tiny size (Pallas in interpret mode, four virtual devices for the
four-chip cell) with the harness's look for a chip skipped, plant the
faults that ``correct`` must catch, and check the trace reduction and the
metric readers on small traces recorded on a TPU v5e.
"""
from __future__ import annotations

import functools
import importlib
import json
import math
import os
import re
import shutil
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
DATA = Path(__file__).resolve().parent / "data"
for p in (str(ROOT / "src"), str(BENCH)):
    if p not in sys.path:
        sys.path.insert(0, p)

import loops          # noqa: E402
import make_counts    # noqa: E402
import reference      # noqa: E402
import run            # noqa: E402
import trace_reduce   # noqa: E402
import work           # noqa: E402

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in SPEC["workloads"]]
PEAK = run.peak_for("TPU v5 lite")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
CONFIGS = {c["name"]: c["file"] for c in SPEC["configs"]}


def heat3d_config(grid, layout=None):
    """The committed 3D heat configuration (``heat3d_x4``) at another grid:
    on a 2x2 mesh, or on one chip."""
    config = json.loads((BENCH / "configs" / "heat3d_x4.json").read_text())
    return {**config, "name": "heat3d", "grid": list(grid),
            "chips": 4 if layout else 1, "layout": layout}


MESH = {"mesh": [2, 2], "axes": ["pod", "data"],
        "partition": ["pod", "data", None]}


def _env():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["PYTHONPATH"] = os.pathsep.join(
        [str(ROOT / "src"), str(BENCH), env.get("PYTHONPATH", "")])
    return env


# ---------------------------------------------------------------------------
# the benchmark's files


def test_names_units_and_cells_are_well_formed():
    metrics = SPEC["end_to_end"] + SPEC["per_layer"]
    names = [m["name"] for m in metrics] + CELLS + [
        c["name"] for c in SPEC["configs"]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    assert all(re.match(r"^[A-Za-z0-9_/%.\-]{1,16}$", m["unit"])
               for m in metrics)
    e2e = {m["name"] for m in SPEC["end_to_end"]}
    assert "setup_s" in e2e
    for m in SPEC["per_layer"]:
        assert m["moves"] in e2e
        assert set(m["workloads"]) <= set(CELLS)
    for cell in CELLS:
        rep = [m for m in SPEC["end_to_end"] if run.applies(m, cell)]
        assert len(rep) >= 2
        assert any(run.applies(m, cell) for m in SPEC["per_layer"])


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves_to_its_files_by_name(cell):
    bench, w, config, traffic = run.load_cell(cell)
    assert config["name"] == w["config"] and traffic["name"] == w["traffic"]
    assert traffic["loop"] in loops.LOOPS
    assert config["chips"] == w["chips"]
    assert w["traffic"] in config["limits"]
    for m in bench["per_layer"]:
        if run.applies(m, cell):
            assert callable(run.metric_reader(m["name"]))


def test_every_metric_has_its_reader():
    for m in SPEC["per_layer"]:
        assert run.metric_path(m["name"]).is_file(), m["name"]
    assert run.metric_path("idle_share.tune").name == "idle_share.py"
    assert run.metric_path("loop_s.tune").name == "loop_s.tune.py"


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_config_states_the_programs_stencil(name):
    """The taps a configuration states are the ones the repo's own
    constructor, named in the file, builds for that deployment; its tiny
    grid for the CPU tests has the grid's rank."""
    config = json.loads((ROOT / CONFIGS[name]).read_text())
    assert config["name"] == name
    call = config["constructor"]
    module = call.split("(", 1)[0].rsplit(".", 1)[0]
    built = eval(call[len(module) + 1:],
                 vars(importlib.import_module(module)))
    assert tuple(config["grid"]) == built.grid_shape
    assert tuple(config["radii"]) == built.radii
    assert tuple(tuple(c) for c in config["coeffs"]) == built.coeffs
    assert len(config["tiny_grid"]) == len(config["grid"])


def test_peaks_are_keyed_by_device_kind():
    assert PEAK["hbm_bytes_per_s"] == 819e9
    assert PEAK["flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        run.peak_for("TPU v99 imaginary")


@pytest.mark.parametrize("name,points,flops", [
    ("heat2d_an5d", 16384 * 16384, 9 * 16382 * 16382),
    ("seismic2d_paper", 449 * 960, 97 * 425 * 936),
    ("heat3d", 1408 ** 3, 13 * 1406 ** 3),
    ("paper1d", 194400, 33 * 194384),
    ("heat3d_an5d", 512 ** 3, 13 * 510 ** 3)])
def test_work_of_one_sweep_by_hand(name, points, flops):
    if name == "heat3d":
        config = heat3d_config((1408, 1408, 1408))
    else:
        config = json.loads(
            (BENCH / "configs" / f"{name}.json").read_text())
    w = work.sweep_work(config)
    assert w == {"points": points, "bytes": 8 * points, "flops": flops}
    least, bound = work.least_time_s(w, PEAK)
    assert bound == "memory"
    assert least == pytest.approx(8 * points / 819e9)


def test_run_fails_without_a_tpu():
    p = subprocess.run(
        [sys.executable, str(BENCH / "run.py"), "--workload", CELLS[0],
         "--seed", "3", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=_env(), capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_run_fails_with_only_the_benchmark_files(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH="")
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELLS[0], "--seed",
         "3", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == ""


# ---------------------------------------------------------------------------
# driving the cells at a tiny size


def _tiny(cell):
    """The cell with its configuration at the file's own ``tiny_grid``."""
    bench, w, config, traffic = run.load_cell(cell)
    config = {**config, "grid": list(config["tiny_grid"])}
    return bench, w, config, traffic


@pytest.fixture
def pallas_interpret(monkeypatch):
    """The kernel entries run their Pallas kernels in interpret mode."""
    for dim in (1, 2, 3):
        mod = importlib.import_module(f"repro.kernels.stencil{dim}d.ops")
        fn = getattr(mod, f"stencil{dim}d")
        monkeypatch.setattr(mod, f"stencil{dim}d",
                            functools.partial(fn, backend="pallas"))


def _run(cell_tuple, seed=2 ** 31 + 17, trace=False, devices=None):
    import jax
    bench, w, config, traffic = cell_tuple
    return run.run_cell(bench, w, config, traffic, seed=seed, seconds=0.2,
                        trace=trace, devices=devices or jax.devices()[:1],
                        peak=PEAK)


ONE_CHIP_SWEEPS = [c for c in CELLS if c.endswith(".sweeps")
                   and run.load_cell(c)[1]["chips"] == 1]


@pytest.mark.parametrize("cell", ONE_CHIP_SWEEPS)
def test_sweeps_cell_is_correct_at_a_tiny_size(cell, pallas_interpret):
    res = _run(_tiny(cell))
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["metrics"]) == {m["name"] for m in SPEC["end_to_end"]
                                   if run.applies(m, cell)}
    assert len(res["metrics"]) == 2
    assert list(res)[-1] == "checks"


def test_one_chip_3d_sweeps_at_a_tiny_size(pallas_interpret):
    """A 3D configuration without a mesh drives the 3D kernel entry, so a
    later one-chip 3D cell needs only its files."""
    bench, w, _config, traffic = run.load_cell("heat2d_an5d.sweeps")
    config = heat3d_config(run.load_cell("heat3d_x4.sweeps")[2]["tiny_grid"])
    res = _run((bench, {**w, "name": "heat3d.sweeps"}, config, traffic))
    assert res["correct"], res["checks"]
    assert res["device"]["count"] == 1


def test_one_chip_1d_sweeps_drive_the_1d_entry(pallas_interpret,
                                               monkeypatch):
    """A 1D configuration without a mesh drives ``stencil1d`` with its one
    axis of taps, and reads correct."""
    mod = importlib.import_module("repro.kernels.stencil1d.ops")
    entry, calls = mod.stencil1d, []

    def counted(x, coeffs, **kw):
        calls.append((x.shape, tuple(coeffs), kw))
        return entry(x, coeffs, **kw)

    monkeypatch.setattr(mod, "stencil1d", counted)
    cell = next(c for c in ONE_CHIP_SWEEPS
                if len(run.load_cell(c)[2]["grid"]) == 1)
    bench, w, config, traffic = _tiny(cell)
    res = _run((bench, w, config, traffic))
    assert res["correct"], res["checks"]
    assert len(calls) == res["attempted"] + 1 > 1      # warm-up, window
    assert calls[0] == ((config["grid"][0],), tuple(config["coeffs"][0]),
                        {"timesteps": 1})


def test_sweeps_refuse_a_grid_of_rank_four():
    import jax
    _bench, _w, config, traffic = _tiny("heat2d_an5d.sweeps")
    d = loops.Sweeps({**config, "grid": [4, 4, 8, 128],
                      "coeffs": config["coeffs"] * 2,
                      "radii": config["radii"] * 2},
                     traffic, 5, jax.devices()[:1])
    with pytest.raises(ValueError, match="rank 4"):
        d.setup()


@pytest.mark.parametrize("fault", ["state_unchanged", "answer_altered",
                                   "answer_nan", "control"])
@pytest.mark.parametrize("cell", ONE_CHIP_SWEEPS)
def test_sweeps_faults_are_not_correct(cell, fault, pallas_interpret,
                                       monkeypatch):
    _bench, _w, config, traffic = _tiny(cell)
    import jax
    d = loops.Sweeps(config, traffic, 5, jax.devices()[:1])
    entry = d._entry
    at = (..., *(7, 9)[-len(config["grid"]):])       # a point of any rank

    def faulty_entry():
        fn, sharding = entry()
        if fault == "state_unchanged":
            return (lambda x: x + 0), sharding
        if fault == "answer_altered":
            return (lambda x: fn(x).at[at].add(1e-3)), sharding
        if fault == "answer_nan":
            return (lambda x: fn(x).at[at].set(float("nan"))), sharding
        return fn, sharding

    monkeypatch.setattr(d, "_entry", faulty_entry)
    d.setup()
    d.window(0.05)
    out = d.outputs()
    if fault == "control":
        out = d.control(out)
    checks, failed = d.compare(out)
    assert not all(c.ok for c in checks) and failed > 0


_HALO = r"""
import os, json
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, jax.numpy as jnp
import run
from repro.distributed import halo
bench, w, _config, traffic = run.load_cell("heat2d_an5d.sweeps")
w = {**w, "name": "heat3d.sweeps", "chips": 4}
config = %r
res = {}
args = dict(seed=2**32 + 9, seconds=0.2, trace=False,
            devices=jax.devices()[:4], peak=run.peak_for("TPU v5 lite"))
res["sound"] = run.run_cell(bench, w, config, traffic, **args)
halo.halo_exchange = lambda x, h, name, array_axis: tuple(
    jnp.zeros_like(jax.lax.slice_in_dim(x, 0, h, axis=array_axis))
    for _ in range(2))
res["no_exchange"] = run.run_cell(bench, w, config, traffic, **args)
print(json.dumps(res))
"""


def test_four_chip_cell_on_virtual_devices_and_without_exchange():
    """A configuration with a mesh layout drives the halo-exchanged step;
    leaving the exchange out reads not correct."""
    code = _HALO % (heat3d_config((8, 16, 128), MESH),)
    p = subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=_env(),
                       capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stderr[-3000:]
    res = json.loads(p.stdout.strip().splitlines()[-1])
    assert res["sound"]["correct"], res["sound"]["checks"]
    assert res["sound"]["device"]["count"] == 4
    assert not res["no_exchange"]["correct"]


_MESH_FAULTS = r"""
import os, json
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax
import loops, run
bench, w, _config, traffic = run.load_cell("heat3d_x4.sweeps")
config = %r
res = {}
args = dict(seed=2**32 + 9, seconds=0.2, trace=False,
            devices=jax.devices()[:4], peak=run.peak_for("TPU v5 lite"))
res["sound"] = run.run_cell(bench, w, config, traffic, **args)
for fault in ("state_unchanged", "answer_altered", "answer_nan", "control"):
    d = loops.Sweeps(config, traffic, 5, jax.devices()[:4])
    entry = d._entry

    def faulty_entry(fault=fault, entry=entry):
        fn, sharding = entry()
        if fault == "state_unchanged":
            return (lambda x: x + 0), sharding
        b = config["grid"][0] // 2         # a plane beside the z boundary
        if fault == "answer_altered":
            return (lambda x: fn(x).at[b, 9, 9].add(1e-3)), sharding
        if fault == "answer_nan":
            return (lambda x: fn(x).at[b, 9, 9].set(float("nan"))), sharding
        return fn, sharding

    d._entry = faulty_entry
    d.setup()
    d.window(0.05)
    out = d.outputs()
    if fault == "control":
        out = d.control(out)
    checks, failed = d.compare(out)
    res[fault] = {"correct": all(c.ok for c in checks) and failed == 0,
                  "checks": {c.name: c.value for c in checks}}
    if fault == "state_unchanged":
        res["shapes"] = [list(a.shape) for g in out["planes"]
                         for a in (g["x"], g["y"])]
        res["planes"] = [g["planes"] for g in out["planes"]]
print(json.dumps(res))
"""

#: a grid with more interior z planes than a mesh cell samples
MESH_GRID = (160, 16, 128)


@pytest.fixture(scope="module")
def mesh_faults():
    """The mesh cell's configuration at ``MESH_GRID`` on four virtual
    devices: a sound run, and each planted fault, in one process."""
    code = _MESH_FAULTS % (heat3d_config(MESH_GRID, MESH),)
    p = subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=_env(),
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_mesh_cell_is_correct_at_a_tiny_size(mesh_faults):
    res = mesh_faults["sound"]
    assert res["correct"], res["checks"]
    assert res["device"]["count"] == 4
    assert set(res["metrics"]) == {"gpts_per_s.x4", "setup_s"}


@pytest.mark.parametrize("fault", ["state_unchanged", "answer_altered",
                                   "answer_nan", "control"])
def test_sweeps_faults_are_not_correct_on_a_mesh(mesh_faults, fault):
    assert not mesh_faults[fault]["correct"], mesh_faults[fault]


def test_mesh_outputs_hold_the_compared_planes_only(mesh_faults):
    """What a mesh cell brings to the host is the compared planes and
    their slabs, never an array of the whole grid's shape."""
    planes = mesh_faults["planes"]
    assert len(planes[0]) == 6 + loops.Sweeps.SAMPLED_PLANES
    for shape in mesh_faults["shapes"]:
        assert shape != list(MESH_GRID)
    assert mesh_faults["shapes"][0] == [3 * len(planes[0]), 16, 128]
    assert mesh_faults["shapes"][5] == [160, 16, len(planes[2])]


def test_compared_planes_at_the_cells_size():
    """Face and shard-boundary planes at every seed, 64 interior z planes
    that change with the seed."""
    config = run.load_cell("heat3d_x4.sweeps")[2]
    grid, radii, layout = config["grid"], config["radii"], config["layout"]
    fixed = [{0, 1, 703, 704, 1406, 1407}, {0, 1, 703, 704, 1406, 1407},
             {0, 1, 1406, 1407}]
    seen = []
    for seed in (0, 7, 2 ** 31 + 3, 2 ** 33 + 1):
        planes = loops.compared_planes(grid, radii, 1, layout, seed, 64)
        assert [set(p) & f for p, f in zip(planes, fixed)] == fixed
        assert planes[1:] == [sorted(f) for f in fixed[1:]]
        assert len(planes[0]) == 70 and planes[0] == sorted(set(planes[0]))
        assert planes == loops.compared_planes(grid, radii, 1, layout, seed,
                                               64)
        seen.append(frozenset(planes[0]) - fixed[0])
    assert len(set(seen)) == len(seen)
    # the slabs and planes brought to the host: under a quarter of the grid
    brought = sum(len(p) * 4 * math.prod(grid) // n
                  for p, n in zip(planes, grid))
    assert brought < math.prod(grid) // 4
    # two sweeps a call widen the face and boundary planes by r each
    planes = loops.compared_planes(grid, radii, 2, layout, 0, 0)
    assert planes[0] == [0, 1, 2, 702, 703, 704, 705, 1405, 1406, 1407]


@pytest.fixture(scope="module")
def tiny_tune():
    """The tuner cell at a 32x64 grid with its count table recorded by the
    sequential host engine."""
    bench, w, config, traffic = run.load_cell("seismic2d_paper.tune")
    config = json.loads(json.dumps(config))
    config["tuner"]["grid"] = [32, 64]
    return (bench, w, config, traffic), make_counts.counts(config, traffic)


def _tune_loop(tiny_tune):
    (_bench, _w, config, traffic), table = tiny_tune
    return loops.Tune(config, traffic, 11, None, table=table)


def test_tune_cell_is_correct_at_a_tiny_size(tiny_tune):
    d = _tune_loop(tiny_tune)
    d.setup()
    stats = d.window(0.2)
    checks, failed = d.compare(d.outputs())
    assert stats["calls"] >= 1 and failed == 0
    assert all(c.ok for c in checks), [(c.name, c.value) for c in checks]
    assert d.metrics(stats)["tune_s"] > 0


@pytest.mark.parametrize("fault", ["state_unchanged", "half_the_lanes",
                                   "cycles_altered", "answer_altered",
                                   "answer_nan", "control"])
def test_tune_faults_are_not_correct(tiny_tune, fault):
    d = _tune_loop(tiny_tune)
    d.setup()
    d.window(0.0)
    out = d.outputs()
    calls = out["calls"]
    for c in calls:
        if fault == "state_unchanged":
            for lane in c["lanes"]:
                lane["output"] = loops.tuner_input(d.seed, c["index"],
                                                     lane["grid"])
        elif fault == "half_the_lanes":
            c["lanes"] = c["lanes"][: len(c["lanes"]) // 2]
        elif fault == "cycles_altered":
            c["lanes"][0]["cycles"] += 1
        elif fault == "answer_altered":
            c["lanes"][-1]["output"][20, 30] += 1e-9
        elif fault == "answer_nan":
            c["lanes"][-1]["output"][20, 30] = np.nan
    if fault == "control":
        out = d.control(out)
    checks, failed = d.compare(out)
    assert failed > 0 and not all(c.ok for c in checks)


@pytest.mark.parametrize("fault", ["loop_state_unchanged", "cycles_halved",
                                   "cycles_doubled", "loads_halved",
                                   "pes_altered"])
def test_tune_model_alone_catches_count_faults(tiny_tune, fault):
    """The closed form, without the program's table, reads each planted
    fault in the device loop's counts."""
    d = _tune_loop(tiny_tune)
    d.setup()
    d.window(0.0)
    out = d.outputs()
    for c in out["calls"]:
        lane = c["lanes"][0]
        if fault == "loop_state_unchanged":
            lane["cycles"] = lane["loads"] = lane["stores"] = lane["flops"] = 0
        elif fault == "cycles_halved":
            lane["cycles"] //= 2
        elif fault == "cycles_doubled":
            lane["cycles"] *= 2
        elif fault == "loads_halved":
            lane["loads"] //= 2
        else:
            c["points"] = [(k, cyc, pes + 1) for k, cyc, pes in c["points"]]
    checks = {c.name: c for c in d.compare(out)[0]}
    assert checks["lanes_outside_model"].value > 0


MODEL_CASES = [((57, 120), (12, 12), 1, 5), ((32, 64), (12, 12), 1, 1),
               ((20, 40), (3, 3), 2, 4), ((16, 30), (1, 1), 1, 6),
               ((11, 24), (2, 2), 2, 8), ((8, 10, 12), (1, 1, 1), 2, 2),
               ((10, 9, 16), (2, 1, 2), 1, 4), ((97,), (3,), 2, 5)]


@pytest.mark.parametrize("grid,radii,t,w", MODEL_CASES)
def test_cgra_model_holds_on_the_programs_counts(grid, radii, t, w):
    """The closed form against a second witness: the program's sequential
    vector engine, on mappings of rank 1 to 3."""
    from repro.core.mapping.nd import map_nd
    from repro.core.roofline import Machine
    from repro.core.simulator import simulate
    from repro.core.spec import StencilSpec
    machine = {"clock_ghz": 1.2, "num_macs": 256, "bw_gbps": 100.0}
    rng = np.random.default_rng(3)
    coeffs = tuple(tuple(float(v) if (a == 0 or k != r) else 0.0
                         for k, v in enumerate(rng.normal(size=2 * r + 1)))
                   for a, r in enumerate(radii))
    spec = StencilSpec(grid, radii, coeffs, dtype="float64", timesteps=t)
    plan = map_nd(spec, w, auto_capacity=True)
    res = simulate(plan, rng.normal(size=grid),
                   Machine("cgra", peak_gflops=614.4, **machine),
                   engine="vector")
    want = reference.cgra_lane_bounds(grid, radii, w, t, 8, machine)
    assert want["pes"] == len(plan.dfg.nodes)
    assert want["loads"][0] <= res.loads <= want["loads"][1]
    lo, hi = want["cycles"]
    assert lo <= res.cycles and (hi is None or res.cycles <= hi)
    assert (hi is None) == (len(grid) == 1 or 16 * w > 100 / 1.2)


def test_committed_fixture_lies_within_the_model():
    config = json.loads(
        (BENCH / "configs" / "seismic2d_paper.json").read_text())
    table = json.loads((BENCH / "configs"
                        / "seismic2d_paper.tune.counts.json").read_text())
    tcfg = config["tuner"]
    for key, want in table["lanes"].items():
        b = reference.cgra_lane_bounds(
            tcfg["grid"], config["radii"], json.loads(key)["workers"], 1, 8,
            tcfg["machine"])
        assert b["pes"] == want["pes"]
        assert b["loads"][0] <= want["loads"] <= b["loads"][1]
        assert b["cycles"][0] <= want["cycles"] <= b["cycles"][1]


# ---------------------------------------------------------------------------
# the reference and the trace reduction


def test_reference_matches_a_direct_sum():
    rng = np.random.default_rng(0)
    x = rng.normal(size=(9, 11, 13))
    coeffs = [[0.2, 0.3, 0.1], [0.05, 0.0, 0.15], [0.1, 0.0, 0.1]]
    got = reference.star_stencil(x, coeffs, rows_per_block=2, threads=3)
    want = np.zeros_like(x)
    for i in range(1, 8):
        for j in range(1, 10):
            for k in range(1, 12):
                want[i, j, k] = sum(
                    coeffs[0][a] * x[i + a - 1, j, k]
                    + coeffs[1][a] * x[i, j + a - 1, k]
                    + coeffs[2][a] * x[i, j, k + a - 1] for a in range(3))
    np.testing.assert_allclose(got, want, rtol=1e-13, atol=1e-15)


@pytest.mark.parametrize("timesteps", [1, 2])
@pytest.mark.parametrize("axis", [0, 1, 2])
def test_plane_reference_matches_the_whole_grid(axis, timesteps):
    """A plane from its slab, at the faces and inside, is the same bits as
    that plane of the whole grid's reference."""
    rng = np.random.default_rng(axis)
    grid = (12, 10, 14)
    x = rng.normal(size=grid).astype(np.float32)
    coeffs = [[0.05, 0.2, 0.3, 0.1, 0.02], [0.05, 0.0, 0.15],
              [0.1, 0.0, 0.1]]
    radii = [2, 1, 1]
    whole = reference.star_stencil(x, coeffs, timesteps)
    h = radii[axis] * timesteps
    n = grid[axis]
    for plane in (0, 1, h, n // 2, n - h - 1, n - 1):
        lo = max(plane - h, 0)
        hi = min(plane + h + 1, n)
        slab = np.take(x, np.arange(lo, hi), axis=axis)
        got = reference.star_stencil_plane(slab, coeffs, timesteps,
                                           axis=axis, lo=lo, plane=plane,
                                           grid=grid)
        np.testing.assert_array_equal(got, np.take(whole, plane, axis=axis))


def test_pareto_front():
    pts = [(3, 1, 0), (1, 3, 0), (2, 2, 0), (3, 3, 0), (1, 3, 0)]
    assert reference.pareto_front(pts) == {(3, 1, 0), (1, 3, 0), (2, 2, 0)}


def _op(name, s, e, kind="op"):
    return trace_reduce.Op(name, s, e, kind)


def test_union_idle_and_exposed_collectives():
    ops = [_op("a", 0, 10), _op("b", 5, 20), _op("c", 30, 40),
           _op("permute", 15, 35, "collective")]
    tr = trace_reduce.Trace({"/device:TPU:0": ops},
                            [trace_reduce.Op("bench.window", 0, 50)])
    assert tr.window() == (0, 50)
    assert tr.busy_s(0, 50) == pytest.approx(40e-9)
    assert tr.idle_share(0, 50) == pytest.approx(20.0)
    assert tr.exposed_s(0, 50) == pytest.approx(10e-9)   # 20..30
    assert trace_reduce.union_ns([(0, 1), (1, 2), (5, 6)]) == 3
    tr.modules = {"/device:TPU:0": [trace_reduce.Op("jit__sweep", 5, 25),
                                    trace_reduce.Op("jit_other", 30, 40)]}
    ctx = {"trace": tr, "window": (0, 50), "calls": 2}
    assert run.metric_reader("loop_s.tune")(ctx) == pytest.approx(10e-9)
    assert run.metric_reader("idle_share.tune")(ctx) == pytest.approx(20.0)


@pytest.mark.parametrize("kernel", ["stencil1d", "stencil3d"])
def test_kernel_roofline_reads_its_own_kernel(kernel):
    """``<kernel>_roofline``: a sweep's least time over the device time a
    sweep of the kernel ops of that name; other kernels and the wrapper's
    ops do not count, and with none of its kernel it reads nothing."""
    ops = [_op(f"{kernel}.1", 0, 40, "kernel"), _op("fusion.2", 40, 50),
           _op(f"{kernel}.1", 100, 140, "kernel"),
           _op("stencil2d.1", 140, 190, "kernel"),
           _op(f"{kernel}x.4", 190, 200, "kernel")]
    tr = trace_reduce.Trace({"/device:TPU:0": ops},
                            [trace_reduce.Op("bench.window", 0, 200)])
    ctx = {"trace": tr, "window": (0, 200), "calls": 2, "chips": 1,
           "work": {"points": 1, "bytes": 8190, "flops": 1}, "peak": PEAK}
    read = run.metric_reader(f"{kernel}_roofline")
    assert read(ctx) == pytest.approx(25.0)      # 10 ns over 40 ns a sweep
    assert read({**ctx, "calls": 0}) is None
    tr.devices["/device:TPU:0"] = ops[1:2] + ops[3:]
    assert read(ctx) is None


def test_op_kinds_from_hlo_text():
    assert trace_reduce.op_kind(
        '%custom-call.1 = f32[1,64,256]{2,1,0} custom-call(f32[1,64,256] '
        '%p), custom_call_target="tpu_custom_call"') == "kernel"
    assert trace_reduce.op_kind(
        "%collective-permute-start.2 = (f32[1,8]) collective-permute-start("
        "f32[1,8] %s), source_target_pairs={{0,1}}") == "collective"
    assert trace_reduce.op_kind(
        "%fusion.3 = f32[8]{0} fusion(f32[8]{0} %a), kind=kLoop") == "op"
    assert trace_reduce.op_name("%fusion.3 = f32[8]{0} fusion()") \
        == "fusion.3"


RECORDED = sorted(DATA.glob("*.xplane.pb"))


@pytest.mark.parametrize("path", RECORDED, ids=lambda p: p.stem)
def test_recorded_chip_trace_reduces(path):
    from jax.profiler import ProfileData
    tr = trace_reduce.from_profile(ProfileData.from_file(str(path)))
    meta = json.loads((DATA / (path.name.split(".")[0] + ".json")).read_text())
    lo, hi = tr.window()
    ops = tr.ops(lo, hi)
    assert list(ops) == ["/device:TPU:0"]
    kernels = [o for o in ops["/device:TPU:0"] if o.kind == "kernel"]
    assert len(kernels) == meta["calls"]
    assert tr.op_seconds(lo, hi, kind="kernel") > 0
    assert tr.op_seconds(lo, hi) > tr.op_seconds(lo, hi, kind="kernel")
    busy = tr.busy_s(lo, hi)
    assert 0 < busy <= (hi - lo) / 1e9
    # the busy union, counted a second way: a timeline of ns buckets
    line = np.zeros(hi - lo, bool)
    for o in ops["/device:TPU:0"]:
        line[o.start - lo:o.end - lo] = True
    assert busy == pytest.approx(line.sum() / 1e9)
    assert tr.idle_share(lo, hi) == pytest.approx(
        100 * (1 - line.mean()))
    spans = [s for s in tr.spans if s.name == "bench.dispatch"]
    assert len(spans) == meta["calls"]
    assert all(lo <= o.start and o.end <= hi for o in ops["/device:TPU:0"])
    config = json.loads(
        (BENCH / "configs" / f"{meta['config']}.json").read_text())
    config["grid"] = meta["grid"]
    ctx = {"trace": tr, "window": (lo, hi), "calls": meta["calls"],
           "work": work.sweep_work(config), "peak": PEAK, "chips": 1,
           "config": config}
    roof = run.metric_reader("stencil2d_roofline")(ctx)
    assert 0 < roof <= 100
    assert run.metric_reader("wrapper_ms")(ctx) > 0
    assert run.metric_reader("dispatch_us")(ctx) > 0
    idle = run.metric_reader("idle_share.sweep")(ctx)
    assert 0 <= idle < 100
    assert run.metric_reader("halo_exposed_ms")(ctx) is None
    assert run.metric_reader("stencil1d_roofline")(ctx) is None
    assert run.metric_reader("stencil3d_roofline")(ctx) is None
    bd = tr.breakdown(lo, hi)
    assert 0 < len(bd["device_ops"]) <= 10 and len(bd["idle_gaps"]) <= 10
    assert math.isclose(sum(t for _n, t in bd["device_ops"]),
                        tr.op_seconds(lo, hi), rel_tol=1e-9) \
        or len(bd["device_ops"]) == 10
