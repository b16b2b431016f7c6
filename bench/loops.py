"""The loops a traffic mix runs, one per kind of entry point.

A traffic file names its ``loop`` and holds that loop's parameters; a
configuration file says what runs (grid, taps, precision, chips, layout).
Each loop sets up its entry on the device from ``--seed``, warms every
shape it will use, runs a closed loop for a given time, hands back host
copies of what the loop produced, and compares them with the plain
reference in ``reference.py``.
"""
from __future__ import annotations

import collections
import concurrent.futures as cf
import contextlib
import json
import math
import threading
import time
from pathlib import Path

import numpy as np

import reference
import work

BENCH_DIR = Path(__file__).resolve().parent


def _span(name: str, on: bool):
    """A host span in the profiler's trace, or nothing when not tracing."""
    if not on:
        return contextlib.nullcontext()
    import jax
    return jax.profiler.TraceAnnotation(name)


def seeded_key(seed: int):
    """A PRNG key from a seed of any size: the low 32 bits seed the key and
    the rest is folded in."""
    import jax
    key = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, seed >> 32)


class Check:
    """One number compared, with its limit.  A limit of ``None`` (not yet
    set) fails."""

    def __init__(self, name: str, value: float, limit: float | None):
        self.name, self.value, self.limit = name, float(value), limit

    @property
    def ok(self) -> bool:
        return self.limit is not None and self.value <= self.limit


class Sweeps:
    """Back-to-back stencil sweeps of one seeded, device-resident grid.

    One chip: the public kernel entry of the grid's rank (``stencil1d`` /
    ``stencil2d`` / ``stencil3d``, the planner's block).  A mesh layout:
    the halo-exchanged step (``distributed_stencil{2,3}d``) over the
    configured mesh.  The loop keeps ``QUEUE_DEPTH`` calls queued behind
    the running one and waits on the oldest, so the device never runs dry
    and outputs never pile up in its memory.  Every call computes the same ``timesteps`` fused sweeps
    (the traffic's) of the same grid, so the last call's output stands for
    all of them in the comparison: on one chip the whole grid, on a mesh
    the planes ``compared_planes`` chooses, since a whole grid too large
    for one chip is too large to bring to the host and compare within a
    run's limits."""

    QUEUE_DEPTH = 1
    #: interior planes along axis 0 that a mesh cell compares, drawn from
    #: the seed, beside its face and shard-boundary planes
    SAMPLED_PLANES = 64

    def __init__(self, config: dict, traffic: dict, seed: int, devices):
        self.config, self.traffic, self.seed = config, traffic, seed
        self.devices = devices
        self.timesteps = int(traffic["timesteps"])
        self.limits = config["limits"][traffic["name"]]
        #: a mesh layout: the halo step, compared on ``compared_planes``
        self.layout = config.get("layout")
        self.fn = self.x = self.y = self.sharding = None

    def _entry(self):
        import jax
        cfg, t = self.config, self.timesteps
        coeffs = tuple(tuple(c) for c in cfg["coeffs"])
        ndim = len(cfg["grid"])
        layout = self.layout
        if layout:
            from jax.sharding import AxisType, NamedSharding, PartitionSpec

            from repro.core.spec import StencilSpec
            from repro.distributed import halo
            axes = tuple(layout["axes"])
            mesh = jax.make_mesh(tuple(layout["mesh"]), axes,
                                 devices=self.devices,
                                 axis_types=(AxisType.Auto,) * len(axes))
            spec = StencilSpec(tuple(cfg["grid"]), tuple(cfg["radii"]),
                               coeffs, dtype=cfg["dtype"], timesteps=t)
            step = getattr(halo, f"distributed_stencil{ndim}d")(
                spec, mesh, axes=axes)
            return step, NamedSharding(mesh, PartitionSpec(
                *layout["partition"]))
        from jax.sharding import SingleDeviceSharding
        if ndim == 1:
            from repro.kernels.stencil1d.ops import stencil1d

            def step(x):
                return stencil1d(x, coeffs[0], timesteps=t)
        elif ndim == 2:
            from repro.kernels.stencil2d.ops import stencil2d

            def step(x):
                return stencil2d(x, *coeffs, timesteps=t)
        elif ndim == 3:
            from repro.kernels.stencil3d.ops import stencil3d

            def step(x):
                return stencil3d(x, *coeffs, timesteps=t)
        else:
            raise ValueError(f"a one-chip sweep runs the kernel entry of its "
                             f"grid's rank, 1, 2 or 3; the grid "
                             f"{cfg['grid']} has rank {ndim}")
        return step, SingleDeviceSharding(self.devices[0])

    def setup(self) -> None:
        import jax
        self.fn, self.sharding = self._entry()
        shape, dtype = tuple(self.config["grid"]), self.config["dtype"]
        make = jax.jit(lambda k: jax.random.normal(k, shape, dtype),
                       out_shardings=self.sharding)
        self.x = make(seeded_key(self.seed))
        self.fn(self.x).block_until_ready()           # compiles every shape

    def window(self, seconds: float, trace: bool = False) -> dict:
        depth = self.QUEUE_DEPTH
        inflight: collections.deque = collections.deque()
        calls = 0
        t0 = time.perf_counter()
        with _span("bench.window", trace):
            while True:
                with _span("bench.dispatch", trace):
                    y = self.fn(self.x)
                calls += 1
                inflight.append(y)
                if len(inflight) > depth:
                    with _span("bench.wait", trace):
                        inflight.popleft().block_until_ready()
                if time.perf_counter() - t0 >= seconds:
                    break
            with _span("bench.wait", trace):
                y.block_until_ready()
        elapsed = time.perf_counter() - t0
        self.y = y
        return {"calls": calls, "elapsed_s": elapsed}

    def metrics(self, stats: dict) -> dict:
        points = math.prod(self.config["grid"]) * self.timesteps
        return {"gpts_per_s": stats["calls"] * points / stats["elapsed_s"]
                / 1e9}

    def work_per_call(self) -> dict:
        return work.sweep_work(self.config, self.timesteps)

    def outputs(self) -> dict:
        """Host copies of what the comparison reads; frees the program's
        device arrays.  One chip: the input and the last output, whole.  A
        mesh layout: max |input|, reduced on the device, and per axis the
        compared planes of the last output (``compared_planes``) with the
        input slab each needs, gathered on the device: never the whole
        grid."""
        if self.layout:
            out = self._sampled_outputs()
        else:
            out = {"x": np.asarray(self.x), "y": np.asarray(self.y)}
        self.x = self.y = self.fn = self.sharding = None
        return out

    def _sampled_outputs(self) -> dict:
        import jax
        import jax.numpy as jnp
        cfg, t = self.config, self.timesteps
        groups, index = [], []
        for ax, planes in enumerate(compared_planes(
                cfg["grid"], cfg["radii"], t, self.layout, self.seed,
                self.SAMPLED_PLANES)):
            n, h = cfg["grid"][ax], cfg["radii"][ax] * t
            w = min(2 * h + 1, n)
            lo = np.clip(np.asarray(planes) - h, 0, n - w)
            groups.append({"axis": ax, "planes": planes, "lo": lo})
            index.append(((lo[:, None] + np.arange(w)).ravel().astype(
                np.int32), np.asarray(planes, np.int32)))
        takes = [plane_gather(self.sharding, ax) for ax in range(len(index))]
        gather = jax.jit(lambda x, y, index: (
            jnp.max(jnp.abs(x)),
            [take(x, y, xi, yi) for take, (xi, yi) in zip(takes, index)]))
        absmax, got = jax.device_get(gather(self.x, self.y, index))
        for g, (xs, ys) in zip(groups, got):
            g["x"], g["y"] = xs, ys
        return {"absmax": float(absmax), "planes": groups}

    def _per_plane(self, group: dict, use, dtype=np.float64) -> list:
        """``use(y, ref)`` for each compared plane of ``group``: ``y`` the
        program's plane, ``ref`` the reference from its input slab, computed
        in ``dtype``; on one thread a core."""
        cfg, ax = self.config, group["axis"]
        w = group["x"].shape[ax] // len(group["planes"])

        def one(i):
            sl = [slice(None)] * group["x"].ndim
            sl[ax] = slice(i * w, (i + 1) * w)
            return use(np.take(group["y"], i, axis=ax),
                       reference.star_stencil_plane(
                           group["x"][tuple(sl)], cfg["coeffs"],
                           self.timesteps, axis=ax, lo=int(group["lo"][i]),
                           plane=group["planes"][i], grid=cfg["grid"],
                           dtype=dtype))

        with cf.ThreadPoolExecutor(
                max_workers=reference.default_threads()) as pool:
            return list(pool.map(one, range(len(group["planes"]))))

    def control(self, out: dict) -> dict:
        """The reference in the precision below the configured one, in the
        program's place."""
        low = reference.CONTROL_DTYPE[self.config["dtype"]]
        if self.layout:
            return {**out, "planes": [
                {**g, "y": np.stack(self._per_plane(g, lambda _y, ref: ref,
                                                    low), axis=g["axis"])}
                for g in out["planes"]]}
        y = reference.star_stencil(out["x"], self.config["coeffs"],
                                   self.timesteps, dtype=low)
        return {"x": out["x"], "y": y}

    def _grid_error(self, out: dict) -> tuple[float, float]:
        """Largest |output - reference| over the whole grid, and max
        |input|; NaN where any output is NaN."""
        x, y = out["x"], out["y"]
        worst = [0.0]
        lock = threading.Lock()

        def visit(lo, hi, ref):
            err = float(np.max(np.abs(y[lo:hi].astype(np.float64) - ref)))
            with lock:
                worst[0] = float(np.maximum(worst[0], err))

        reference.star_stencil(x, self.config["coeffs"], self.timesteps,
                               visit=visit)
        return worst[0], float(np.max(np.abs(x)))

    def _planes_error(self, out: dict) -> tuple[float, float]:
        """Largest |output - reference| over every compared plane, and max
        |input|; NaN where any output is NaN."""
        def err(y, ref):
            return float(np.max(np.abs(y.astype(np.float64) - ref)))

        worst = float(np.max([np.max(self._per_plane(g, err))
                              for g in out["planes"]]))
        return worst, out["absmax"]

    def compare(self, out: dict) -> tuple[list[Check], int]:
        """The output against the float64 reference, as a share of the
        configured precision's rounding bound: on one chip the whole grid
        (the zero rim included), on a mesh the compared planes.  Returns
        the checks and the outputs that failed."""
        worst, absmax = (self._planes_error(out) if self.layout
                         else self._grid_error(out))
        bound = reference.rounding_bound(self.config["coeffs"],
                                         self.timesteps, absmax,
                                         self.config["dtype"])
        check = Check("err_over_bound", worst / bound,
                      self.limits["err_over_bound"])
        return [check], int(not check.ok)


def compared_planes(grid, radii, timesteps: int, layout: dict, seed: int,
                    sampled: int) -> list[list[int]]:
    """Per axis, the output planes a mesh cell compares.  On every axis the
    ``r * T + 1`` planes next to each face: the zero rim and the first
    planes with full support.  On every sharded axis the ``r * T`` planes on
    each side of each shard boundary, whose outputs read the exchanged
    halo.  Along axis 0, ``sampled`` more drawn from ``seed``, so that over
    many runs every plane is compared."""
    shards = dict(zip(layout["axes"], layout["mesh"]))
    planes = []
    for ax, (n, r) in enumerate(zip(grid, radii)):
        h = r * timesteps
        keep = set(range(min(h + 1, n))) | set(range(max(n - h - 1, 0), n))
        s = shards.get(layout["partition"][ax], 1)
        for k in range(1, s):
            b = k * n // s
            keep |= set(range(max(b - h, 0), min(b + h, n)))
        planes.append(keep)
    rest = sorted(set(range(grid[0])) - planes[0])
    rng = np.random.default_rng([seed, len(grid)])
    planes[0] |= {int(p) for p in rng.choice(
        rest, size=min(sampled, len(rest)), replace=False)}
    return [sorted(p) for p in planes]


def plane_gather(sharding, axis: int):
    """A function ``f(x, y, x_index, y_index)`` that gathers the planes
    ``x_index`` of ``x`` and ``y_index`` of ``y`` along ``axis``, from
    arrays laid out as ``sharding``.  Each chip takes the planes it holds,
    zeros the rest, and a sum over the mesh axis that shards ``axis`` joins
    them, so no chip ever holds more than its shard and the planes."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    spec = tuple(sharding.spec) + (None,) * (axis + 1 - len(sharding.spec))
    name = spec[axis]
    out = P(*spec[:axis], None, *spec[axis + 1:])

    def take(a, index):
        if name is None:
            return jnp.take(a, index, axis=axis)
        n = a.shape[axis]
        local = index - jax.lax.axis_index(name) * n
        mine = (local >= 0) & (local < n)
        got = jnp.take(a, jnp.clip(local, 0, n - 1), axis=axis)
        shape = [1] * a.ndim
        shape[axis] = -1
        return jax.lax.psum(jnp.where(mine.reshape(shape), got, 0), name)

    return jax.shard_map(
        lambda x, y, xi, yi: (take(x, xi), take(y, yi)),
        mesh=sharding.mesh, in_specs=(P(*spec), P(*spec), P(), P()),
        out_specs=(out, out))


class SeededTarget:
    """The tuner's stencil target with its input grids drawn from the seed
    (one grid per call), recording what each measured lane returns."""

    def __init__(self, spec, workload_timesteps: int, seed: int, call: int):
        from repro.explore.space import SpecTarget
        self._base = SpecTarget(spec, workload_timesteps=workload_timesteps)
        self.seed, self.call = seed, call
        self.lanes: list[dict] = []

    def __getattr__(self, name):
        return getattr(self._base, name)

    def make_input(self, plan) -> np.ndarray:
        return tuner_input(self.seed, self.call, plan.spec.grid_shape)

    def verify(self, plan, cfg, x, res) -> None:
        self.lanes.append({"config": canonical(cfg.canonical()),
                           "grid": list(plan.spec.grid_shape),
                           "timesteps": plan.spec.timesteps,
                           "cycles": res.cycles, "loads": res.loads,
                           "stores": res.stores, "flops": res.flops,
                           "output": np.array(res.output)})


def tuner_input(seed: int, call: int, shape) -> np.ndarray:
    """The input grid of call ``call`` (-1 is the warm-up) of a seed."""
    return np.random.default_rng([seed, call + 1]).normal(size=tuple(shape))


def canonical(d: dict) -> str:
    return json.dumps(d, sort_keys=True, separators=(",", ":"))


class Tune:
    """Back-to-back ``explore()`` calls of the batched tuner, each a fresh
    search with no evaluation cache, each on its own seeded input grid."""

    def __init__(self, config: dict, traffic: dict, seed: int, devices,
                 table: dict | None = None):
        self.config, self.traffic, self.seed = config, traffic, seed
        self.calls: list[dict] = []
        self.limits = config["limits"][traffic["name"]]
        if table is None:
            name = f"{config['name']}.{traffic['name']}.counts.json"
            table = json.loads((BENCH_DIR / "configs" / name).read_text())
        self.table = table

    def setup(self) -> None:
        self.setup_space()
        self._call(-1)                    # compiles every lane group's loop
        self.calls.clear()

    def setup_space(self) -> None:
        """The stencil, machine and search space the traffic names."""
        from repro.core.roofline import Machine
        from repro.core.spec import StencilSpec
        from repro.explore import SpaceOptions, tile_candidates
        tcfg = self.config["tuner"]
        self.spec = StencilSpec(tuple(tcfg["grid"]),
                                tuple(self.config["radii"]),
                                tuple(tuple(c) for c in self.config["coeffs"]),
                                dtype=tcfg["dtype"])
        self.machine = Machine(**tcfg["machine"])
        space = self.traffic["space"]
        tiles = tile_candidates(self.spec, tuple(space["tile_budgets_bytes"]))
        self.options = SpaceOptions(
            temporal=tuple(space["temporal"]),
            capacities=tuple(space["capacities"]),
            tiles=(None,) + tuple(t for t in tiles if t is not None),
            fabrics=())

    def _call(self, index: int) -> None:
        from repro.explore import Budget, explore
        target = SeededTarget(self.spec,
                               self.config["tuner"]["workload_timesteps"],
                               self.seed, index)
        res = explore(target, self.machine, options=self.options,
                      budget=Budget(batch_size=self.traffic["batch_size"]),
                      verify=True)
        self.calls.append({
            "index": index, "stats": res.stats, "lanes": target.lanes,
            "points": [(canonical(p.config.canonical()), p.cycles, p.pes)
                       for p in res.ideal_points],
            "front": [p.objectives() for p in res.front]})

    def window(self, seconds: float, trace: bool = False) -> dict:
        t0 = time.perf_counter()
        n = 0
        with _span("bench.window", trace):
            while n == 0 or time.perf_counter() - t0 < seconds:
                with _span("bench.explore", trace):
                    self._call(n)
                n += 1
        return {"calls": n, "elapsed_s": time.perf_counter() - t0}

    def metrics(self, stats: dict) -> dict:
        return {"tune_s": stats["elapsed_s"] / stats["calls"]}

    def work_per_call(self) -> dict:
        return {"lanes": len(self.table["lanes"])}

    def outputs(self) -> dict:
        return {"calls": self.calls}

    def control(self, out: dict) -> dict:
        """Every lane's output grid replaced by the reference computed in
        the precision below the configured one."""
        low = reference.CONTROL_DTYPE[self.config["tuner"]["dtype"]]
        calls = []
        for c in out["calls"]:
            lanes = []
            for lane in c["lanes"]:
                x = tuner_input(self.seed, c["index"], lane["grid"])
                y = reference.star_stencil(x, self.config["coeffs"],
                                           lane["timesteps"], dtype=low)
                lanes.append({**lane, "output": y})
            calls.append({**c, "lanes": lanes})
        return {"calls": calls}

    def outside_model(self, lane: dict, point: tuple | None) -> bool:
        """Whether a measured lane's counts, or its search point's, break
        what the mapping's shape alone says they must be
        (``reference.cgra_lane_bounds``, ``work.py``)."""
        cfg = json.loads(lane["config"])
        tcfg, radii = self.config["tuner"], self.config["radii"]
        grid, t = lane["grid"], lane["timesteps"]
        want = reference.cgra_lane_bounds(
            grid, radii, cfg["workers"], t,
            np.dtype(tcfg["dtype"]).itemsize, tcfg["machine"])
        (llo, lhi), (clo, chi) = want["loads"], want["cycles"]
        repeats = tcfg["workload_timesteps"] // t * math.prod(
            -(-(n - 2 * r * t) // (g - 2 * r * t))
            for n, g, r in zip(tcfg["grid"], grid, radii))
        return not (llo <= lane["loads"] <= lhi
                    and clo <= lane["cycles"]
                    and (chi is None or lane["cycles"] <= chi)
                    and lane["stores"] == work.interior_points(grid, radii, t)
                    and lane["flops"] == work.flops(grid, radii,
                                                    self.config["coeffs"], t)
                    and point == (lane["cycles"] * repeats, want["pes"]))

    def compare(self, out: dict) -> tuple[list[Check], int]:
        """Per call: no cache hit and no lane on the host; every measured
        lane's simulated counts, and its point's workload cycles and PEs,
        within what the mapping's shape says (``outside_model``); the same
        set of lanes and the same counts as the program's own table (a
        regression fixture, ``make_counts.py``); the front is the Pareto set
        of the points; and each lane's output grid is within the float64
        rounding bound of the reference on that call's input."""
        coeffs = self.config["coeffs"]
        table = self.table["lanes"]
        n_fallback = n_cached = n_model = n_fixture = n_front = 0
        worst = 0.0
        failed = 0
        refs: dict = {}
        for c in out["calls"]:
            bad = 0
            n_fallback += c["stats"]["n_host_fallback"]
            n_cached += c["stats"]["n_cached"]
            bad += c["stats"]["n_host_fallback"] + c["stats"]["n_cached"]
            seen = {lane["config"]: lane for lane in c["lanes"]}
            pts = {k: (cyc, pes) for k, cyc, pes in c["points"]}
            model = sum(self.outside_model(lane, pts.get(key))
                        for key, lane in seen.items())
            fixture = len(set(table) ^ set(seen)) + sum(
                (lane["cycles"], lane["loads"]) != (want["cycles"],
                                                    want["loads"])
                or pts.get(key) != (want["workload_cycles"], want["pes"])
                for key, lane in seen.items()
                if (want := table.get(key)) is not None)
            n_model += model
            n_fixture += fixture
            bad += model + fixture
            front = reference.pareto_front(
                [(cyc, pes, 0) for cyc, pes in pts.values()])
            if set(map(tuple, c["front"])) != front or not front:
                n_front += 1
                bad += 1
            for lane in c["lanes"]:
                key = (c["index"], tuple(lane["grid"]), lane["timesteps"])
                if key not in refs:
                    x = tuner_input(self.seed, c["index"], lane["grid"])
                    refs[key] = (reference.star_stencil(
                        x, coeffs, lane["timesteps"]),
                        reference.rounding_bound(
                            coeffs, lane["timesteps"],
                            float(np.max(np.abs(x))), "float64"))
                ref, bound = refs[key]
                r = float(np.max(np.abs(lane["output"].astype(np.float64)
                                        - ref))) / bound
                worst = float(np.maximum(worst, r))
                limit = self.limits["err_over_bound"]
                if limit is None or not r <= limit:
                    bad += 1
            failed += bad > 0
        checks = [Check("err_over_bound", worst,
                        self.limits["err_over_bound"]),
                  Check("lanes_outside_model", n_model, 0),
                  Check("fixture_mismatches", n_fixture, 0),
                  Check("front_mismatches", n_front, 0),
                  Check("host_fallback_lanes", n_fallback, 0),
                  Check("cache_hits", n_cached, 0)]
        return checks, failed


LOOPS = {"sweeps": Sweeps, "tune": Tune}
