#!/usr/bin/env python3
"""Drive the main path once on a TPU and check every result.

Run from the root of a checkout:

    python chip_smoke.py               # one chip: kernels, simulator, tuner
    python chip_smoke.py --four-chips  # the halo-exchange step on a 2x2 mesh

Everything runs in this one process, which owns the chip.  Phases:

* kernels: the Pallas stencil kernels through their public entry points
  (``backend="auto"``, the planner's own block) at the paper's grids and at
  heat grids resident in HBM, each against the float64 numpy oracle within
  the float32 error bound of ``f32_bound``.  The compiled program must hold
  a Mosaic kernel (``tpu_custom_call``), so a run in interpret mode or on
  the jnp reference path fails.
* simulator: ``simulate_batch(engine="jax")`` on the device against
  ``engine="vector"`` on the host.  Cycles, per-op fires, loads, stores,
  flops and output bits must be identical.
* tuner: the batched tuner sweep against the sequential one.  Same Pareto
  front, no cache hits, no lane rerouted to the host engine.
* four chips (``--four-chips`` only): ``distributed_stencil2d`` and
  ``distributed_stencil3d`` over a ("pod", "data") 2x2 mesh against the
  one-chip kernel result, with the output spread over 4 distinct devices.

Each phase prints one JSON line per case.  Where JAX finds no TPU the script
exits non-zero and prints no result.  On success the last line of standard
output is ``{"ok": true, "device": {"platform", "kind", "count"}}``.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import traceback
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

SEED = 0
#: what a compiled program holds where a Pallas kernel runs on the TPU
KERNEL_MARKER = "tpu_custom_call"


class SmokeFailure(AssertionError):
    """A result disagreed with its oracle."""


def emit(**fields) -> None:
    print(json.dumps(fields), flush=True)


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def f32_bound(coeffs, timesteps: int, x_absmax: float) -> float:
    """Largest |float32 result - exact result| a star stencil can show.

    One sweep sums the n nonzero taps in float32.  With unit roundoff
    u = eps/2, rounding each coefficient, each product and each of the n-1
    additions adds at most u * sum|c_k x_k| <= u * S * M (S = sum |c_k|,
    M = max |x|), so a sweep errs by at most (n + 1) u S M.  Sweep t passes
    the error before it on with gain S and adds (n + 1) u S^t M of its own,
    so T fused sweeps err by at most T (n + 1) u S^T M.  Using eps for u
    leaves a factor 2 of headroom for the order XLA and Mosaic pick."""
    taps = sum(1 for axis in coeffs for c in axis if c != 0.0)
    s = sum(abs(c) for axis in coeffs for c in axis)
    eps = float(np.finfo(np.float32).eps)
    return timesteps * (taps + 1) * eps * s ** timesteps * x_absmax


def oracle(x: np.ndarray, spec, timesteps: int) -> np.ndarray:
    """float64 reference over the spec's grid (last axes of ``x``)."""
    from repro.core.reference import stencil_reference_np
    spec64 = dataclasses.replace(spec, dtype="float64", timesteps=timesteps)
    x64 = x.astype(np.float64)
    if x.ndim == len(spec.grid_shape):
        return stencil_reference_np(x64, spec64)
    return np.stack([stencil_reference_np(row, spec64) for row in x64])


def f32_input(rng, shape) -> np.ndarray:
    return rng.standard_normal(shape, dtype=np.float32)


def kernel_case(name: str, fn, x: np.ndarray, spec, timesteps: int) -> None:
    import jax
    xd = jax.device_put(x)
    compiled = jax.jit(fn).lower(xd).compile()
    has_kernel = KERNEL_MARKER in compiled.as_text()
    y = np.asarray(compiled(xd))
    err = float(np.max(np.abs(y - oracle(x, spec, timesteps))))
    tol = f32_bound(spec.coeffs, timesteps, float(np.max(np.abs(x))))
    emit(phase="kernels", case=name, shape=list(x.shape), timesteps=timesteps,
         max_err=err, tol=tol, tpu_custom_call=has_kernel)
    check(has_kernel, f"{name}: no {KERNEL_MARKER} in the compiled program")
    check(y.shape == x.shape, f"{name}: output shape {y.shape}")
    check(err <= tol, f"{name}: max error {err} above bound {tol}")


def phase_kernels(n1: int = 194_400, seismic: tuple = (449, 960),
                  heat2d: int = 8192, heat3d: int = 512) -> None:
    from repro.core.spec import (heat_2d, heat_3d, paper_stencil_1d,
                                 paper_stencil_2d)
    from repro.kernels.stencil1d.ops import stencil1d
    from repro.kernels.stencil2d.ops import stencil2d
    from repro.kernels.stencil3d.ops import stencil3d
    rng = np.random.default_rng(SEED)

    s1 = paper_stencil_1d(n=n1, dtype="float32")
    (c1,) = s1.coeffs
    x1 = f32_input(rng, (n1,))
    kernel_case("stencil1d_paper", lambda x: stencil1d(x, c1), x1, s1, 1)
    xb = f32_input(rng, (8, n1))
    kernel_case("stencil1d_paper_batch8", lambda x: stencil1d(x, c1), xb,
                s1, 1)
    kernel_case("stencil1d_paper_T4",
                lambda x: stencil1d(x, c1, timesteps=4), x1, s1, 4)

    s2 = paper_stencil_2d(*seismic, dtype="float32")
    x2 = f32_input(rng, seismic)
    kernel_case("stencil2d_seismic", lambda x: stencil2d(x, *s2.coeffs), x2,
                s2, 1)
    kernel_case("stencil2d_seismic_T4",
                lambda x: stencil2d(x, *s2.coeffs, timesteps=4), x2, s2, 4)

    h2 = heat_2d(heat2d, heat2d)
    kernel_case("stencil2d_heat", lambda x: stencil2d(x, *h2.coeffs),
                f32_input(rng, h2.grid_shape), h2, 1)

    h3 = heat_3d(heat3d, heat3d, heat3d)
    kernel_case("stencil3d_heat", lambda x: stencil3d(x, *h3.coeffs),
                f32_input(rng, h3.grid_shape), h3, 1)


def phase_simulator(n1: int = 194_400, seismic: tuple = (113, 240)) -> None:
    from repro.core import CGRA, map_1d, map_2d
    from repro.core.simulator import simulate_batch
    from repro.core.spec import paper_stencil_1d, paper_stencil_2d
    rng = np.random.default_rng(SEED)
    s1 = paper_stencil_1d(n=n1)
    s2 = paper_stencil_2d(*seismic, r=12)
    cases = [("paper1d_w6", s1, lambda: map_1d(s1, workers=6)),
             ("seismic2d_w5", s2, lambda: map_2d(s2, workers=5))]
    for name, spec, mk in cases:
        x = rng.normal(size=spec.grid_shape)
        (dev,) = simulate_batch([(mk(), x)], CGRA, engine="jax")
        (host,) = simulate_batch([(mk(), x)], CGRA, engine="vector")
        for engine, res in (("jax", dev), ("vector", host)):
            check(not isinstance(res, Exception),
                  f"{name}: engine={engine} failed: {res!r}")
        same = {
            "cycles": dev.cycles == host.cycles,
            "fires": dev.fires == host.fires,
            "loads": dev.loads == host.loads,
            "stores": dev.stores == host.stores,
            "flops": dev.flops == host.flops,
            "output_bits": dev.output.tobytes() == host.output.tobytes(),
        }
        emit(phase="simulator", case=name, grid=list(spec.grid_shape),
             cycles=dev.cycles, vector_cycles=host.cycles, loads=dev.loads,
             stores=dev.stores, identical=same)
        check(all(same.values()),
              f"{name}: jax engine differs from vector: {same}")


def phase_tuner(grid: tuple = (48, 96)) -> None:
    """The heat2d stage-1 ideal sweep, batched on the device vs sequential
    on the host."""
    from repro.core import CGRA
    from repro.core.spec import heat_2d
    from repro.explore import Budget, SpaceOptions, explore, tile_candidates
    heat = heat_2d(*grid, dtype="float64")
    opts = SpaceOptions(
        temporal=(1, 2), capacities=("auto", "unbounded"),
        tiles=(None,) + tuple(t for t in tile_candidates(heat, (2048, 8192))
                              if t is not None),
        fabrics=())
    dev = explore(heat, CGRA, options=opts, budget=Budget(batch_size=32),
                  workload_timesteps=2)
    host = explore(heat, CGRA, options=opts, budget=Budget(),
                   workload_timesteps=2)

    def front(res):
        return sorted(json.dumps([p.config.canonical(), p.objectives()],
                                 sort_keys=True) for p in res.front)

    same_front = front(dev) == front(host)
    emit(phase="tuner", case="heat2d_stage1_sweep", grid=list(grid),
         n_measured=dev.stats["n_measured"],
         vector_n_measured=host.stats["n_measured"],
         n_cached=dev.stats["n_cached"],
         n_host_fallback=dev.stats["n_host_fallback"],
         front_size=len(dev.front), same_front=same_front)
    check(dev.stats["n_measured"] == host.stats["n_measured"] > 0,
          "tuner: batched and sequential sweeps measured different configs")
    check(dev.stats["n_cached"] == 0, "tuner: cache hits in a fresh sweep")
    check(dev.stats["n_host_fallback"] == 0,
          "tuner: lanes fell back to the host engine")
    check(same_front, "tuner: Pareto fronts differ")


def phase_four_chips(heat2d: int = 16_384, heat3d: int = 512) -> None:
    import jax
    from jax.sharding import AxisType, NamedSharding, PartitionSpec as P

    from repro.core.spec import heat_2d, heat_3d
    from repro.distributed.halo import (distributed_stencil2d,
                                        distributed_stencil3d)
    from repro.kernels.stencil2d.ops import stencil2d
    from repro.kernels.stencil3d.ops import stencil3d
    devices = jax.devices()
    check(len(devices) >= 4, f"four-chips: {len(devices)} devices")
    axes = ("pod", "data")
    mesh = jax.make_mesh((2, 2), axes, devices=devices[:4],
                         axis_types=(AxisType.Auto,) * 2)
    rng = np.random.default_rng(SEED)
    s2 = dataclasses.replace(heat_2d(heat2d, heat2d), timesteps=4)
    s3 = heat_3d(heat3d, heat3d, heat3d)
    cases = [
        ("distributed_stencil2d_heat_T4", s2, distributed_stencil2d,
         P(*axes), lambda x: stencil2d(x, *s2.coeffs, timesteps=4)),
        ("distributed_stencil3d_heat", s3, distributed_stencil3d,
         P(*axes, None), lambda x: stencil3d(x, *s3.coeffs)),
    ]
    for name, spec, build, pspec, one_chip in cases:
        x = f32_input(rng, spec.grid_shape)
        y = build(spec, mesh, axes=axes)(
            jax.device_put(x, NamedSharding(mesh, pspec)))
        spread = {s.device for s in y.addressable_shards}
        y1 = jax.jit(one_chip)(jax.device_put(x, devices[0]))
        err = float(np.max(np.abs(np.asarray(y) - np.asarray(y1))))
        # both sides are within f32_bound of the exact result
        tol = 2 * f32_bound(spec.coeffs, spec.timesteps,
                            float(np.max(np.abs(x))))
        emit(phase="four_chips", case=name, shape=list(spec.grid_shape),
             timesteps=spec.timesteps, devices=len(spread),
             sharding_devices=len(y.sharding.device_set),
             max_err_vs_one_chip=err, tol=tol)
        check(len(spread) == 4 and len(y.sharding.device_set) == 4,
              f"{name}: output on {len(spread)} devices, not 4")
        check(err <= tol, f"{name}: max error {err} above bound {tol}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the halo-exchange step on a 2x2 mesh")
    args = ap.parse_args(argv)
    try:
        from repro.compile_cache import configure_compile_cache
    except ImportError as e:
        print(f"chip_smoke: the repro package is not here ({e}); run from "
              "the root of a checkout", file=sys.stderr)
        return 2
    cache_dir = configure_compile_cache()

    import jax
    devices = jax.devices()
    d0 = devices[0]
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(devices)}
    if d0.platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (default device: {d0}); "
              "nothing was run", file=sys.stderr)
        return 1
    emit(device=device, compile_cache=cache_dir)

    phases = ([("four_chips", phase_four_chips)] if args.four_chips else
              [("kernels", phase_kernels), ("simulator", phase_simulator),
               ("tuner", phase_tuner)])
    failed = []
    for name, phase in phases:
        try:
            phase()
        except Exception:                  # report the phase, run the rest
            traceback.print_exc()
            failed.append(name)
        emit(phase=name, ok=name not in failed)
    if failed:
        print(f"chip_smoke: failed phases: {failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
