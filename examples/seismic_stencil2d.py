"""The paper's §VI oil/gas seismic stencil (49-pt, rx=ry=12, 960x449) run
through every layer of the stack on one host:

  roofline (§VI) -> CGRA mapping (§III-B) -> cycle simulation (§VIII, reduced
  grid) -> TPU Pallas kernel (interpret) -> fused-timestep variant (§IV).

Run:  PYTHONPATH=src python examples/seismic_stencil2d.py
"""
import dataclasses
import time

import jax.numpy as jnp
import numpy as np

from repro.core import CGRA, TPU_V5E, analyze, map_2d, simulate
from repro.core.reference import stencil_reference_np
from repro.core.spec import paper_stencil_2d
from repro.kernels.stencil2d.ops import plan_2d_blocks, stencil2d


def main():
    spec = paper_stencil_2d()                       # 960x449, r=12, fp64
    roof = analyze(spec, CGRA)
    print(f"[roofline] AI={roof.arithmetic_intensity:.2f} -> "
          f"{roof.achievable_gflops:.0f} GFLOPS on CGRA (w*={roof.workers}); "
          f"paper: 559 GFLOPS, 5 workers")

    # cycle-accurate simulation at 1/16 grid (utilization is scale-stable)
    small = paper_stencil_2d(ny=113, nx=240, r=12)
    plan = map_2d(small, workers=5)
    x = np.random.default_rng(0).normal(size=small.grid_shape)
    t0 = time.time()
    res = simulate(plan, x, CGRA)
    ok = np.allclose(res.output, stencil_reference_np(x, small))
    print(f"[simulate] {res.summary()}  exact={ok}  ({time.time()-t0:.1f}s)"
          f"  paper: 77-78% of peak")

    # TPU kernel, fp32, with the VMEM block planner (§III-B Blocking)
    spec32 = paper_stencil_2d(dtype="float32")
    blocks = plan_2d_blocks(449, 960, 12, 12, timesteps=1)
    xf = jnp.asarray(np.random.default_rng(1).normal(size=(1, 449, 960)),
                     jnp.float32)
    y = stencil2d(xf, spec32.coeffs[0], spec32.coeffs[1], backend="pallas")
    ref = stencil_reference_np(np.asarray(xf[0]),
                               dataclasses.replace(spec32))
    print(f"[pallas] blocks={blocks} max err={np.abs(np.asarray(y[0])-ref).max():.2e}")

    # fused timesteps: where does the seismic stencil turn compute-bound?
    for t in (1, 2, 4):
        st = dataclasses.replace(spec32, timesteps=t)
        r = analyze(st, TPU_V5E)
        print(f"[fusion T={t}] AI={r.arithmetic_intensity:6.2f} -> "
              f"{r.achievable_gflops/1000:6.2f} TFLOPS on v5e ({r.bound})")


def lint_plans():
    """Static-verifier hook (``python -m repro.analysis.lint examples/``)."""
    yield map_2d(paper_stencil_2d(ny=30, nx=48, r=12), workers=8)


if __name__ == "__main__":
    main()
