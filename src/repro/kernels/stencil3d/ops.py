"""Public entry point for the 3D stencil.

``timesteps > 1`` runs T separate sweeps (each a Pallas call): fused-T 3D
star sweeps have diamond composite support and would need all 26 corner
views; the HBM round trip between sweeps is the documented trade (the CGRA/
1D/2D paths fuse in-fabric per §IV).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.stencil3d.kernel import stencil3d_pallas
from repro.kernels.stencil3d.ref import stencil3d_ref

# default per-core VMEM budget for auto-blocking (v5e has 128 MiB; leave
# headroom for the 7 halo views + double buffering the kernel allocates).
_VMEM_BUDGET_BYTES = 8 * 1024 * 1024


def _next_multiple(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def _auto_block(shape: tuple[int, int, int], cz, cy, cx,
                dtype: str, budget: int) -> tuple[int, int, int]:
    """Pick (bz, by, bx) with the CGRA strip-mining planner (§III-B): the
    same ``plan_blocks`` that sizes scratchpad strips sizes VMEM tiles."""
    from repro.core.mapping import plan_blocks
    from repro.core.spec import StencilSpec
    spec = StencilSpec(shape, tuple((len(c) - 1) // 2 for c in (cz, cy, cx)),
                       (tuple(cz), tuple(cy), tuple(cx)), dtype=dtype)
    bz, by, bx = plan_blocks(spec, budget, lane_multiple=128).block_shape
    # TPU sublane tiling holds by construction: plan_blocks seeds the y axis
    # at min(ny, 8) and only grows it in +8 steps.
    assert by == shape[1] or by % 8 == 0
    return (bz, by, bx)


def stencil3d(x: jax.Array, cz, cy, cx, *, timesteps: int = 1,
              backend: str = "auto",
              block: tuple[int, int, int] | None = (8, 16, 128),
              vmem_budget_bytes: int = _VMEM_BUDGET_BYTES) -> jax.Array:
    """Batched 3D star stencil over the last three axes (z, y, x).

    ``block=None`` derives the tile from :func:`repro.core.mapping.plan_blocks`
    under ``vmem_budget_bytes`` instead of using a fixed shape.
    """
    cz = tuple(float(c) for c in cz)
    cy = tuple(float(c) for c in cy)
    cx = tuple(float(c) for c in cx)
    if backend == "auto":
        backend = "pallas" if jax.default_backend() == "tpu" else "xla"
    if backend == "xla":
        return stencil3d_ref(x, cz, cy, cx, timesteps=timesteps)
    interpret = jax.default_backend() != "tpu"
    return _entry(x, cz, cy, cx, timesteps, block, vmem_budget_bytes,
                  interpret)


@functools.partial(jax.jit,
                   static_argnames=("cz", "cy", "cx", "timesteps", "block",
                                    "vmem_budget_bytes", "interpret"))
def _entry(x, cz, cy, cx, timesteps, block, vmem_budget_bytes, interpret):
    """The whole entry as one program: where the block divides the grid the
    reshapes are bitcasts and the pad and slice vanish, so the T kernel
    calls are the only passes over the grid, each masking its own rim."""
    lead = x.shape[:-3]
    nz, ny, nx = x.shape[-3:]
    if block is None:
        block = _auto_block((nz, ny, nx), cz, cy, cx, str(x.dtype),
                            vmem_budget_bytes)
    bz, by, bx = block
    pz, py, px = (_next_multiple(nz, bz) - nz, _next_multiple(ny, by) - ny,
                  _next_multiple(nx, bx) - nx)
    out = jnp.pad(x.reshape((-1, nz, ny, nx)),
                  ((0, 0), (0, pz), (0, py), (0, px)))
    rz, ry, rx = ((len(c) - 1) // 2 for c in (cz, cy, cx))
    for t in range(1, timesteps + 1):
        out = stencil3d_pallas(out, cz, cy, cx, block=block,
                               extent=(nz, ny, nx),
                               rim=(rz * t, ry * t, rx * t),
                               interpret=interpret)
    return out[:, :nz, :ny, :nx].reshape(*lead, nz, ny, nx)
