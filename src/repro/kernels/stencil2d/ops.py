"""Public entry point for the 2D stencil: planning, padding, backend dispatch."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.spec import StencilSpec
from repro.kernels.stencil2d.kernel import stencil2d_pallas
from repro.kernels.stencil2d.ref import stencil2d_ref

# three quarters of the 16 MiB of VMEM the TPU v5e compiler gives a kernel
# by default: the rest is headroom for what the working-set model misses
VMEM_BUDGET_BYTES = 12 * 1024 * 1024


def plan_2d_blocks(ny: int, nx: int, ry: int, rx: int, timesteps: int,
                   bytes_per_elem: int = 4,
                   vmem_budget: int = VMEM_BUDGET_BYTES) -> tuple[int, int]:
    """(block_y, block_x): x stays lane-aligned (128), y in sublane units (8).

    Working set as the TPU compiler allocates it: the 9 input views and the
    output tile, each double-buffered by the pipeline, plus the f32 haloed
    workspace and the shifted slices the unrolled tap ladder keeps alive —
    at most 6 + taps/2 workspace-sized buffers in v5e compiles of 5- to
    49-tap stencils at T=1 and T=4."""
    hy, hx = ry * timesteps, rx * timesteps
    taps = 2 * (ry + rx) + 1
    by = max(8, _next_multiple(hy, 8))
    bx = max(128, _next_multiple(hx, 128))

    def ws(by_, bx_):
        ext = (by_ + 2 * hy) * (bx_ + 2 * hx)
        return (2 * (9 + 1) * by_ * bx_ * bytes_per_elem
                + (6 + taps // 2) * ext * 4)

    progress = True
    while progress:
        progress = False
        if by < min(ny, 512) and ws(by * 2, bx) <= vmem_budget:
            by *= 2
            progress = True
        if bx < min(nx, 1024) and ws(by, bx * 2) <= vmem_budget:
            bx *= 2
            progress = True
    return by, bx


def _next_multiple(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def stencil2d(x: jax.Array, cy: tuple[float, ...], cx: tuple[float, ...], *,
              timesteps: int = 1, backend: str = "auto",
              block: tuple[int, int] | None = None) -> jax.Array:
    """Batched 2D star stencil over the last two axes (y=-2, x=-1)."""
    cy = tuple(float(c) for c in cy)
    cx = tuple(float(c) for c in cx)
    if backend == "auto":
        backend = "pallas" if jax.default_backend() == "tpu" else "xla"
    if backend == "xla":
        return stencil2d_ref(x, cy, cx, timesteps=timesteps)

    interpret = jax.default_backend() != "tpu"
    ry, rx = (len(cy) - 1) // 2, (len(cx) - 1) // 2
    lead = x.shape[:-2]
    ny, nx = x.shape[-2:]
    xb = x.reshape((-1, ny, nx))
    if block is None:
        block = plan_2d_blocks(ny, nx, ry, rx, timesteps)
    by, bx = block
    py = _next_multiple(ny, by) - ny
    px = _next_multiple(nx, bx) - nx
    xp = jnp.pad(xb, ((0, 0), (0, py), (0, px)))
    out = _dispatch(xp, cy, cx, timesteps, by, bx, interpret, ny, nx)
    return out[:, :ny, :nx].reshape(*lead, ny, nx)


@functools.partial(jax.jit,
                   static_argnames=("cy", "cx", "timesteps", "by", "bx",
                                    "interpret", "tny", "tnx"))
def _dispatch(xp, cy, cx, timesteps, by, bx, interpret, tny, tnx):
    y = stencil2d_pallas(xp, cy, cx, timesteps=timesteps, block_y=by,
                         block_x=bx, interpret=interpret)
    ry, rx = (len(cy) - 1) // 2, (len(cx) - 1) // 2
    hy, hx = ry * timesteps, rx * timesteps
    jj = jnp.arange(xp.shape[-2])[:, None]
    ii = jnp.arange(xp.shape[-1])[None, :]
    valid = (jj >= hy) & (jj < tny - hy) & (ii >= hx) & (ii < tnx - hx)
    return jnp.where(valid, y, 0).astype(y.dtype)


def stencil2d_from_spec(x: jax.Array, spec: StencilSpec, **kw) -> jax.Array:
    assert spec.ndim == 2
    return stencil2d(x, spec.coeffs[0], spec.coeffs[1],
                     timesteps=spec.timesteps, **kw)
