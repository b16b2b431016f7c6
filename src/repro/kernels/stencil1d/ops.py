"""Public entry point for the 1D stencil: planning, padding, backend dispatch.

``stencil1d(x, coeffs)`` accepts any (..., N) array:
  * flattens leading dims to a batch,
  * pads batch/length to the planned block multiples (zero padding is
    harmless: the kernel's position masks ignore out-of-range columns, and
    padded batch rows are sliced away),
  * dispatches to the Pallas kernel (TPU, or ``interpret=True`` elsewhere) or
    the pure-jnp reference (``backend="xla"``), which is also what the LM
    models use under jit on CPU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.spec import StencilSpec
from repro.kernels.stencil1d.kernel import stencil1d_pallas
from repro.kernels.stencil1d.ref import stencil1d_ref

# three quarters of the 16 MiB of VMEM the TPU v5e compiler gives a kernel
# by default: the rest is headroom for what the working-set model misses
VMEM_BUDGET_BYTES = 12 * 1024 * 1024


def plan_1d_blocks(n: int, batch: int, radius: int, timesteps: int,
                   bytes_per_elem: int = 4,
                   vmem_budget: int = VMEM_BUDGET_BYTES,
                   variant: str = "vpu") -> tuple[int, int]:
    """Pick (block_b, block_n): lane-aligned block_n as large as fits.

    Working set as the TPU compiler allocates it: 3 input views and the
    output tile, double-buffered, with the batch rows padded to the
    8-sublane tile, plus at most 6 + taps/2 f32 haloed workspaces for the
    tap ladder (v5e compiles of the 17-tap stencil at T=1 and T=4).  The
    ``mxu`` variant also holds its (block_n+2h) x (block_n+2h-2r) f32 band
    matrix, which caps its block far below the ``vpu`` one."""
    halo = radius * timesteps
    taps = 2 * radius + 1
    block_b = 8 if batch >= 8 else max(1, batch)
    rows = _next_multiple(block_b, 8)

    def ws(bn):
        w = bn + 2 * halo
        total = (2 * (3 + 1) * rows * bn * bytes_per_elem
                 + (6 + taps // 2) * rows * w * 4)
        if variant == "mxu":
            total += w * (w - 2 * radius) * 4
        return total

    block_n = 128
    while block_n < min(n, 4096) and ws(block_n * 2) <= vmem_budget:
        block_n *= 2
    block_n = max(block_n, _next_multiple(halo, 128))
    return block_b, block_n


def _next_multiple(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def stencil1d(x: jax.Array, coeffs: tuple[float, ...], *,
              timesteps: int = 1, backend: str = "auto",
              variant: str = "vpu",
              block: tuple[int, int] | None = None) -> jax.Array:
    """Batched 1D star stencil along the last axis. See ref.py for semantics."""
    coeffs = tuple(float(c) for c in coeffs)
    r = (len(coeffs) - 1) // 2
    if backend == "auto":
        backend = "pallas" if jax.default_backend() == "tpu" else "xla"
    if backend == "xla":
        return stencil1d_ref(x, coeffs, timesteps=timesteps)

    interpret = jax.default_backend() != "tpu"
    lead = x.shape[:-1]
    n = x.shape[-1]
    xb = x.reshape((-1, n))
    batch = xb.shape[0]
    if block is None:
        block = plan_1d_blocks(n, batch, r, timesteps, variant=variant)
    bb, bn = block
    pb = _next_multiple(batch, bb) - batch
    pn = _next_multiple(n, bn) - n
    xp = jnp.pad(xb, ((0, pb), (0, pn)))
    # padded tail columns are masked via the n-argument = true length
    out = _dispatch(xp, coeffs, timesteps, bb, bn, variant, interpret, n)
    return out[:batch, :n].reshape(*lead, n)


@functools.partial(jax.jit,
                   static_argnames=("coeffs", "timesteps", "bb", "bn",
                                    "variant", "interpret", "true_n"))
def _dispatch(xp, coeffs, timesteps, bb, bn, variant, interpret, true_n):
    # The kernel masks by padded length; re-mask by the true length so padded
    # columns cannot contribute (they're zero anyway) and outputs beyond
    # true_n - halo are dropped.
    y = stencil1d_pallas(xp, coeffs, timesteps=timesteps, block_b=bb,
                         block_n=bn, variant=variant, interpret=interpret)
    r = (len(coeffs) - 1) // 2
    halo = r * timesteps
    idx = jnp.arange(xp.shape[-1])
    valid = (idx >= halo) & (idx < true_n - halo)
    return jnp.where(valid, y, 0).astype(y.dtype)


def stencil1d_from_spec(x: jax.Array, spec: StencilSpec, **kw) -> jax.Array:
    assert spec.ndim == 1
    return stencil1d(x, spec.coeffs[0], timesteps=spec.timesteps, **kw)
