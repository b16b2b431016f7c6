"""Public entry point for the 1D stencil: planning, padding, backend dispatch.

``stencil1d(x, coeffs)`` accepts any (..., N) array.  The Pallas path is one
jitted program: it flattens the leading dims to a batch, pads batch and
length to the planned block multiples, runs the kernel (TPU, or
``interpret=True`` elsewhere), which alone masks the boundary against the
true length, and slices the padding away.  ``backend="xla"`` runs the
pure-jnp reference, which is also what the LM models use under jit on CPU.
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
    if backend == "auto":
        backend = "pallas" if jax.default_backend() == "tpu" else "xla"
    if backend == "xla":
        return stencil1d_ref(x, coeffs, timesteps=timesteps)

    interpret = jax.default_backend() != "tpu"
    return _entry(x, coeffs, timesteps, block, variant, interpret)


@functools.partial(jax.jit,
                   static_argnames=("coeffs", "timesteps", "block", "variant",
                                    "interpret"))
def _entry(x, coeffs, timesteps, block, variant, interpret):
    """The whole entry as one program: where the block divides the signal
    the reshapes are bitcasts and the pad and slice vanish, so the kernel
    is the only pass over it."""
    lead = x.shape[:-1]
    n = x.shape[-1]
    xb = x.reshape((-1, n))
    batch = xb.shape[0]
    bb, bn = block or plan_1d_blocks(n, batch, (len(coeffs) - 1) // 2,
                                     timesteps, variant=variant)
    pb = _next_multiple(batch, bb) - batch
    pn = _next_multiple(n, bn) - n
    out = stencil1d_pallas(jnp.pad(xb, ((0, pb), (0, pn))), coeffs,
                           timesteps=timesteps, block_b=bb, block_n=bn,
                           extent=n, variant=variant, interpret=interpret)
    return out[:batch, :n].reshape(*lead, n)


def stencil1d_from_spec(x: jax.Array, spec: StencilSpec, **kw) -> jax.Array:
    assert spec.ndim == 1
    return stencil1d(x, spec.coeffs[0], timesteps=spec.timesteps, **kw)
