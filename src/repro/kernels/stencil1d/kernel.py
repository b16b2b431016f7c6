"""Pallas TPU kernel for the batched 1D star stencil (paper §III-A on TPU).

CGRA→TPU mapping (DESIGN.md §3):
  * a Pallas *program instance* (one grid cell) = one worker team: it owns an
    output tile of ``(block_b, block_n)`` points;
  * the reader workers' load-once/reuse-2r-times discipline = the halo-view
    trick: the input row is DMA'd into VMEM once per tile (plus two
    neighbour-tile views) and every one of the 2r+1 taps reads it from VMEM;
  * the MUL→MAC chain = an unrolled shift–FMA ladder on the VPU;
  * the data-filtering PEs (0^m 1^n 0^p) = position masks from
    ``broadcasted_iota`` — same predicate, vectorized;
  * §IV temporal pipelining = ``timesteps`` fused sweeps in VMEM with the halo
    widened to ``r * timesteps`` (trapezoid tiling).

Two compute formulations:
  * ``_stencil_vpu_body``  — shift-FMA ladder (tap-parallel on lanes); flops =
    2*(2r+1) per point; VPU-bound.
  * ``_stencil_mxu_body``  — beyond-paper: out = ext @ W_band, a banded-matrix
    matmul that trades ~(block_n+2rT)/(2r+1)x redundant flops for MXU
    throughput; wins once the fused stencil turns compute-bound (see
    EXPERIMENTS.md §Perf).

Grid requirements (enforced by ops.py): N % block_n == 0, B % block_b == 0,
r * timesteps <= block_n.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu


def _ext_positions(j, block_n: int, halo: int):
    return j * block_n - halo + jax.lax.broadcasted_iota(
        jnp.int32, (1, block_n + 2 * halo), 1)


def _masked_ext(prev, cur, nxt, j, *, block_n: int, halo: int, n: int,
                acc_dtype):
    """Assemble the haloed VMEM workspace; zero positions outside [0, n)
    (this also erases the garbage the clamped edge views bring in)."""
    ext = jnp.concatenate(
        [prev[:, -halo:], cur[:, :], nxt[:, :halo]], axis=1).astype(acc_dtype)
    pos = _ext_positions(j, block_n, halo)
    return jnp.where((pos >= 0) & (pos < n), ext, 0)


def _sweep_ladder(ext, coeffs: tuple[float, ...], out_w: int, acc_dtype):
    """One stencil sweep: shift-FMA ladder over the taps (the MAC chain)."""
    r = (len(coeffs) - 1) // 2
    acc = jnp.zeros((ext.shape[0], out_w), acc_dtype)
    for k, c in enumerate(coeffs):
        if c == 0.0:
            continue
        acc = acc + jnp.asarray(c, acc_dtype) * ext[:, k:k + out_w]
    return acc


def _vpu_body(prev, cur, nxt, o, *, coeffs, timesteps, block_n, n, out_dtype):
    j = pl.program_id(1)
    r = (len(coeffs) - 1) // 2
    halo = r * timesteps
    acc_dtype = jnp.float32
    ext = _masked_ext(prev, cur, nxt, j, block_n=block_n, halo=halo, n=n,
                      acc_dtype=acc_dtype)
    w = block_n + 2 * halo
    for _ in range(timesteps):
        w -= 2 * r
        ext = _sweep_ladder(ext, coeffs, w, acc_dtype)
    opos = j * block_n + jax.lax.broadcasted_iota(jnp.int32, (1, block_n), 1)
    valid = (opos >= halo) & (opos < n - halo)
    o[:, :] = jnp.where(valid, ext, 0).astype(out_dtype)


def _mxu_body(prev, cur, nxt, band, o, *, timesteps, radius, block_n, n,
              out_dtype):
    """out = ext @ W_band (one banded matmul per fused sweep)."""
    j = pl.program_id(1)
    halo = radius * timesteps
    ext = _masked_ext(prev, cur, nxt, j, block_n=block_n, halo=halo, n=n,
                      acc_dtype=jnp.float32)
    w = block_n + 2 * halo
    off = 0
    for _ in range(timesteps):
        w -= 2 * radius
        # band operand holds the largest needed banded matrix; slice per sweep.
        ext = jax.lax.dot_general(
            ext, band[off:off + w + 2 * radius, :w],
            (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        off = 0  # band rows always indexed from 0: widths only shrink
    opos = j * block_n + jax.lax.broadcasted_iota(jnp.int32, (1, block_n), 1)
    valid = (opos >= halo) & (opos < n - halo)
    o[:, :] = jnp.where(valid, ext, 0).astype(out_dtype)


def make_band(coeffs: tuple[float, ...], in_w: int, out_w: int) -> np.ndarray:
    """Banded matrix W with W[i + k, i] = coeffs[k]: ext(in_w) @ W -> (out_w)."""
    r = (len(coeffs) - 1) // 2
    assert in_w >= out_w + 2 * r
    band = np.zeros((in_w, out_w), np.float32)
    for k, c in enumerate(coeffs):
        for i in range(out_w):
            band[i + k, i] = c
    return band


@functools.partial(
    jax.jit,
    static_argnames=("coeffs", "timesteps", "block_b", "block_n", "variant",
                     "interpret"))
def stencil1d_pallas(x: jax.Array, coeffs: tuple[float, ...], *,
                     timesteps: int = 1, block_b: int = 8,
                     block_n: int = 512, variant: str = "vpu",
                     interpret: bool = False) -> jax.Array:
    """x: (B, N) -> (B, N). Requires B % block_b == 0, N % block_n == 0,
    radius * timesteps <= block_n (ops.py pads to satisfy these)."""
    b, n = x.shape
    r = (len(coeffs) - 1) // 2
    halo = r * timesteps
    if b % block_b or n % block_n:
        raise ValueError(f"shape {x.shape} not divisible by block "
                         f"({block_b},{block_n}); pad in ops.py")
    if halo > block_n:
        raise ValueError(f"halo {halo} exceeds block_n {block_n}")
    nb, nn = b // block_b, n // block_n

    views = [
        pl.BlockSpec((block_b, block_n), lambda i, j: (i, jnp.maximum(j - 1, 0))),
        pl.BlockSpec((block_b, block_n), lambda i, j: (i, j)),
        pl.BlockSpec((block_b, block_n),
                     lambda i, j, _nn=nn: (i, jnp.minimum(j + 1, _nn - 1))),
    ]
    out_spec = pl.BlockSpec((block_b, block_n), lambda i, j: (i, j))
    out_shape = jax.ShapeDtypeStruct((b, n), x.dtype)
    params = pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary"))

    if variant == "vpu":
        body = functools.partial(
            _vpu_body, coeffs=coeffs, timesteps=timesteps, block_n=block_n,
            n=n, out_dtype=x.dtype)
        return pl.pallas_call(
            body, grid=(nb, nn), in_specs=views, out_specs=out_spec,
            out_shape=out_shape, compiler_params=params,
            interpret=interpret)(x, x, x)
    elif variant == "mxu":
        band = jnp.asarray(make_band(coeffs, block_n + 2 * halo,
                                     block_n + 2 * halo - 2 * r))
        band_spec = pl.BlockSpec(band.shape, lambda i, j: (0, 0))
        body = functools.partial(
            _mxu_body, timesteps=timesteps, radius=r, block_n=block_n, n=n,
            out_dtype=x.dtype)
        return pl.pallas_call(
            body, grid=(nb, nn), in_specs=views + [band_spec],
            out_specs=out_spec, out_shape=out_shape, compiler_params=params,
            interpret=interpret)(x, x, x, band)
    raise ValueError(f"unknown variant {variant!r}")
