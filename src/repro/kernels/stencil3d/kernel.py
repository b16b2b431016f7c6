"""Pallas TPU kernel: 3D star stencil, single sweep per call.

Star stencils at T=1 only need *face* neighbours, so the VMEM workspace is
assembled from 7 views (centre ± one block per axis) instead of the 27-view
full halo — the 3D generalization of the paper's line-buffer discipline:
a (bz + 2rz, by + 2ry, bx + 2rx) *cross-shaped* region is resident per tile
and every input element loaded from HBM feeds up to 2(rz+ry+rx)+1 taps.

Fused T>1 needs corner halos (diamond composite support); ops.py runs T
separate sweeps instead and documents the HBM-roundtrip trade (the §IV
fusion analysis in core/temporal still applies to the CGRA/1D/2D paths).
Each sweep masks its own boundary: reads past the grid's true extent are
zero and outputs within the sweep's rim are written as zero, so no pass
outside the kernel masks the grid.

Grid: (batch, nbz, nby, nbx) with batch blocks of 1.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _body(c, zm, zp, ym, yp, xm, xp, o, *, cz, cy, cx, bz, by, bx,
          nz, ny, nx, hz, hy, hx, out_dtype):
    jz, jy, jx = pl.program_id(1), pl.program_id(2), pl.program_id(3)
    rz, ry, rx = ((len(cc) - 1) // 2 for cc in (cz, cy, cx))
    f32 = jnp.float32
    ctr = c[0].astype(f32)                           # (bz, by, bx)

    def gpos(j, b, n, axis, extent, halo):
        base = j * b - halo
        io = jax.lax.broadcasted_iota(jnp.int32, extent, axis)
        return base + io

    acc = jnp.zeros((bz, by, bx), f32)
    # z-axis taps: band (bz + 2rz, by, bx) from zm/c/zp
    zext = jnp.concatenate([zm[0, -rz:].astype(f32), ctr,
                            zp[0, :rz].astype(f32)], 0)
    zpos = gpos(jz, bz, nz, 0, (bz + 2 * rz, 1, 1), rz)
    zext = jnp.where((zpos >= 0) & (zpos < nz), zext, 0)
    for k, cc in enumerate(cz):
        if cc != 0.0:
            acc = acc + cc * zext[k:k + bz]
    # y-axis taps
    yext = jnp.concatenate([ym[0, :, -ry:].astype(f32), ctr,
                            yp[0, :, :ry].astype(f32)], 1)
    ypos = gpos(jy, by, ny, 1, (1, by + 2 * ry, 1), ry)
    yext = jnp.where((ypos >= 0) & (ypos < ny), yext, 0)
    for k, cc in enumerate(cy):
        if cc != 0.0:
            acc = acc + cc * yext[:, k:k + by]
    # x-axis taps
    xext = jnp.concatenate([xm[0, :, :, -rx:].astype(f32), ctr,
                            xp[0, :, :, :rx].astype(f32)], 2)
    xpos = gpos(jx, bx, nx, 2, (1, 1, bx + 2 * rx), rx)
    xext = jnp.where((xpos >= 0) & (xpos < nx), xext, 0)
    for k, cc in enumerate(cx):
        if cc != 0.0:
            acc = acc + cc * xext[:, :, k:k + bx]

    oz = gpos(jz, bz, nz, 0, (bz, 1, 1), 0)
    oy = gpos(jy, by, ny, 1, (1, by, 1), 0)
    ox = gpos(jx, bx, nx, 2, (1, 1, bx), 0)
    valid = ((oz >= hz) & (oz < nz - hz) & (oy >= hy) & (oy < ny - hy) &
             (ox >= hx) & (ox < nx - hx))
    o[0] = jnp.where(valid, acc, 0).astype(out_dtype)


@functools.partial(
    jax.jit, static_argnames=("cz", "cy", "cx", "block", "extent", "rim",
                              "interpret"))
def stencil3d_pallas(x: jax.Array, cz: tuple[float, ...],
                     cy: tuple[float, ...], cx: tuple[float, ...], *,
                     block: tuple[int, int, int] = (8, 16, 128),
                     extent: tuple[int, int, int],
                     rim: tuple[int, int, int],
                     interpret: bool = False) -> jax.Array:
    """x: (B, nz, ny, nx) -> same shape; one star sweep.

    ``extent`` is the grid's true (nz, ny, nx): x holds it at its origin.
    Inputs outside it read as zero, and outputs outside ``[rim, extent -
    rim)`` on each axis are written as zero (sweep t of T has rim r·t), so
    this is the only boundary mask."""
    b, nz, ny, nx = x.shape
    bz, by, bx = block
    assert nz % bz == 0 and ny % by == 0 and nx % bx == 0
    rz, ry, rx = ((len(c) - 1) // 2 for c in (cz, cy, cx))
    assert rz <= bz and ry <= by and rx <= bx
    nbz, nby, nbx = nz // bz, ny // by, nx // bx

    def vspec(dz, dy, dx):
        def imap(i, jz, jy, jx):
            return (i, jnp.clip(jz + dz, 0, nbz - 1),
                    jnp.clip(jy + dy, 0, nby - 1),
                    jnp.clip(jx + dx, 0, nbx - 1))
        return pl.BlockSpec((1, bz, by, bx), imap)

    views = [vspec(0, 0, 0), vspec(-1, 0, 0), vspec(1, 0, 0),
             vspec(0, -1, 0), vspec(0, 1, 0), vspec(0, 0, -1),
             vspec(0, 0, 1)]
    body = functools.partial(_body, cz=cz, cy=cy, cx=cx, bz=bz, by=by, bx=bx,
                             nz=extent[0], ny=extent[1], nx=extent[2],
                             hz=rim[0], hy=rim[1], hx=rim[2],
                             out_dtype=x.dtype)
    return pl.pallas_call(
        body, grid=(b, nbz, nby, nbx), in_specs=views,
        out_specs=pl.BlockSpec((1, bz, by, bx),
                               lambda i, jz, jy, jx: (i, jz, jy, jx)),
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary",
                                 "arbitrary")),
        interpret=interpret, name="stencil3d")(*([x] * 7))
