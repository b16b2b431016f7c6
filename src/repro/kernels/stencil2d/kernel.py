"""Pallas TPU kernel for the batched 2D star stencil (paper §III-B on TPU).

CGRA→TPU mapping:
  * the paper's **mandatory buffering** (2·ry rows live on-fabric while the x
    sweep streams) = the row-halo views held in VMEM for the life of a tile;
  * **strip-mining/blocking** (§III-B "Blocking") = the (block_y, block_x)
    BlockSpec tiling chosen by ops.plan_2d_blocks under the VMEM budget;
  * x-chains and y-chains = two unrolled shift-FMA ladders sharing one VMEM
    workspace (each input element is read from HBM once per tile and feeds up
    to 2rx+2ry+1 taps — the paper's reuse bound);
  * §IV temporal fusion: T sweeps in VMEM, halo = r·T per face.  Fused star
    sweeps have diamond-shaped composite support, so the workspace is
    assembled from all 9 neighbour tiles (corners included); for T=1 the
    corner contribution is masked-zero dead weight (see §Perf for the 5-view
    variant trade-off).

Grid: (batch, nby, nbx); batch blocks are size 1.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _sweep2d(ext, cy, cx, out_h, out_w, acc_dtype):
    ry = (len(cy) - 1) // 2
    rx = (len(cx) - 1) // 2
    acc = jnp.zeros((ext.shape[0], out_h, out_w), acc_dtype)
    for a, c in enumerate(cy):
        if c != 0.0:
            acc = acc + jnp.asarray(c, acc_dtype) * ext[:, a:a + out_h, rx:rx + out_w]
    for b, c in enumerate(cx):
        if c != 0.0:
            acc = acc + jnp.asarray(c, acc_dtype) * ext[:, ry:ry + out_h, b:b + out_w]
    return acc


def _body(tl, tc, tr, ml, mc, mr, bl, bc, br, o, *, cy, cx, timesteps,
          block_y, block_x, ny, nx, out_dtype):
    jy = pl.program_id(1)
    jx = pl.program_id(2)
    ry = (len(cy) - 1) // 2
    rx = (len(cx) - 1) // 2
    hy, hx = ry * timesteps, rx * timesteps
    acc_dtype = jnp.float32

    top = jnp.concatenate([tl[:, -hy:, -hx:], tc[:, -hy:, :], tr[:, -hy:, :hx]], 2)
    mid = jnp.concatenate([ml[:, :, -hx:], mc[:, :, :], mr[:, :, :hx]], 2)
    bot = jnp.concatenate([bl[:, :hy, -hx:], bc[:, :hy, :], br[:, :hy, :hx]], 2)
    ext = jnp.concatenate([top, mid, bot], 1).astype(acc_dtype)

    rr = (jy * block_y - hy
          + jax.lax.broadcasted_iota(jnp.int32, (1, block_y + 2 * hy, 1), 1))
    cc = (jx * block_x - hx
          + jax.lax.broadcasted_iota(jnp.int32, (1, 1, block_x + 2 * hx), 2))
    ext = jnp.where((rr >= 0) & (rr < ny) & (cc >= 0) & (cc < nx), ext, 0)

    h, w = block_y + 2 * hy, block_x + 2 * hx
    for _ in range(timesteps):
        h -= 2 * ry
        w -= 2 * rx
        ext = _sweep2d(ext, cy, cx, h, w, acc_dtype)

    orr = jy * block_y + jax.lax.broadcasted_iota(jnp.int32, (1, block_y, 1), 1)
    occ = jx * block_x + jax.lax.broadcasted_iota(jnp.int32, (1, 1, block_x), 2)
    valid = ((orr >= hy) & (orr < ny - hy) & (occ >= hx) & (occ < nx - hx))
    o[:, :, :] = jnp.where(valid, ext, 0).astype(out_dtype)


@functools.partial(
    jax.jit,
    static_argnames=("cy", "cx", "timesteps", "block_y", "block_x",
                     "interpret"))
def stencil2d_pallas(x: jax.Array, cy: tuple[float, ...],
                     cx: tuple[float, ...], *, timesteps: int = 1,
                     block_y: int = 128, block_x: int = 256,
                     interpret: bool = False) -> jax.Array:
    """x: (B, ny, nx) -> (B, ny, nx). ny % block_y == 0, nx % block_x == 0,
    ry*T <= block_y, rx*T <= block_x (ops.py pads)."""
    b, ny, nx = x.shape
    ry = (len(cy) - 1) // 2
    rx = (len(cx) - 1) // 2
    if ny % block_y or nx % block_x:
        raise ValueError(f"grid {(ny, nx)} not divisible by block "
                         f"({block_y},{block_x})")
    if ry * timesteps > block_y or rx * timesteps > block_x:
        raise ValueError("halo exceeds block")
    nby, nbx = ny // block_y, nx // block_x

    def vspec(dy, dx):
        def imap(i, jy, jx):
            return (i, jnp.clip(jy + dy, 0, nby - 1), jnp.clip(jx + dx, 0, nbx - 1))
        return pl.BlockSpec((1, block_y, block_x), imap)

    views = [vspec(dy, dx) for dy in (-1, 0, 1) for dx in (-1, 0, 1)]
    body = functools.partial(
        _body, cy=cy, cx=cx, timesteps=timesteps, block_y=block_y,
        block_x=block_x, ny=ny, nx=nx, out_dtype=x.dtype)
    return pl.pallas_call(
        body, grid=(b, nby, nbx), in_specs=views,
        out_specs=pl.BlockSpec((1, block_y, block_x), lambda i, jy, jx: (i, jy, jx)),
        out_shape=jax.ShapeDtypeStruct((b, ny, nx), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        interpret=interpret)(*([x] * 9))
