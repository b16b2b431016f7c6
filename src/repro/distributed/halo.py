"""Multi-chip halo exchange for distributed stencils (DESIGN.md §3).

The paper's PE→PE producer-consumer links, lifted to ICI scale: when a stencil
grid is sharded into strips across mesh devices, each sweep only needs
``r * timesteps`` boundary elements from the two neighbour shards — a
``jax.lax.ppermute`` pair, not an all-gather.  Devices at the global edges
receive zeros from ppermute (no source), which *is* the oracle's boundary
convention — no special-casing.

Fusing T time-steps per exchange divides the number of neighbour messages by
T at the cost of wider halos and overlapped recompute: the
communication-avoiding trade the paper's §IV pipeline makes on-fabric.

All functions run *inside* ``jax.shard_map``.
"""
from __future__ import annotations

import functools
from typing import Sequence

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.spec import StencilSpec


# --------------------------------------------------------------------------
# shard_map interior: exchange + local sweeps
# --------------------------------------------------------------------------
def halo_exchange(x: jax.Array, halo: int, axis_name: str,
                  array_axis: int) -> tuple[jax.Array, jax.Array]:
    """Return (left_halo, right_halo) received from neighbours along
    ``axis_name``; zeros at the global edges."""
    n = jax.lax.psum(1, axis_name)
    fwd = [(i, i + 1) for i in range(n - 1)]      # my right edge -> right nbr
    bwd = [(i, i - 1) for i in range(1, n)]       # my left edge -> left nbr
    sl = [slice(None)] * x.ndim

    sl[array_axis] = slice(x.shape[array_axis] - halo, None)
    from_left = jax.lax.ppermute(x[tuple(sl)], axis_name, fwd)

    sl[array_axis] = slice(0, halo)
    from_right = jax.lax.ppermute(x[tuple(sl)], axis_name, bwd)
    return from_left, from_right


def _sweep_ext_1d(ext: jax.Array, coeffs: tuple[float, ...],
                  out_w: int) -> jax.Array:
    acc = jnp.zeros(ext.shape[:-1] + (out_w,), ext.dtype)
    for k, c in enumerate(coeffs):
        if c != 0.0:
            acc = acc + c * ext[..., k:k + out_w]
    return acc


def _local_stencil1d(x: jax.Array, spec: StencilSpec, axis_name: str):
    """Local shard of the fused 1D stencil with one halo exchange."""
    (r,) = spec.radii
    t = spec.timesteps
    halo = r * t
    nl = x.shape[-1]
    left, right = halo_exchange(x, halo, axis_name, array_axis=x.ndim - 1)
    ext = jnp.concatenate([left, x, right], axis=-1)
    w = nl + 2 * halo
    for _ in range(t):
        w -= 2 * r
        ext = _sweep_ext_1d(ext, spec.coeffs[0], w)
    # global boundary mask (matches reference: rim of r*t is zeroed)
    idx = jax.lax.axis_index(axis_name)
    gpos = idx * nl + jnp.arange(nl)
    n_total = jax.lax.psum(1, axis_name) * nl
    valid = (gpos >= halo) & (gpos < n_total - halo)
    return jnp.where(valid, ext, 0).astype(x.dtype)


def _local_stencil2d(x: jax.Array, spec: StencilSpec, ax_names: tuple[str, str]):
    """Local shard of the fused 2D star stencil; exchanges along both axes.

    Fused star sweeps have diamond composite support, so after exchanging
    rows we also exchange the *corner-extended* columns: exchange along y
    first, then exchange the y-extended array along x (corners ride along).
    """
    ry, rx = spec.radii
    t = spec.timesteps
    hy, hx = ry * t, rx * t
    ny_l, nx_l = x.shape[-2], x.shape[-1]
    yname, xname = ax_names

    up, down = halo_exchange(x, hy, yname, array_axis=x.ndim - 2)
    xt = jnp.concatenate([up, x, down], axis=-2)
    left, right = halo_exchange(xt, hx, xname, array_axis=x.ndim - 1)
    ext = jnp.concatenate([left, xt, right], axis=-1)

    h, w = ny_l + 2 * hy, nx_l + 2 * hx
    cy, cx = spec.coeffs
    for _ in range(t):
        h -= 2 * ry
        w -= 2 * rx
        acc = jnp.zeros(ext.shape[:-2] + (h, w), ext.dtype)
        for a, c in enumerate(cy):
            if c != 0.0:
                acc = acc + c * ext[..., a:a + h, rx:rx + w]
        for b_, c in enumerate(cx):
            if c != 0.0:
                acc = acc + c * ext[..., ry:ry + h, b_:b_ + w]
        ext = acc

    iy = jax.lax.axis_index(yname)
    ix = jax.lax.axis_index(xname)
    gy = iy * ny_l + jnp.arange(ny_l)[:, None]
    gx = ix * nx_l + jnp.arange(nx_l)[None, :]
    tot_y = jax.lax.psum(1, yname) * ny_l
    tot_x = jax.lax.psum(1, xname) * nx_l
    valid = (gy >= hy) & (gy < tot_y - hy) & (gx >= hx) & (gx < tot_x - hx)
    return jnp.where(valid, ext, 0).astype(x.dtype)


def _local_stencil3d(x: jax.Array, spec: StencilSpec,
                     ax_names: tuple[str, str]):
    """Local shard of a 3D star stencil; z over ax_names[0], y over
    ax_names[1], x unsharded (the innermost axis keeps lane locality)."""
    rz, ry, rx = spec.radii
    t = spec.timesteps
    hz, hy = rz * t, ry * t
    nz_l, ny_l = x.shape[-3], x.shape[-2]
    zname, yname = ax_names

    up, down = halo_exchange(x, hz, zname, array_axis=x.ndim - 3)
    zt = jnp.concatenate([up, x, down], axis=-3)
    left, right = halo_exchange(zt, hy, yname, array_axis=x.ndim - 2)
    ext = jnp.concatenate([left, zt, right], axis=-2)

    d, h = nz_l + 2 * hz, ny_l + 2 * hy
    w = x.shape[-1]
    cz, cy, cx = spec.coeffs
    for _ in range(t):
        d -= 2 * rz
        h -= 2 * ry
        w2 = w - 2 * rx
        acc = jnp.zeros(ext.shape[:-3] + (d, h, w2), ext.dtype)
        for a, c in enumerate(cz):
            if c != 0.0:
                acc = acc + c * ext[..., a:a + d, ry:ry + h, rx:rx + w2]
        for b_, c in enumerate(cy):
            if c != 0.0:
                acc = acc + c * ext[..., rz:rz + d, b_:b_ + h, rx:rx + w2]
        for c_, c in enumerate(cx):
            if c != 0.0:
                acc = acc + c * ext[..., rz:rz + d, ry:ry + h, c_:c_ + w2]
        # x axis is unsharded: re-pad with zeros to keep extents aligned
        acc = jnp.pad(acc, [(0, 0)] * (acc.ndim - 1) + [(rx, rx)])
        ext = acc
        w = acc.shape[-1]

    iz = jax.lax.axis_index(zname)
    iy = jax.lax.axis_index(yname)
    gz = iz * nz_l + jnp.arange(nz_l)[:, None, None]
    gy = iy * ny_l + jnp.arange(ny_l)[None, :, None]
    gx = jnp.arange(x.shape[-1])[None, None, :]
    tz = jax.lax.psum(1, zname) * nz_l
    ty = jax.lax.psum(1, yname) * ny_l
    valid = ((gz >= hz) & (gz < tz - hz) & (gy >= hy) & (gy < ty - hy) &
             (gx >= rx * t) & (gx < x.shape[-1] - rx * t))
    return jnp.where(valid, ext, 0).astype(x.dtype)


# --------------------------------------------------------------------------
# public API: mesh-level distributed stencils
# --------------------------------------------------------------------------
def distributed_stencil1d(spec: StencilSpec, mesh: Mesh, axis: str = "data"):
    """Build a jitted f(x) running the fused 1D stencil sharded into strips
    along ``axis``.  x: (N,) with N % mesh.shape[axis] == 0."""
    (n,) = spec.grid_shape
    shards = mesh.shape[axis]
    assert n % shards == 0, (n, shards)
    assert n // shards >= spec.radii[0] * spec.timesteps, \
        "shard smaller than halo; reduce timesteps or shards"
    pspec = P(axis)

    fn = jax.shard_map(
        functools.partial(_local_stencil1d, spec=spec, axis_name=axis),
        mesh=mesh, in_specs=pspec, out_specs=pspec)
    return jax.jit(fn, in_shardings=NamedSharding(mesh, pspec),
                   out_shardings=NamedSharding(mesh, pspec))


def distributed_stencil2d(spec: StencilSpec, mesh: Mesh,
                          axes: tuple[str, str] = ("pod", "data")):
    """Fused 2D stencil sharded (y over axes[0], x over axes[1])."""
    ny, nx = spec.grid_shape
    sy, sx = mesh.shape[axes[0]], mesh.shape[axes[1]]
    assert ny % sy == 0 and nx % sx == 0
    assert ny // sy >= spec.radii[0] * spec.timesteps
    assert nx // sx >= spec.radii[1] * spec.timesteps
    pspec = P(axes[0], axes[1])

    fn = jax.shard_map(
        functools.partial(_local_stencil2d, spec=spec, ax_names=axes),
        mesh=mesh, in_specs=pspec, out_specs=pspec)
    return jax.jit(fn, in_shardings=NamedSharding(mesh, pspec),
                   out_shardings=NamedSharding(mesh, pspec))


def distributed_stencil3d(spec: StencilSpec, mesh: Mesh,
                          axes: tuple[str, str] = ("pod", "data")):
    """Fused 3D star stencil sharded (z over axes[0], y over axes[1])."""
    nz, ny, nx = spec.grid_shape
    sz, sy = mesh.shape[axes[0]], mesh.shape[axes[1]]
    assert nz % sz == 0 and ny % sy == 0
    assert nz // sz >= spec.radii[0] * spec.timesteps
    assert ny // sy >= spec.radii[1] * spec.timesteps
    pspec = P(axes[0], axes[1], None)

    fn = jax.shard_map(
        functools.partial(_local_stencil3d, spec=spec, ax_names=axes),
        mesh=mesh, in_specs=pspec, out_specs=pspec)
    return jax.jit(fn, in_shardings=NamedSharding(mesh, pspec),
                   out_shardings=NamedSharding(mesh, pspec))


def halo_bytes_per_step(spec: StencilSpec, shards: Sequence[int]) -> int:
    """Collective traffic of one fused exchange (for §Roofline accounting)."""
    b = spec.bytes_per_elem
    total = 0
    for ax, (n, r, s) in enumerate(zip(spec.grid_shape, spec.radii, shards)):
        if s <= 1:
            continue
        other = 1
        for a2, n2 in enumerate(spec.grid_shape):
            if a2 != ax:
                other *= n2
        total += 2 * (s - 1) * r * spec.timesteps * other * b
    return total
