"""Plain reference for the benchmark's stencils, independent of the program.

A star stencil: each output point is the sum over axes and taps of
``coeffs[axis][k] * x[... i_axis + k - r_axis ...]``, with zeros read outside
the grid.  After each of ``timesteps`` sweeps the rim of ``r * (t + 1)``
points per face is set to zero, so only outputs with full support remain.
Nothing here imports the program under test.
"""
from __future__ import annotations

import concurrent.futures as cf
import math
import os

import ml_dtypes
import numpy as np

#: the dtype each configured precision's control computes in: the nearest
#: precision below it that a later change could be tempted to use
CONTROL_DTYPE = {"float64": np.float32, "float32": ml_dtypes.bfloat16}


def _sweep_rows(x: np.ndarray, coeffs, radii, lo: int, hi: int,
                dtype, axis: int = 0) -> np.ndarray:
    """Planes [lo, hi) along ``axis`` of one unmasked sweep of ``x``, zeros
    read outside ``x``."""
    n0 = x.shape[axis]
    shape = list(x.shape)
    shape[axis] = hi - lo
    out = np.zeros(shape, dtype)
    for ax, (r, cs) in enumerate(zip(radii, coeffs)):
        for k, c in enumerate(cs):
            if c == 0.0:
                continue
            c = dtype(c)
            off = k - r
            dst = [slice(None)] * x.ndim
            src = [slice(None)] * x.ndim
            if ax == axis:
                a, b = max(lo + off, 0), min(hi + off, n0)
                if a >= b:
                    continue
                src[ax], dst[ax] = slice(a, b), slice(a - off - lo,
                                                      b - off - lo)
                out[tuple(dst)] += c * x[tuple(src)].astype(dtype, copy=False)
                continue
            src[axis] = slice(lo, hi)
            blk = x[tuple(src)].astype(dtype, copy=False)
            n = x.shape[ax]
            src[axis] = slice(None)
            if off > 0:
                src[ax], dst[ax] = slice(off, n), slice(0, n - off)
            else:
                src[ax], dst[ax] = slice(0, n + off), slice(-off, n)
            out[tuple(dst)] += c * blk[tuple(src)]
    return out


def _rim_mask(shape, radii, depth: int, lo: int, hi: int,
              axis: int = 0) -> np.ndarray:
    """True where planes [lo, hi) along ``axis`` of ``shape`` lie
    ``r * depth`` or more from every face."""
    size = list(shape)
    size[axis] = hi - lo
    mask = np.ones(size, bool)
    for ax, r in enumerate(radii):
        h = r * depth
        if h == 0:
            continue
        idx = np.arange(lo, hi) if ax == axis else np.arange(shape[ax])
        ok = (idx >= h) & (idx < shape[ax] - h)
        mask &= np.expand_dims(ok, tuple(i for i in range(len(shape))
                                         if i != ax))
    return mask


def default_threads() -> int:
    """Threads for a reference computed in blocks: one a core, at most 16."""
    return min(16, len(os.sched_getaffinity(0)))


def star_stencil(x: np.ndarray, coeffs, timesteps: int = 1, *,
                 dtype=np.float64, threads: int | None = None,
                 rows_per_block: int | None = None,
                 visit=None) -> np.ndarray | None:
    """``timesteps`` masked sweeps of the star stencil over all of ``x``,
    computed in ``dtype`` in blocks of rows on ``threads`` threads (by
    default one a core, at most 16).

    With ``visit``, the last sweep is not stored: ``visit(lo, hi, rows)``
    gets each block of rows [lo, hi) of the result instead (from several
    threads), so a whole grid can be compared without a second copy."""
    radii = tuple((len(c) - 1) // 2 for c in coeffs)
    if threads is None:
        threads = default_threads()
    cur = np.asarray(x)
    n0 = cur.shape[0]
    if rows_per_block is None:
        row = math.prod(cur.shape[1:]) or 1
        rows_per_block = max(1, (1 << 22) // row)
    blocks = [(a, min(a + rows_per_block, n0))
              for a in range(0, n0, rows_per_block)]
    for t in range(timesteps):
        last = t == timesteps - 1
        nxt = None if (last and visit) else np.empty(cur.shape, dtype)

        def run(lohi, src=cur, dst=nxt, depth=t + 1):
            lo, hi = lohi
            acc = _sweep_rows(src, coeffs, radii, lo, hi, dtype)
            rows = np.where(_rim_mask(src.shape, radii, depth, lo, hi),
                            acc, dtype(0))
            if dst is None:
                visit(lo, hi, rows)
            else:
                dst[lo:hi] = rows

        with cf.ThreadPoolExecutor(max_workers=threads) as pool:
            list(pool.map(run, blocks))
        cur = nxt
    return cur


def star_stencil_plane(slab: np.ndarray, coeffs, timesteps: int, *,
                       axis: int, lo: int, plane: int, grid,
                       dtype=np.float64) -> np.ndarray:
    """Plane ``plane`` along ``axis`` of ``timesteps`` masked sweeps of the
    star stencil over a whole grid of shape ``grid``, from ``slab``: the
    grid's input planes [lo, lo + slab.shape[axis]) along ``axis``, every
    other axis whole.  The slab must hold every input plane within
    ``r * timesteps`` of ``plane`` that lies in the grid.  The zero rim is
    the grid's, not the slab's, so a slab inside the grid is interior.
    Each output is summed as ``star_stencil`` sums it, to the same bits."""
    radii = tuple((len(c) - 1) // 2 for c in coeffs)
    cur, cur_lo = slab, lo
    for t in range(timesteps):
        reach = radii[axis] * (timesteps - 1 - t)
        a = max(plane - reach, cur_lo)
        b = min(plane + reach + 1, cur_lo + cur.shape[axis])
        acc = _sweep_rows(cur, coeffs, radii, a - cur_lo, b - cur_lo, dtype,
                          axis)
        cur = np.where(_rim_mask(grid, radii, t + 1, a, b, axis), acc,
                       dtype(0))
        cur_lo = a
    return np.take(cur, plane - cur_lo, axis=axis)


def rounding_bound(coeffs, timesteps: int, x_absmax: float,
                   dtype: str) -> float:
    """Largest |result in ``dtype`` - exact result| a star stencil can show.

    One sweep sums the n nonzero taps.  With unit roundoff u, rounding each
    coefficient, each product and each of the n-1 additions adds at most
    u * S * M (S = sum |c_k|, M = max |x|), so a sweep errs by at most
    (n + 1) u S M; T sweeps by at most T (n + 1) u S^T M.  Taking eps for u
    leaves a factor 2 of headroom for the order a compiler picks."""
    taps = sum(1 for axis in coeffs for c in axis if c != 0.0)
    s = sum(abs(c) for axis in coeffs for c in axis)
    eps = float(np.finfo(np.dtype(dtype)).eps)
    return timesteps * (taps + 1) * eps * s ** timesteps * x_absmax


def _needed_inputs(grid, radii, timesteps: int) -> np.ndarray:
    """True at the input sites that some output with full support after
    ``timesteps`` sweeps depends on: that interior, widened along each axis
    by its radius once a sweep."""
    need = np.zeros(grid, bool)
    need[tuple(slice(r * timesteps, n - r * timesteps)
               for n, r in zip(grid, radii))] = True
    for _ in range(timesteps):
        wide = need.copy()
        for ax, r in enumerate(radii):
            n = grid[ax]
            for off in range(1, r + 1):
                lo = [slice(None)] * len(grid)
                hi = [slice(None)] * len(grid)
                lo[ax], hi[ax] = slice(0, n - off), slice(off, n)
                wide[tuple(lo)] |= need[tuple(hi)]
                wide[tuple(hi)] |= need[tuple(lo)]
        need = wide
    return need


def cgra_lane_bounds(grid, radii, workers: int, timesteps: int,
                     itemsize: int, machine: dict) -> dict:
    """What one simulated run of the paper's worker pipeline (Sec. III,
    one pass of ``timesteps`` fused sweeps over the whole grid) must
    count, from the mapping's shape alone.

    ``workers`` readers stream the grid interleaved in row-major order
    (reader k loads the flat sites k, k + w, ...), each site at most once;
    each of the ``workers`` compute workers holds, per fused sweep, one
    filter and one multiply(-add) per tap and an adder joining the axes'
    chains; the pipeline's ends hold two address generators, the load, the
    store and the sync of each worker, and one compare.  Every PE fires at
    most once a cycle, and the memory port moves ``bw / clock`` bytes a
    cycle.  So:

    * ``pes`` is exact;
    * ``loads`` lies between the loads each reader must make to reach the
      last site its class needs, and the whole grid;
    * ``cycles`` is at least the slowest reader's needed loads plus the
      store after them, and the memory port's and the MACs' least time.
      A ceiling holds where nothing stalls a reader: the port moves a load
      and a store of every worker each cycle, and an output's outer-axis
      taps, ``r`` rows on, arrive later than its innermost chain of
      ``2r + 1`` taps takes to fill.  Then ``cycles`` is at most the
      slowest reader's whole stream plus one cycle for each PE on the
      longest path from a load to the compare.  Elsewhere (a 1D grid has no
      outer axis) no ceiling is claimed: ``None``.
    """
    d, w, t = len(grid), workers, timesteps
    taps = 1 + 2 * sum(radii)
    n = math.prod(grid)
    stores = math.prod(max(0, g - 2 * r * t) for g, r in zip(grid, radii))
    flops = (2 * taps - 1) * sum(
        math.prod(max(0, g - 2 * r * (k + 1)) for g, r in zip(grid, radii))
        for k in range(t))
    flat = np.flatnonzero(_needed_inputs(tuple(grid), radii, t).ravel())
    need = [0] * w
    for k in range(w):
        cls = flat[flat % w == k]
        if cls.size:
            need[k] = int(cls[-1] - k) // w + 1
    port = machine["bw_gbps"] / machine["clock_ghz"]          # bytes a cycle
    macs = 2 * machine["num_macs"]                             # flops a cycle
    lo = max(max(need) + 1,
             math.ceil((sum(need) + stores) * itemsize / port),
             math.ceil(flops / macs))
    hi = None
    lag = radii[0] * math.prod(grid[1:]) // w if d > 1 else 0
    if 2 * w * itemsize <= port and lag >= 2 * radii[-1] + 1:
        depth = 2 + t * (1 + taps + d - 1) + 4      # addr, load .. store, sync
        hi = -(-n // w) + depth
    return {"pes": w * (5 + t * (2 * taps + d - 1)) + 1,
            "loads": (sum(need), n), "cycles": (lo, hi)}


def pareto_front(points: list[tuple]) -> set:
    """The objective vectors (all minimised) that no other vector dominates."""
    pts = set(points)
    return {p for p in pts
            if not any(q != p and all(a <= b for a, b in zip(q, p))
                       for q in pts)}
