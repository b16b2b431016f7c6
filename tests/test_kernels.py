"""Per-kernel shape/dtype sweeps: Pallas (interpret=True) vs the pure-jnp
oracles (assignment deliverable (c))."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.conv1d.ops import causal_conv1d
from repro.kernels.conv1d.ref import conv1d_ref
from repro.kernels.stencil1d.ops import (VMEM_BUDGET_BYTES, plan_1d_blocks,
                                         stencil1d)
from repro.kernels.stencil1d.ref import stencil1d_ref
from repro.kernels.stencil2d.ops import stencil2d
from repro.kernels.stencil2d.ref import stencil2d_ref
from repro.kernels.stencil3d.ops import stencil3d
from repro.kernels.stencil3d.ref import stencil3d_ref
from repro.kernels.swa.ops import sliding_window_attention
from repro.kernels.swa.ref import swa_ref, swa_ref_chunked

TOL = {"float32": 2e-5, "bfloat16": 3e-2}


def _mk(rng, shape, dtype):
    return jnp.asarray(rng.normal(size=shape), dtype)


# --------------------------------------------------------------------------
@pytest.mark.parametrize("b,n,r,t,variant,dtype", [
    (4, 256, 1, 1, "vpu", "float32"),
    (4, 256, 2, 1, "mxu", "float32"),
    (2, 384, 8, 1, "vpu", "float32"),
    (2, 384, 3, 2, "vpu", "float32"),
    (2, 384, 3, 2, "mxu", "float32"),
    (1, 200, 1, 3, "vpu", "float32"),
    (3, 1000, 5, 2, "vpu", "float32"),
    (2, 256, 2, 1, "vpu", "bfloat16"),
    (2, 256, 2, 2, "mxu", "bfloat16"),
])
def test_stencil1d_sweep(rng, b, n, r, t, variant, dtype):
    coeffs = tuple((rng.normal(size=2 * r + 1) / (2 * r + 1)).tolist())
    x = _mk(rng, (b, n), dtype)
    y = stencil1d(x, coeffs, timesteps=t, backend="pallas", variant=variant,
                  block=(min(b, 8), 128))
    yr = stencil1d_ref(x, coeffs, timesteps=t)
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(yr, np.float32), atol=TOL[dtype])


@pytest.mark.parametrize("shape", [(5000,), (2, 1000), (2, 3, 384)])
def test_stencil1d_entry_is_one_dispatch(rng, shape):
    """Reshape, pad, kernel and slice are one jitted program: a call
    enqueues it once and runs nothing eagerly around it, and inside an
    outer jit it still gives the oracle's answer."""
    coeffs = (0.1, 0.2, 0.4, 0.2, 0.1)

    def fn(x):
        return stencil1d(x, coeffs, timesteps=2, backend="pallas")

    x = _mk(rng, shape, "float32")
    jaxpr = jax.make_jaxpr(fn)(x)
    assert [e.primitive.name for e in jaxpr.eqns] == ["jit"]
    assert jaxpr.out_avals[0].shape == shape
    np.testing.assert_allclose(np.asarray(jax.jit(fn)(x)),
                               np.asarray(stencil1d_ref(x, coeffs,
                                                        timesteps=2)),
                               atol=TOL["float32"])


def test_stencil1d_block_planner():
    bb, bn = plan_1d_blocks(n=194400, batch=1, radius=8, timesteps=4)
    assert bn % 128 == 0 and bn >= 8 * 4
    # the mxu variant's (bn+2h) x (bn+2h-2r) f32 band must fit the budget
    _, bn_mxu = plan_1d_blocks(n=194400, batch=1, radius=8, timesteps=4,
                               variant="mxu")
    w = bn_mxu + 2 * 32
    assert bn_mxu < bn and w * (w - 16) * 4 <= VMEM_BUDGET_BYTES


# --------------------------------------------------------------------------
@pytest.mark.parametrize("b,ny,nx,ry,rx,t,dtype", [
    (1, 64, 128, 1, 1, 1, "float32"),
    (2, 64, 128, 2, 3, 1, "float32"),
    (1, 48, 96, 1, 1, 2, "float32"),
    (1, 72, 160, 2, 2, 3, "float32"),
    (2, 40, 140, 3, 1, 1, "float32"),
    (1, 64, 128, 1, 1, 2, "bfloat16"),
    (2, 45, 200, 2, 2, 2, "float32"),       # padded on both axes
])
def test_stencil2d_sweep(rng, b, ny, nx, ry, rx, t, dtype):
    cy = tuple((rng.normal(size=2 * ry + 1) / (2 * ry + 1)).tolist())
    cx = rng.normal(size=2 * rx + 1) / (2 * rx + 1)
    cx[rx] = 0.0
    x = _mk(rng, (b, ny, nx), dtype)
    y = stencil2d(x, cy, tuple(cx), timesteps=t, backend="pallas",
                  block=(8, 128))
    yr = stencil2d_ref(x, cy, tuple(cx), timesteps=t)
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(yr, np.float32), atol=TOL[dtype])


@pytest.mark.parametrize("shape", [(45, 200), (2, 45, 200), (2, 3, 64, 128)])
def test_stencil2d_entry_is_one_dispatch(shape):
    """Reshape, pad, kernel and slice are one jitted program: a call
    enqueues it once and runs nothing eagerly around it."""
    x = jnp.zeros(shape, jnp.float32)
    jaxpr = jax.make_jaxpr(lambda x: stencil2d(
        x, (0.1, 0.8, 0.1), (0.2, 0.0, 0.2), timesteps=2, backend="pallas",
        block=(8, 128)))(x)
    assert [e.primitive.name for e in jaxpr.eqns] == ["jit"]
    assert jaxpr.out_avals[0].shape == shape


# --------------------------------------------------------------------------
@pytest.mark.parametrize("b,hq,hkv,s,d,w,blk,dtype", [
    (1, 4, 4, 256, 32, 64, 64, "float32"),
    (2, 8, 2, 256, 64, 128, 64, "float32"),
    (1, 2, 1, 300, 32, 100, 64, "float32"),      # padded S
    (1, 4, 4, 512, 32, 512, 128, "float32"),     # full-causal window
    (2, 6, 3, 128, 16, 1, 64, "float32"),        # self-only window
    (1, 4, 2, 256, 32, 96, 64, "bfloat16"),
])
def test_swa_sweep(rng, b, hq, hkv, s, d, w, blk, dtype):
    q = _mk(rng, (b, hq, s, d), dtype)
    k = _mk(rng, (b, hkv, s, d), dtype)
    v = _mk(rng, (b, hkv, s, d), dtype)
    y = sliding_window_attention(q, k, v, window=w, backend="pallas",
                                 block=blk)
    yr = swa_ref(q, k, v, window=w)
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(yr, np.float32), atol=TOL[dtype])


@pytest.mark.parametrize("s,w", [(256, 64), (300, 100), (128, 128), (200, 48)])
def test_swa_chunked_equals_dense(rng, s, w):
    q = _mk(rng, (2, 4, s, 32), "float32")
    k = _mk(rng, (2, 2, s, 32), "float32")
    v = _mk(rng, (2, 2, s, 32), "float32")
    np.testing.assert_allclose(
        np.asarray(swa_ref_chunked(q, k, v, window=w)),
        np.asarray(swa_ref(q, k, v, window=w)), atol=2e-5)


# --------------------------------------------------------------------------
@pytest.mark.parametrize("b,s,c,k,dtype", [
    (2, 128, 64, 4, "float32"),
    (1, 100, 48, 7, "float32"),
    (3, 256, 128, 2, "float32"),
    (1, 64, 16, 16, "float32"),
    (2, 128, 64, 4, "bfloat16"),
])
def test_conv1d_sweep(rng, b, s, c, k, dtype):
    x = _mk(rng, (b, s, c), dtype)
    w = _mk(rng, (k, c), dtype)
    bias = _mk(rng, (c,), dtype)
    y = causal_conv1d(x, w, bias, backend="pallas", block_s=64, block_c=32)
    yr = conv1d_ref(x, w, bias)
    # bf16: unit-normal taps x inputs -> |y| up to ~4; one bf16 quantum at
    # that magnitude is 0.03, and kernel/ref round at different points.
    atol = 8e-2 if dtype == "bfloat16" else TOL[dtype]
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(yr, np.float32), atol=atol)


def test_kernels_grad_through_xla_path(rng):
    """The XLA paths are the ones used inside jitted training — they must be
    differentiable."""
    x = _mk(rng, (2, 64), "float32")
    g = jax.grad(lambda a: jnp.sum(stencil1d(a, (0.25, 0.5, 0.25),
                                             backend="xla") ** 2))(x)
    assert np.isfinite(np.asarray(g)).all()


# --------------------------------------------------------------------------
@pytest.mark.parametrize("b,nz,ny,nx,rz,ry,rx,t,dtype", [
    (1, 16, 16, 128, 1, 1, 1, 1, "float32"),
    (2, 16, 32, 128, 2, 1, 3, 1, "float32"),
    (1, 24, 16, 128, 1, 2, 1, 2, "float32"),
    (1, 16, 16, 128, 1, 1, 1, 1, "bfloat16"),
    (1, 20, 20, 200, 1, 1, 1, 1, "float32"),    # padded on every axis
    (1, 20, 20, 200, 1, 1, 1, 2, "float32"),
    (1, 20, 20, 200, 1, 2, 1, 3, "float32"),
    (2, 20, 20, 200, 2, 1, 1, 2, "float32"),
])
def test_stencil3d_sweep(rng, b, nz, ny, nx, rz, ry, rx, t, dtype):
    cz = tuple((rng.normal(size=2 * rz + 1) / (2 * rz + 1)).tolist())
    cy = rng.normal(size=2 * ry + 1) / (2 * ry + 1)
    cy[ry] = 0.0
    cx = rng.normal(size=2 * rx + 1) / (2 * rx + 1)
    cx[rx] = 0.0
    x = _mk(rng, (b, nz, ny, nx), dtype)
    y = stencil3d(x, cz, tuple(cy), tuple(cx), timesteps=t,
                  backend="pallas", block=(8, 16, 128))
    yr = stencil3d_ref(x, cz, tuple(cy), tuple(cx), timesteps=t)
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(yr, np.float32), atol=TOL[dtype])


@pytest.mark.parametrize("shape", [(20, 20, 200), (2, 20, 20, 200)])
def test_stencil3d_entry_is_one_dispatch(rng, shape):
    """Reshape, pad, the T kernel calls and slice are one jitted program: a
    call enqueues it once and runs nothing eagerly around it, and inside an
    outer jit it still gives the oracle's answer."""
    cz, cy, cx = (0.1, 0.4, 0.1), (0.1, 0.0, 0.1), (0.1, 0.0, 0.1)

    def fn(x):
        return stencil3d(x, cz, cy, cx, timesteps=2, backend="pallas",
                         block=(8, 16, 128))

    x = _mk(rng, shape, "float32")
    jaxpr = jax.make_jaxpr(fn)(x)
    assert [e.primitive.name for e in jaxpr.eqns] == ["jit"]
    assert jaxpr.out_avals[0].shape == shape
    np.testing.assert_allclose(np.asarray(jax.jit(fn)(x)),
                               np.asarray(stencil3d_ref(x, cz, cy, cx,
                                                        timesteps=2)),
                               atol=TOL["float32"])
