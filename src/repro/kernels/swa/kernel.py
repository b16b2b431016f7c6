"""Pallas TPU kernel: causal sliding-window attention (flash-style online
softmax), with the paper's stencil reuse discipline on the MXU.

Mapping rationale (DESIGN.md §4): local attention is a sequence stencil —
every query block's support is a fixed-width band of KV blocks behind it.
As in the stencil kernels, each KV block is DMA'd into VMEM once per query
band and reused by the whole (bq x bk) tile on the MXU; boundary handling is
the same position-predicate filtering the paper implements with filter PEs.

Grid: (B*Hq, num_q_blocks, num_window_blocks); the window dimension is the
innermost (sequential) axis carrying the online-softmax recurrence in VMEM
scratch.  KV block index = q_block - (nw-1) + wi, clamped; contributions from
negative (non-existent) desired blocks are skipped with ``pl.when``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _body(qref, kref, vref, oref, mref, lref, accref, *, bq, bk, nw, window,
          seq, scale, out_dtype):
    qi = pl.program_id(1)
    wi = pl.program_id(2)

    @pl.when(wi == 0)
    def _init():
        mref[:, :] = jnp.full_like(mref[:, :], NEG_INF)
        lref[:, :] = jnp.zeros_like(lref[:, :])
        accref[:, :] = jnp.zeros_like(accref[:, :])

    desired = qi - (nw - 1) + wi

    @pl.when(desired >= 0)
    def _compute():
        q = qref[0, 0, :, :].astype(jnp.float32) * scale      # (bq, D)
        k = kref[0, 0, :, :].astype(jnp.float32)              # (bk, D)
        v = vref[0, 0, :, :].astype(jnp.float32)              # (bk, D)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        qpos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kpos = desired * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = (kpos <= qpos) & (kpos > qpos - window) & (kpos < seq)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = mref[:, :]                                   # (bq, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)          # (bq, bk)
        alpha = jnp.exp(m_prev - m_new)                       # (bq, 1)
        lref[:, :] = lref[:, :] * alpha + jnp.sum(p, axis=1, keepdims=True)
        accref[:, :] = accref[:, :] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        mref[:, :] = m_new

    @pl.when(wi == nw - 1)
    def _finish():
        l = jnp.maximum(lref[:, :], 1e-30)
        oref[0, 0, :, :] = (accref[:, :] / l).astype(out_dtype)


@functools.partial(
    jax.jit,
    static_argnames=("window", "block_q", "block_k", "interpret"))
def swa_pallas(q: jax.Array, k: jax.Array, v: jax.Array, *, window: int,
               block_q: int = 128, block_k: int = 128,
               interpret: bool = False) -> jax.Array:
    """q: (B, Hq, S, D); k, v: (B, Hkv, S, D). S % block_q == 0 required
    (ops.py pads); block_q == block_k for static index math."""
    b, hq, s, d = q.shape
    hkv = k.shape[1]
    assert hq % hkv == 0, "GQA needs Hq % Hkv == 0"
    group = hq // hkv
    assert block_q == block_k, "kv-block walk assumes block_q == block_k"
    assert s % block_q == 0
    nq = s // block_q
    nw = (window - 1 + block_q - 1) // block_q + 1   # kv blocks per window
    nw = min(nw, nq)
    scale = 1.0 / (d ** 0.5)

    def qmap(bh, qi, wi):
        return (bh // hq, bh % hq, qi, 0)

    def kvmap(bh, qi, wi):
        blk = jnp.clip(qi - (nw - 1) + wi, 0, nq - 1)
        return (bh // hq, (bh % hq) // group, blk, 0)

    body = functools.partial(
        _body, bq=block_q, bk=block_k, nw=nw, window=window, seq=s,
        scale=scale, out_dtype=q.dtype)
    return pl.pallas_call(
        body,
        grid=(b * hq, nq, nw),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d), qmap),
            pl.BlockSpec((1, 1, block_k, d), kvmap),
            pl.BlockSpec((1, 1, block_k, d), kvmap),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, d), qmap),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret, name="swa")(q, k, v)
