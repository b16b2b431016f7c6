"""Pallas TPU kernel: depthwise causal conv1d (one-sided sequence stencil).

Same halo-view mapping as kernels/stencil1d, specialized:
  * taps are *learned per-channel* weights — passed as an operand (the paper's
    "constant input" to each MAC PE becomes a VMEM-resident (K, C) tile);
  * one-sided (causal) halo: only the previous sequence block is viewed;
  * channel axis rides the 128-lane dimension, sequence the sublane dimension
    — each loaded (bs, bc) tile is reused by all K taps from VMEM.

Grid: (B, num_seq_blocks, num_channel_blocks).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _body(prev, cur, wref, o, *, kk, block_s, out_dtype):
    si = pl.program_id(1)
    halo = kk - 1
    acc_dtype = jnp.float32
    ext = jnp.concatenate([prev[0, -halo:, :], cur[0, :, :]], 0).astype(acc_dtype)
    # causal zero-fill: positions before the sequence start
    pos = si * block_s - halo + jax.lax.broadcasted_iota(
        jnp.int32, (block_s + halo, 1), 0)
    ext = jnp.where(pos >= 0, ext, 0)
    acc = jnp.zeros((block_s, ext.shape[1]), acc_dtype)
    for k in range(kk):
        acc = acc + ext[k:k + block_s, :] * wref[k, :][None, :].astype(acc_dtype)
    o[0, :, :] = acc.astype(out_dtype)


@functools.partial(jax.jit,
                   static_argnames=("block_s", "block_c", "interpret"))
def conv1d_pallas(x: jax.Array, w: jax.Array, *, block_s: int = 256,
                  block_c: int = 128, interpret: bool = False) -> jax.Array:
    """x: (B, S, C); w: (K, C). S % block_s == 0, C % block_c == 0,
    K - 1 <= block_s (ops.py pads)."""
    b, s, c = x.shape
    kk = w.shape[0]
    assert s % block_s == 0 and c % block_c == 0 and kk - 1 <= block_s
    ns, nc = s // block_s, c // block_c

    xspec_prev = pl.BlockSpec(
        (1, block_s, block_c),
        lambda i, si, ci: (i, jnp.maximum(si - 1, 0), ci))
    xspec_cur = pl.BlockSpec((1, block_s, block_c),
                             lambda i, si, ci: (i, si, ci))
    wspec = pl.BlockSpec((kk, block_c), lambda i, si, ci: (0, ci))
    body = functools.partial(_body, kk=kk, block_s=block_s, out_dtype=x.dtype)
    return pl.pallas_call(
        body, grid=(b, ns, nc),
        in_specs=[xspec_prev, xspec_cur, wspec],
        out_specs=pl.BlockSpec((1, block_s, block_c),
                               lambda i, si, ci: (i, si, ci)),
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel")),
        interpret=interpret, name="conv1d")(x, x, w)
