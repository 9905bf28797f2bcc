"""Pallas TPU kernel: fused multi-dot -- the (K5) payload of p(l)-CG.

Computes the 2l+1 dot products of one iteration, ``out[k] = <W[:, k], z>``,
in a single pass over ``z``: the window matrix W (the sliding-window basis
vectors stacked **lane-major**, shape ``(n, m)`` so the m-entry band of one
grid point is contiguous) streams through VMEM chunk-by-chunk together with
exactly one copy of z.  A naive implementation reads z once *per dot*;
fusing cuts HBM traffic from 2(2l+1)n to (2l+2)n words (beyond-paper
optimization: the paper fuses the *reduction*, we additionally fuse the
local reads).

Accumulation dtype is ``promote_types(dtype, float32)``: bf16/f32 inputs
accumulate in f32 like the TPU MXU, f64 inputs (x64 solver paths, interpret
mode) keep full f64 so the kernel tiers stay bit-comparable to the inline
jnp math.

Accumulation across grid steps revisits the same output block (sequential
TPU grid), the canonical Pallas reduction pattern.  Under ``vmap`` (the
batched multi-RHS engine) the batching rule appends one grid dimension, so
a ``(B, n, m)`` window still lowers to ONE kernel launch.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .blocks import block_rows, row_block


def _kernel(acc, n, bn, w_ref, z_ref, o_ref):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    w = w_ref[...].astype(acc)                    # (bn, m)
    z = z_ref[...].astype(acc)                    # (bn, 1)
    wz = jnp.where(block_rows(i, bn) < n, w * z, 0.0)  # partial last block
    o_ref[...] += wz.sum(axis=0, keepdims=True)


@functools.partial(jax.jit, static_argnames=("bn", "interpret"))
def multidot(W, z, *, bn: int = 2048, interpret: bool | None = None):
    """out (m,) = W.T (m, n) @ z (n,) for lane-major W (n, m), one fused
    pass, ``promote_types(dtype, f32)`` accumulation."""
    n, m = W.shape
    bn = row_block(n, bn)
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    acc = jnp.promote_types(W.dtype, jnp.float32)
    out = pl.pallas_call(
        functools.partial(_kernel, acc, n, bn),
        grid=(pl.cdiv(n, bn),),
        in_specs=[
            pl.BlockSpec((bn, m), lambda i: (i, 0)),
            pl.BlockSpec((bn, 1), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((1, m), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((1, m), acc),
        interpret=interpret,
    )(W, z.reshape(n, 1))
    return out[0]
