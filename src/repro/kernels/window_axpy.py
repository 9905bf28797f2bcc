"""Pallas TPU kernel: fused sliding-window AXPY -- the (K4) v-recurrence.

Computes  v_new = (z - sum_k g[k] * V[:, k]) / gcc  (paper Alg. 2 line 17)
in a single pass over the **lane-major** window ``V (n, m)`` (the m-entry
band of one grid point is contiguous): every chunk of the m window vectors
is read once and combined in VMEM, instead of m separate AXPY sweeps
(m reads + m-1 writes of the accumulator).  HBM traffic drops from
~(2m+1)n to (m+2)n words.

Accumulation dtype is ``promote_types(dtype, float32)`` (f64 in, f64
accumulated) so the kernel tier stays bit-comparable to the inline jnp
math on the x64 solver paths.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .blocks import row_block


def _kernel(acc, v_ref, z_ref, g_ref, o_ref):
    V = v_ref[...].astype(acc)                    # (bn, m)
    z = z_ref[...].astype(acc)                    # (bn, 1)
    g = g_ref[...].astype(acc)                    # (1, m+1); g[0, m] = gcc
    out = z - (V * g[:, :-1]).sum(axis=1, keepdims=True)
    o_ref[...] = (out / g[:, -1:]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bn", "interpret"))
def window_axpy(V, z, g, gcc, *, bn: int = 2048,
                interpret: bool | None = None):
    """v_new (n,) = (z - V @ g) / gcc ; lane-major V (n, m), g (m,)."""
    n, m = V.shape
    bn = row_block(n, bn)         # a partial last block writes only rows < n
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    acc = jnp.promote_types(V.dtype, jnp.float32)
    gfull = jnp.concatenate([g.astype(acc),
                             jnp.asarray([gcc], acc)]).reshape(1, m + 1)
    out = pl.pallas_call(
        functools.partial(_kernel, acc),
        grid=(pl.cdiv(n, bn),),
        in_specs=[
            pl.BlockSpec((bn, m), lambda i: (i, 0)),
            pl.BlockSpec((bn, 1), lambda i: (i, 0)),
            pl.BlockSpec((1, m + 1), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((bn, 1), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, 1), V.dtype),
        interpret=interpret,
    )(V, z.reshape(n, 1), gfull)
    return out[:, 0]
