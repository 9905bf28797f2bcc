"""Pallas TPU kernel: 5-point stencil SPMV on a local 2-D subdomain.

This is kernel (K1) of the p(l)-CG iteration (paper Alg. 3): the local part
of ``y = A x`` for the unscaled Poisson stencil (diag 4, neighbors -1), with
halo rows/columns received from the 4 mesh neighbors (repro.distributed
performs the ``ppermute`` exchange; the kernel is purely local).

TPU mapping: the grid tiles the local block over rows; each step holds a
(bh, W) tile in VMEM plus its row-neighbors, so vertical neighbor access
never leaves VMEM.  A tile spans the whole local width W (any W, e.g. the
500- or 875-wide blocks of the paper's grids on a 2x2 mesh), and bh is a
multiple of 8 sized to a VMEM budget (``blocks.row_block``); when bh does
not divide H the last tile is partial, and the south halo is applied by
global row index, not by tile position.

``stencil2d_batched`` is the multi-RHS variant: the B lanes of a
``(B, H, W)`` batch ride the leading block axis (the same lane-leading
layout as the ``(B, n, window)`` batched scan-engine kernels), so the
local SPMV over ALL right-hand sides is ONE ``pallas_call`` whose grid
still only tiles rows -- each grid step streams a ``(B, bh, W)`` brick.
``repro.kernels.ops`` installs it as the ``jax.vmap`` rule of the
single-lane kernel (``custom_vmap``), which is how the mesh engine's
``shard_map(vmap(plcg_scan))`` path lowers its halo SPMV to one launch.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .blocks import block_rows, row_block

#: bytes of one (bh, W) f32 tile (lanes padded to 128): 4 such tiles,
#: double-buffered, plus the shifted temporaries stay inside the default
#: scoped VMEM of a v5e core
TILE_BYTES = 512 * 1024


def _tile_rows(H: int, W: int, bh: int, lanes: int = 1) -> int:
    """Row-block height: at most ``bh`` and the VMEM tile budget."""
    padded_w = -(-W // 128) * 128
    budget = max(TILE_BYTES // (4 * padded_w * lanes), 1)
    return row_block(H, min(bh, budget))


def _kernel(H, bh, xp_ref, xc_ref, xn_ref, hn_ref, hs_ref, hw_ref, he_ref,
            o_ref):
    i = pl.program_id(0)
    acc = jnp.promote_types(xc_ref.dtype, jnp.float32)
    xc = xc_ref[...].astype(acc)
    top_halo = jnp.where(i == 0, hn_ref[...].astype(acc),
                         xp_ref[-1:, :].astype(acc))
    up = jnp.concatenate([top_halo, xc[:-1]], axis=0)
    down = jnp.concatenate([xc[1:], xn_ref[:1, :].astype(acc)], axis=0)
    down = jnp.where(block_rows(i, bh) == H - 1, hs_ref[...].astype(acc),
                     down)
    left = jnp.concatenate([hw_ref[...].astype(acc), xc[:, :-1]], axis=1)
    right = jnp.concatenate([xc[:, 1:], he_ref[...].astype(acc)], axis=1)
    o_ref[...] = (4.0 * xc - up - down - left - right).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bh", "interpret"))
def stencil2d(x, halo_n, halo_s, halo_w, halo_e, *, bh: int = 256,
              interpret: bool | None = None):
    """y = A_local x with Dirichlet halos.

    x: (H, W) local block; halo_n/halo_s: (W,); halo_w/halo_e: (H,).
    """
    H, W = x.shape
    bh = _tile_rows(H, W, bh)
    nblocks = pl.cdiv(H, bh)
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    dtype = x.dtype
    hn = halo_n.reshape(1, W).astype(dtype)
    hs = halo_s.reshape(1, W).astype(dtype)
    hw = halo_w.reshape(H, 1).astype(dtype)
    he = halo_e.reshape(H, 1).astype(dtype)
    kernel = functools.partial(_kernel, H, bh)
    return pl.pallas_call(
        kernel,
        grid=(nblocks,),
        in_specs=[
            pl.BlockSpec((bh, W), lambda i: (jnp.maximum(i - 1, 0), 0)),
            pl.BlockSpec((bh, W), lambda i: (i, 0)),
            pl.BlockSpec((bh, W), lambda i: (jnp.minimum(i + 1, nblocks - 1), 0)),
            pl.BlockSpec((1, W), lambda i: (0, 0)),
            pl.BlockSpec((1, W), lambda i: (0, 0)),
            pl.BlockSpec((bh, 1), lambda i: (i, 0)),
            pl.BlockSpec((bh, 1), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((bh, W), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((H, W), dtype),
        interpret=interpret,
    )(x, x, x, hn, hs, hw, he)


def _kernel_batched(H, bh, xp_ref, xc_ref, xn_ref, hn_ref, hs_ref, hw_ref,
                    he_ref, o_ref):
    i = pl.program_id(0)
    acc = jnp.promote_types(xc_ref.dtype, jnp.float32)
    xc = xc_ref[...].astype(acc)                            # (B, bh, W)
    top_halo = jnp.where(i == 0, hn_ref[...].astype(acc),
                         xp_ref[:, -1:, :].astype(acc))
    up = jnp.concatenate([top_halo, xc[:, :-1, :]], axis=1)
    down = jnp.concatenate([xc[:, 1:, :], xn_ref[:, :1, :].astype(acc)],
                           axis=1)
    row = i * bh + jax.lax.broadcasted_iota(jnp.int32, (1, bh, 1), 1)
    down = jnp.where(row == H - 1, hs_ref[...].astype(acc), down)
    left = jnp.concatenate([hw_ref[...].astype(acc), xc[:, :, :-1]], axis=2)
    right = jnp.concatenate([xc[:, :, 1:], he_ref[...].astype(acc)], axis=2)
    o_ref[...] = (4.0 * xc - up - down - left - right).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bh", "interpret"))
def stencil2d_batched(x, halo_n, halo_s, halo_w, halo_e, *, bh: int = 256,
                      interpret: bool | None = None):
    """y = A_local x for all B lanes in ONE launch.

    x: (B, H, W) lane-leading local batch; halo_n/halo_s: (B, W);
    halo_w/halo_e: (B, H).  Grid and VMEM tiling are identical to the
    single-lane kernel -- lanes only widen each block to (B, bh, W).
    """
    B, H, W = x.shape
    bh = _tile_rows(H, W, bh, lanes=B)
    nblocks = pl.cdiv(H, bh)
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    dtype = x.dtype
    hn = halo_n.reshape(B, 1, W).astype(dtype)
    hs = halo_s.reshape(B, 1, W).astype(dtype)
    hw = halo_w.reshape(B, H, 1).astype(dtype)
    he = halo_e.reshape(B, H, 1).astype(dtype)
    kernel = functools.partial(_kernel_batched, H, bh)
    return pl.pallas_call(
        kernel,
        grid=(nblocks,),
        in_specs=[
            pl.BlockSpec((B, bh, W), lambda i: (0, jnp.maximum(i - 1, 0), 0)),
            pl.BlockSpec((B, bh, W), lambda i: (0, i, 0)),
            pl.BlockSpec((B, bh, W),
                         lambda i: (0, jnp.minimum(i + 1, nblocks - 1), 0)),
            pl.BlockSpec((B, 1, W), lambda i: (0, 0, 0)),
            pl.BlockSpec((B, 1, W), lambda i: (0, 0, 0)),
            pl.BlockSpec((B, bh, 1), lambda i: (0, i, 0)),
            pl.BlockSpec((B, bh, 1), lambda i: (0, i, 0)),
        ],
        out_specs=pl.BlockSpec((B, bh, W), lambda i: (0, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, W), dtype),
        interpret=interpret,
    )(x, x, x, hn, hs, hw, he)
