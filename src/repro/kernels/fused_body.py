"""Pallas TPU megakernel: the fused p(l)-CG iteration body.

One launch per iteration computes everything in the scan body that touches
an n-vector (paper arXiv:1801.04728 Alg. 3):

* **(K1)** the 5-point stencil SPMV ``t_hat = A z_i`` -- fused in-kernel
  when the operator is the paper's 2-D Poisson stencil (``stencil_hw``
  given); otherwise ``t`` (and ``t_hat``) stream in as inputs.  A
  *diagonal* preconditioner apply ``t = M^{-1} t_hat`` (the ``inv_diag``
  hint of ``repro.core.precond.Jacobi``) also runs in-kernel -- a scalar
  inverse diagonal rides the packed scalar operand, a vector one streams
  as an ``(n, 1)`` operand -- so preconditioned p(l)-CG keeps ONE launch
  per steady-state iteration;
* **(K4)** the sliding-window AXPY recurrences: the new basis vector
  ``v_c = (z_{c-l} - sum_k g_k v_{c-2l+k}) / g_cc``, the new auxiliary
  vector ``z_{i+1} = (t - gamma z_i - delta z_{i-1}) / delta'`` (and the
  ``zhat`` recurrence when preconditioned), including the warmup-phase
  variant ``z_{i+1} = t - sigma_i z_i`` selected in-kernel on the
  ``steady`` flag;
* **(K5)** the 2l+1 dot products of the next reduction payload, computed
  against the *updated* windows while they are still resident in VMEM.

Windows are **lane-major** ``(n, window)``: the 2l+1-entry band of one
grid point is contiguous, each basis vector is read from HBM exactly once
per iteration, and under ``vmap`` (the batched multi-RHS engine) the
batching rule appends a grid dimension so a ``(B, n, window)`` batch is
still ONE launch.  Per iteration the kernel replaces one launch each for
the SPMV, the v-AXPY, and two multi-dots (plus their intermediate HBM
round-trips) with a single pass: traffic drops from ~(10l+9)n to (6l+7)n
words and launch count from 4+ to 1.

Scalar recurrences (K2/K3/K6) stay in jnp: they are O(l^2) latency-bound
work that would only force the kernel shape dynamic.

All math runs in ``promote_types(dtype, float32)`` -- f64 solver paths
(x64, interpret mode) keep full precision so ``backend="fused"`` is
bit-comparable to the inline jnp body.

Grid: 1-D over row-blocks of n, each a multiple of 8 rows
(``blocks.row_block``); a partial last block is masked out of the dots.
With the stencil fused, the SPMV runs in the same ``(bs, 1)`` column
layout as the windows (no lane-to-sublane relayout, which Mosaic
refuses): the neighbours of grid point g sit at g +- 1 and g +- W rows,
the block spans whole halo blocks of ``hb = roundup(W, 8)`` rows, and the
W rows above and below come from the previous and next halo blocks of
``Zw`` itself.  Boundary masks follow from the global row index (first
and last grid row, first and last grid column).  The dot payload
accumulates across grid steps into a revisited output block -- the
canonical Pallas reduction pattern.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .blocks import SUBLANES, block_rows, row_block

#: scal layout: [steady, s_warm, gam, dlt, dsub, gcc, invd, g_0 .. g_{2l-1}]
#: (invd is the scalar inverse diagonal for diag="scalar", else unused)
N_FIXED_SCALARS = 7


def _make_kernel(l: int, has_zh: bool, stencil, diag: str, n: int, bs: int,
                 acc):
    """``stencil`` is None or ``(W, hb)``: grid width and halo rows."""
    m = 2 * l + 1
    has_diag = diag != "none"
    has_stencil = stencil is not None

    def kernel(*refs):
        it = iter(refs)
        scal_ref = next(it)
        v_ref = next(it)
        z_ref = next(it)
        zh_ref = next(it) if has_zh else None
        invd_ref = next(it) if diag == "vector" else None
        if has_stencil:
            zp_ref, zn_ref = next(it), next(it)     # halo blocks of Zw
        elif has_diag:
            th_ref = next(it)                   # t computed in-kernel
        else:
            t_ref = next(it)
            th_ref = next(it) if has_zh else None
        vo_ref = next(it)
        zo_ref = next(it)
        zho_ref = next(it) if has_zh else None
        d_ref = next(it)

        i = pl.program_id(0)
        g_row = block_rows(i, bs)
        scal = scal_ref[...].astype(acc)            # (1, 7 + 2l)
        steady = scal[0, 0] > 0.5
        s_warm, gam, dlt = scal[0, 1], scal[0, 2], scal[0, 3]
        dsub, gcc = scal[0, 4], scal[0, 5]
        g = scal[:, N_FIXED_SCALARS:]               # (1, 2l)

        V = v_ref[...].astype(acc)                  # (bs, 2l+1)
        Z = z_ref[...].astype(acc)                  # (bs, l+1)

        # ---- (K1) SPMV: in-kernel 5-point stencil or streamed t --------
        if has_stencil:
            W, hb = stencil
            zc = Z[:, :1]                           # (bs, 1) = z_i
            zp = zp_ref[:, :1].astype(acc)          # (hb, 1) rows above
            zn = zn_ref[:, :1].astype(acc)          # (hb, 1) rows below
            up = jnp.concatenate([zp[hb - W:], zc[:bs - W]], axis=0)
            down = jnp.concatenate([zc[W:], zn[:W]], axis=0)
            left = jnp.concatenate([zp[hb - 1:], zc[:-1]], axis=0)
            right = jnp.concatenate([zc[1:], zn[:1]], axis=0)
            col = jax.lax.rem(g_row, jnp.int32(W))
            zero = jnp.zeros_like(zc)               # Dirichlet boundary
            up = jnp.where(g_row >= W, up, zero)
            down = jnp.where(g_row < n - W, down, zero)
            left = jnp.where(col != 0, left, zero)
            right = jnp.where(col != W - 1, right, zero)
            traw = 4.0 * zc - up - down - left - right
            # the SPMV stream is storage-dtype under the precision
            # policy: round the in-kernel result exactly like the
            # streamed-t tiers store it (identity when storage is the
            # accumulation dtype)
            traw = traw.astype(zo_ref.dtype).astype(acc)
        elif has_diag:
            traw = th_ref[...].astype(acc)          # (bs, 1)
        if has_diag:
            # in-kernel diagonal preconditioner: t = M^{-1} t_hat
            # (the preconditioned stream is storage-dtype too)
            th = traw
            iv = (scal[0, 6] if diag == "scalar"
                  else invd_ref[...].astype(acc))
            t = (iv * traw).astype(zo_ref.dtype).astype(acc)
        elif has_stencil:
            t = th = traw
        else:
            t = t_ref[...].astype(acc)              # (bs, 1)
            th = th_ref[...].astype(acc) if has_zh else t

        # ---- (K4) v recurrence (steady only; warmup keeps the window) --
        vnew = (Z[:, l - 1:l]
                - (V[:, :2 * l] * g).sum(axis=1, keepdims=True)) / gcc
        V2 = jnp.where(steady, jnp.concatenate([vnew, V[:, :-1]], axis=1),
                       V)
        # ---- (K4) z recurrence with in-kernel warmup select ------------
        znew = jnp.where(steady,
                         (t - gam * Z[:, :1] - dsub * Z[:, 1:2]) / dlt,
                         t - s_warm * Z[:, :1])
        Z2 = jnp.concatenate([znew, Z[:, :-1]], axis=1)
        lhs = znew
        if has_zh:
            Zh = zh_ref[...].astype(acc)            # (bs, 3)
            zhnew = jnp.where(
                steady, (th - gam * Zh[:, :1] - dsub * Zh[:, 1:2]) / dlt,
                th - s_warm * Zh[:, :1])
            zho_ref[...] = jnp.concatenate(
                [zhnew, Zh[:, :-1]], axis=1).astype(zho_ref.dtype)
            lhs = zhnew
        vo_ref[...] = V2.astype(vo_ref.dtype)
        zo_ref[...] = Z2.astype(zo_ref.dtype)

        # ---- (K5) payload dots against the updated windows -------------
        # dot the windows AS STORED: under a low-precision storage dtype
        # the Gram payload must describe the basis later iterations read
        # back (and match the per-kernel tier, which dots the rounded
        # windows); identity casts when storage == accumulation dtype
        V2s = V2.astype(vo_ref.dtype).astype(acc)
        Z2s = Z2.astype(zo_ref.dtype).astype(acc)
        # rows past n (a partial last block) hold unspecified values
        ok = g_row < n
        vd = jnp.where(ok, V2s[:, :l + 1] * lhs, 0.0).sum(axis=0)  # (l+1,)
        zd = jnp.where(ok, Z2s[:, :l] * lhs, 0.0).sum(axis=0)      # (l,)

        @pl.when(i == 0)
        def _init():
            d_ref[...] = jnp.zeros_like(d_ref)

        d_ref[...] += jnp.concatenate([vd, zd]).reshape(1, m)

    return kernel


@functools.partial(jax.jit,
                   static_argnames=("l", "stencil_hw", "diag", "bn",
                                    "interpret"))
def fused_body(Vw, Zw, scal, Zhw=None, t=None, t_hat=None, invd=None, *,
               l: int, stencil_hw=None, diag: str = "none", bn: int = 2048,
               interpret: bool | None = None):
    """One fused p(l)-CG body step on lane-major windows.

    Args:
      Vw: (n, 2l+1) basis window, slot 0 newest.
      Zw: (n, l+1) auxiliary window, slot 0 newest.
      scal: (1, 7+2l) packed scalars
        ``[steady, s_warm, gam, dlt, dsub, gcc, invd, g...]`` (the invd
        slot carries the scalar inverse diagonal for ``diag="scalar"``).
      Zhw: (n, 3) zhat window (preconditioned runs) or None.
      t: (n,) preconditioned SPMV result; None computes it in-kernel
        (from the fused 5-point stencil and/or the diagonal apply).
      t_hat: (n,) unpreconditioned SPMV result (required with ``Zhw``
        unless the stencil is fused in-kernel).
      invd: (n, 1) inverse diagonal operand for ``diag="vector"``.
      stencil_hw: (H, W) 2-D grid shape of the Poisson domain; set =>
        the (K1) SPMV runs in-kernel.
      diag: "none" | "scalar" | "vector" -- in-kernel diagonal
        preconditioner mode (requires ``Zhw``).
      bn: target row-block size (a multiple of 8, or n when n <= bn; with
        the stencil fused, a whole number of ``roundup(W, 8)``-row halo
        blocks).  Any n compiles: the last block may be partial.

    Returns:
      (Vw2, Zw2, Zhw2 | None, dots) with ``dots`` the (2l+1,) payload
      ``[vd_0..vd_l, zd_0..zd_{l-1}]`` in the accumulation dtype.
    """
    n, m = Vw.shape
    if m != 2 * l + 1:
        raise ValueError(f"Vw must be (n, 2l+1), got {Vw.shape} for l={l}")
    has_zh = Zhw is not None
    has_stencil = stencil_hw is not None
    has_diag = diag != "none"
    if has_diag and not has_zh:
        raise ValueError("in-kernel diag preconditioner needs the Zhw "
                         "window")
    if has_stencil and has_zh and not has_diag:
        raise ValueError("in-kernel SPMV with a preconditioner requires "
                         "the diag mode (general prec => stream t/t_hat)")
    if has_stencil or has_diag:
        if t is not None:
            raise ValueError("t is computed in-kernel with the stencil/"
                             "diag fused; pass t=None")
    elif t is None:
        raise ValueError("with nothing fused in-kernel (no stencil_hw, "
                         "diag='none') the streamed t operand is required")
    if has_diag and not has_stencil and t_hat is None:
        raise ValueError("the in-kernel diag apply needs the streamed "
                         "t_hat operand when the stencil is not fused")
    if has_stencil:
        H, W2d = stencil_hw
        if H * W2d != n:
            raise ValueError(f"stencil_hw {stencil_hw} != n={n}")
        if H < 2:
            raise ValueError(f"the fused stencil needs >= 2 grid rows, got "
                             f"stencil_hw={stencil_hw}")
        hb = -(-W2d // SUBLANES) * SUBLANES     # halo block >= one grid row
        hb = n if hb >= n else hb
        k = max(bn // hb, 1)
        bs = n if k * hb >= n else k * hb
        nhalo = pl.cdiv(n, hb)
    else:
        bs = row_block(n, bn)
    nblocks = pl.cdiv(n, bs)
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    acc = jnp.promote_types(Vw.dtype, jnp.float32)
    ns = scal.shape[-1]

    row = lambda i: (i, 0)          # noqa: E731
    fix = lambda i: (0, 0)          # noqa: E731
    in_specs = [pl.BlockSpec((1, ns), fix),
                pl.BlockSpec((bs, m), row),
                pl.BlockSpec((bs, l + 1), row)]
    operands = [scal, Vw, Zw]
    if has_zh:
        in_specs.append(pl.BlockSpec((bs, 3), row))
        operands.append(Zhw)
    if diag == "vector":
        in_specs.append(pl.BlockSpec((bs, 1), row))
        operands.append(invd.reshape(n, 1))
    if has_stencil:
        # the hb rows just above / below block i, read from Zw itself
        in_specs += [
            pl.BlockSpec((hb, l + 1),
                         lambda i: (jnp.maximum(i * k - 1, 0), 0)),
            pl.BlockSpec((hb, l + 1),
                         lambda i: (jnp.minimum((i + 1) * k, nhalo - 1), 0)),
        ]
        operands += [Zw, Zw]
    elif has_diag:
        in_specs.append(pl.BlockSpec((bs, 1), row))
        operands.append(t_hat.reshape(n, 1))
    else:
        in_specs.append(pl.BlockSpec((bs, 1), row))
        operands.append(t.reshape(n, 1))
        if has_zh:
            in_specs.append(pl.BlockSpec((bs, 1), row))
            operands.append(t_hat.reshape(n, 1))

    out_specs = [pl.BlockSpec((bs, m), row),
                 pl.BlockSpec((bs, l + 1), row)]
    out_shape = [jax.ShapeDtypeStruct((n, m), Vw.dtype),
                 jax.ShapeDtypeStruct((n, l + 1), Zw.dtype)]
    if has_zh:
        out_specs.append(pl.BlockSpec((bs, 3), row))
        out_shape.append(jax.ShapeDtypeStruct((n, 3), Zhw.dtype))
    out_specs.append(pl.BlockSpec((1, m), fix))
    out_shape.append(jax.ShapeDtypeStruct((1, m), acc))

    outs = pl.pallas_call(
        _make_kernel(l, has_zh, (W2d, hb) if has_stencil else None, diag,
                     n, bs, acc),
        grid=(nblocks,),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
    )(*operands)
    Vw2, Zw2 = outs[0], outs[1]
    Zhw2 = outs[2] if has_zh else None
    return Vw2, Zw2, Zhw2, outs[-1][0]
