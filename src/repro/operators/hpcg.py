"""HPCG's operator: the 27-point stencil on a 3-D grid.

The High Performance Conjugate Gradients benchmark (Dongarra, Heroux,
Luszczek; reference code ``GenerateProblem``) discretizes a 3-D elliptic
problem with a 27-point stencil: diagonal 26, each of the 26 neighbours
-1, zero Dirichlet boundary (neighbours outside the domain are dropped).
The operator is SPD.  ``A u = 27 u - box(u)``, where ``box`` sums the
3 x 3 x 3 neighbourhood of every point (the point itself included); the
box sum is separable, three 3-point sums along the three axes.

Works on numpy and JAX arrays alike (pad/slice arithmetic only), like
``poisson2d``.
"""
from __future__ import annotations

from typing import Any

import numpy as np

from ..core.linop import LinearOperator

Array = Any


def _box_sum(g: Array, xp=np) -> Array:
    """Sum of each point's 3 x 3 x 3 neighbourhood (itself included) on a
    3-D field ``g``, with zeros outside the grid."""
    for axis in range(3):
        n = g.shape[axis]
        p = xp.pad(g, [(1, 1) if a == axis else (0, 0) for a in range(3)])

        def part(lo, p=p, axis=axis, n=n):
            return p[tuple(slice(lo, lo + n) if a == axis else slice(None)
                           for a in range(3))]

        g = part(0) + part(1) + part(2)
    return g


def hpcg27(nx: int, ny: int | None = None,
           nz: int | None = None) -> LinearOperator:
    """HPCG's 27-point operator on an ``nx x ny x nz`` grid (cube by
    default).  Carries the hint ``stencil27=(nx, ny, nz)``, from which
    ``repro.core.precond.Multigrid`` builds its V-cycle."""
    ny = nx if ny is None else ny
    nz = nx if nz is None else nz
    grid = (int(nx), int(ny), int(nz))
    n = nx * ny * nz

    def matvec(u):
        if isinstance(u, np.ndarray):
            xp = np
        else:
            import jax.numpy as xp
        g = u.reshape(grid)
        return (27.0 * g - _box_sum(g, xp)).reshape(u.shape)

    return LinearOperator(matvec=matvec, n=n, diag=np.full(n, 26.0),
                          name=f"hpcg27-{nx}x{ny}x{nz}", stencil27=grid)
