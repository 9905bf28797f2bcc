"""Plain float64 reference of HPCG's solve, independent of ``src/``.

HPCG (Dongarra, Heroux, Luszczek; reference code ``GenerateProblem``,
``ComputeSYMGS``, ``ComputeMG``, ``CG``):

* the operator is the 27-point stencil, diagonal 26 and each of the 26
  neighbours -1, with zero Dirichlet boundary;
* the preconditioner is a V-cycle: one symmetric Gauss-Seidel sweep
  before and one after the coarse correction on each level, one sweep on
  the coarsest; restriction injects the fine residual at the points
  ``(2i, 2j, 2k)`` and prolongation adds the coarse correction there; the
  coarse operator is the same stencil on the coarse grid;
* the sweep here is the multicoloured one the system under test uses
  (8 colours by the parity of (i, j, k), forward 7..0, backward 0..7),
  where HPCG's reference sweeps lexicographically.

Everything is numpy on the natural ``(nx, ny, nz)`` layout, with strided
views and in-place updates: no code is shared with the program.
"""
from __future__ import annotations

import itertools

import numpy as np

OFFSETS = [d for d in itertools.product((-1, 0, 1), repeat=3) if d != (0, 0, 0)]


def neighbour_sum(x: np.ndarray) -> np.ndarray:
    """Sum of the 26 neighbours of every point, zero outside the grid."""
    p = np.pad(x, 1)
    nx, ny, nz = x.shape
    out = np.zeros_like(x)
    for dx, dy, dz in OFFSETS:
        out += p[1 + dx:1 + dx + nx, 1 + dy:1 + dy + ny, 1 + dz:1 + dz + nz]
    return out


def apply(x: np.ndarray) -> np.ndarray:
    """``A x`` on a field of shape ``(nx, ny, nz)``."""
    return 26.0 * x - neighbour_sum(x)


COLOURS = [(a, b, c) for a in (0, 1) for b in (0, 1) for c in (0, 1)]


def neighbour_sum_at(x: np.ndarray, a: int, b: int, c: int) -> np.ndarray:
    """:func:`neighbour_sum` at the points whose (i, j, k) have parities
    (a, b, c) only."""
    p = np.pad(x, 1)
    nx, ny, nz = x.shape
    out = 0.0
    for dx, dy, dz in OFFSETS:
        out = out + p[1 + a + dx:1 + dx + nx:2, 1 + b + dy:1 + dy + ny:2,
                      1 + c + dz:1 + dz + nz:2]
    return out


def symgs(r: np.ndarray, x: np.ndarray) -> np.ndarray:
    """One symmetric Gauss-Seidel sweep of ``A x = r`` from ``x``: each
    colour in turn (colour ``4a + 2b + c`` holds the points whose
    (i, j, k) have parities (a, b, c)), 7..0 then 0..7, set to
    ``(r + neighbours) / 26``.  The coarse points (colour 0) are not the
    last updated, which would zero the residual injected from them."""
    x = x.copy()
    for col in list(range(7, -1, -1)) + list(range(8)):
        a, b, c = COLOURS[col]
        x[a::2, b::2, c::2] = (r[a::2, b::2, c::2]
                               + neighbour_sum_at(x, a, b, c)) / 26.0
    return x


def vcycle(r: np.ndarray, levels: int = 4) -> np.ndarray:
    """HPCG's ``ComputeMG``: ``M^{-1} r`` on the grid of ``r``."""
    x = symgs(r, np.zeros_like(r))
    if levels == 1:
        return x
    rc = (r - apply(x))[::2, ::2, ::2]
    xc = vcycle(rc, levels - 1)
    x[::2, ::2, ::2] += xc
    return symgs(r, x)


def pcg(b: np.ndarray, iters: int, levels: int = 4):
    """Preconditioned CG from ``x = 0`` for exactly ``iters`` iterations
    (HPCG's timed set); returns ``x`` and the true relative residual
    after each iteration."""
    x = np.zeros_like(b)
    r = b.copy()
    z = vcycle(r, levels)
    p = z.copy()
    rz = np.vdot(r, z)
    bn = np.linalg.norm(b)
    hist = []
    for _ in range(iters):
        ap = apply(p)
        alpha = rz / np.vdot(p, ap)
        x += alpha * p
        r -= alpha * ap
        hist.append(float(np.linalg.norm(b - apply(x)) / bn))
        z = vcycle(r, levels)
        rz_new = np.vdot(r, z)
        p = z + (rz_new / rz) * p
        rz = rz_new
    return x, hist
