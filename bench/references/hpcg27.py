"""Plain reference of HPCG's 27-point operator.

Diagonal 26, each of the 26 neighbours -1, zero Dirichlet boundary
(neighbours outside the domain are dropped), on an ``nx x ny x nz`` grid
(HPCG reference code, ``GenerateProblem``).  Written with numpy slicing
on a zero-padded field; the same code runs on ``jax.numpy`` arrays,
which is how the benchmark makes its right-hand sides on the device.
Nothing here comes from the system under test.
"""
from __future__ import annotations

import itertools

import numpy as np


def apply(u, xp=np):
    """``A u`` for a field ``u`` of shape ``(..., nx, ny, nz)``."""
    nx, ny, nz = u.shape[-3:]
    p = xp.pad(u, [(0, 0)] * (u.ndim - 3) + [(1, 1)] * 3)
    out = 26 * u
    for dx, dy, dz in itertools.product((0, 1, 2), repeat=3):
        if (dx, dy, dz) != (1, 1, 1):
            out = out - p[..., dx:dx + nx, dy:dy + ny, dz:dz + nz]
    return out


def true_rel_residual(b, x, grid) -> float:
    """float64 ``||b - A x|| / ||b||`` of one solution on ``grid``."""
    b64 = np.asarray(b, np.float64).reshape(grid)
    x64 = np.asarray(x, np.float64).reshape(grid)
    return float(np.linalg.norm(b64 - apply(x64)) / np.linalg.norm(b64))
