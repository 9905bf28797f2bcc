"""Plain reference of the unscaled 5-point 2-D Poisson operator.

Diagonal 4, neighbours -1, homogeneous Dirichlet boundary, on an
``nx x ny`` grid (arXiv:1801.04728 Sec. 5).  Written with numpy slicing
on a zero-padded field; the same code runs on ``jax.numpy`` arrays, which
is how the benchmark makes its right-hand sides on the device.  Nothing
here comes from the system under test.
"""
from __future__ import annotations

import numpy as np


def apply(u, xp=np):
    """``A u`` for a field ``u`` of shape ``(..., nx, ny)``."""
    pad = [(0, 0)] * (u.ndim - 2) + [(1, 1), (1, 1)]
    p = xp.pad(u, pad)
    return (4 * u - p[..., :-2, 1:-1] - p[..., 2:, 1:-1]
            - p[..., 1:-1, :-2] - p[..., 1:-1, 2:])


def true_rel_residual(b, x, grid) -> float:
    """float64 ``||b - A x|| / ||b||`` of one solution on ``grid``."""
    b64 = np.asarray(b, np.float64).reshape(grid)
    x64 = np.asarray(x, np.float64).reshape(grid)
    return float(np.linalg.norm(b64 - apply(x64)) / np.linalg.norm(b64))
