"""HBM words that one p(l)-CG iteration preconditioned by HPCG's V-cycle
has to stream, from its shapes (``hpcg256.set50``).

The count follows ``bench/roofline.py``: what the algorithm must stream,
whatever implements it, so a share against it stays at or under 100% as
long as each vector is read from HBM once where the algorithm reads it.
It leaves out the O(l^2) scalar recurrences and the solution / search
direction updates.  Per point of the fine grid (n points), a word each:

* the Krylov body, ``(6l + 7) + 7``: the unpreconditioned body's windows,
  SPMV input and output and ``t`` read back (``bench/roofline.py``),
  plus the ``zhat`` window of 3 read and written back and ``t_hat`` read
  back by its recurrence;
* the V-cycle, on each level ``k`` of ``n / 8^k`` points:
  - a symmetric Gauss-Seidel sweep is two passes over the grid, each
    reading ``r`` and ``x`` and writing ``x`` (3), except a first pass
    from ``x = 0`` (2): pre-smoothing 5, post-smoothing 6;
  - the residual at the coarse points and its injection: ``x`` read
    whole for the stencil, ``r`` read and ``r_c`` written at the coarse
    points (1 + 2/8);
  - the prolongation: ``x`` and ``x_c`` read and ``x`` written at the
    coarse points (3/8);
  - the coarsest level only smooths once from zero (5).

At four levels that is ``12.625 * (1 + 1/8 + 1/64) + 5/512 = 14.41``
words a point for the V-cycle, and ``6l + 14 + 14.41`` in all: 46.41 at
l = 3.
"""
from __future__ import annotations

import math

from bench.roofline import words_per_iter as krylov_words

#: words a point of one level streams: pre-smoothing (2 + 3), post-
#: smoothing (3 + 3), residual with injection (1 + 2/8), prolongation (3/8)
LEVEL_WORDS = 5 + 6 + 1.25 + 0.375
#: the coarsest level: one sweep from zero
COARSEST_WORDS = 5


def vcycle_words(n: int, levels: int = 4) -> float:
    """Words one V-cycle on ``n`` fine points streams."""
    return (sum(LEVEL_WORDS * n / 8 ** k for k in range(levels - 1))
            + COARSEST_WORDS * n / 8 ** (levels - 1))


def words_per_iter(l: int, n: int, levels: int = 4) -> float:
    """Words one preconditioned iteration streams on ``n`` points."""
    return krylov_words(l, n) + 7 * n + vcycle_words(n, levels)


def bytes_per_iter(l: int, grid, word_bytes: int, levels: int = 4) -> float:
    return words_per_iter(l, math.prod(grid), levels) * word_bytes
