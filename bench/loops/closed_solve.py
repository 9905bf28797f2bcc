"""Closed loop, one client: one right-hand side per prepared
``Solver.solve``, waiting for ``x`` before the next; the right-hand sides
cycle through the ring."""
import jax
from jax.profiler import TraceAnnotation

#: host spans of one step; the traced window is their extent
SPANS = ("solve",)


def setup(cell):
    return None


def step(cell, ring, k):
    i = k % len(ring)
    with TraceAnnotation("solve"):
        r = cell.solver.solve(ring[i])
        jax.block_until_ready(r.x)
    return [(i, r)]
