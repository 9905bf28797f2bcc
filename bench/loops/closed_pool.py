"""Closed loop, one client that keeps the queue full: ``batch``
right-hand sides submitted to a ``SolverPool(max_batch=batch,
pad_to=(batch,))``, one flush, and every ``SolveHandle.result()``
collected before the next ``batch``; only the ``batch``-lane shape is
compiled."""
from jax.profiler import TraceAnnotation

SPANS = ("submit", "flush", "result")


def setup(cell):
    from repro.core import SolverPool
    return SolverPool(cell.solver, max_batch=cell.batch,
                      pad_to=(cell.batch,))


def step(cell, ring, k):
    idx = [(k * cell.batch + j) % len(ring) for j in range(cell.batch)]
    with TraceAnnotation("submit"):
        handles = [cell.state.submit(ring[i]) for i in idx]
    with TraceAnnotation("flush"):
        cell.state.flush()
    with TraceAnnotation("result"):
        out = [(i, h.result()) for i, h in zip(idx, handles)]
    return out
