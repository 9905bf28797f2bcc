"""The benchmark's cells cut to a size the CPU runs in seconds, for the
tests in ``tests/bench``: the grid and the iteration cap shrink; the
traffic, the other solver settings and the limits stay as committed."""
from __future__ import annotations

from bench import harness

GRID, MAXITER = 24, 200
_RESOLVE = harness.resolve


def shrink(spec: dict) -> dict:
    spec = dict(spec)
    cfg = spec["cfg"]
    spec["cfg"] = dict(cfg, grid=[GRID, GRID],
                       solver=dict(cfg["solver"], maxiter=MAXITER))
    return spec


def small_resolve(name: str, root=harness.ROOT) -> dict:
    """``harness.resolve`` of the cell at the small size."""
    return shrink(_RESOLVE(name, root))
