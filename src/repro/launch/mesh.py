"""Production mesh construction (single-pod v5e-256 and 2-pod 512-chip).

Defined as functions (never module-level constants) so importing this
module does not touch jax device state.

``make_mesh_compat`` / ``abstract_mesh_compat`` (re-exported from
``repro.compat``) build every mesh with explicit ``Auto`` axis types and
are the only mesh constructors the rest of the repo (and the test suite)
should use.
"""
from __future__ import annotations

from ..compat import abstract_mesh_compat, make_mesh_compat  # noqa: F401


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh_compat(shape, axes)


def make_mesh_for(devices: int, model_parallel: int = 1, pods: int = 1):
    """Generic mesh helper for examples/tests on arbitrary device counts."""
    data = devices // (model_parallel * pods)
    if pods > 1:
        return make_mesh_compat((pods, data, model_parallel),
                                ("pod", "data", "model"))
    return make_mesh_compat((data, model_parallel), ("data", "model"))


def make_solver_mesh(*, multi_pod: bool = False):
    """Flat 2-D processor grid for the distributed p(l)-CG solver: the
    Poisson domain is decomposed over ("data","model") as a (16,16) (or
    (32,16) across pods) grid of subdomains.  Pass the result straight to
    ``repro.core.solve(A, b, mesh=...)``."""
    if multi_pod:
        # fold the pod axis into rows: the solver engine wants a flat
        # 2-axis grid (32 x 16 subdomains)
        return make_mesh_compat((32, 16), ("data", "model"))
    return make_production_mesh(multi_pod=False)


def make_solver_mesh_for(devices: int, ny: int | None = None,
                         nx: int | None = None):
    """Flat 2-D solver processor grid for an arbitrary device count.

    The column axis gets the largest power of two whose square fits in
    ``devices`` and that divides ``ny``; the remaining devices become
    rows, trimmed until they divide ``nx`` -- so the decomposition in
    ``solve(..., mesh=...)`` is legal on an (nx, ny) grid whenever both
    extents are passed.  Device counts that don't factor cleanly use the
    largest legal subset (e.g. 4 of 5 devices).  This is the mesh the
    launchers hand to the mesh-aware front-end.
    """
    mp = 1
    while mp * mp <= devices and (ny is None or ny % mp == 0):
        mp *= 2
    mp = max(mp // 2, 1)
    rows = max(devices // mp, 1)
    while rows > 1 and nx is not None and nx % rows:
        rows -= 1
    return make_mesh_compat((rows, mp), ("data", "model"))
