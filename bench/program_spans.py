"""Arithmetic of the per-layer metrics read from the program's own spans
and counters (``repro.core.telemetry``, in the harness's process after
the window).

The window's records are picked out by the run's own counts: the newest
``solver.solve`` / ``solver.flush`` roots whose request ids add up to
``ctx.run["rhs"]`` (at least one a step), plus every root that serves one
of those ids (a flush's ``solver.submit`` roots).  The warm-up step is
older than all of them and never read.  A program without the telemetry
module, or a store that no longer holds every request of the window,
reads ``None``.
"""
from __future__ import annotations

from bench.trace import measure, union

#: roots that run the solver; the request ids they serve are solutions
WORK = ("solver.solve", "solver.flush")


def window_roots(ctx) -> list | None:
    """The window's root records (oldest first), or ``None``."""
    try:
        from repro.core import telemetry
    except ImportError:
        return None
    roots = telemetry.roots()
    rhs, steps = ctx.run["rhs"], ctx.run["steps"]
    if rhs <= 0:
        return None
    work, served = [], 0
    for r in reversed(roots):
        if served >= rhs:
            break
        if r.name in WORK:
            work.append(r)
            served += len(r.requests)
    if served != rhs or len(work) < steps:
        return None
    ids = {i for r in work for i in r.requests}
    return [r for r in roots if r.requests and set(r.requests) <= ids]


def self_ns(root) -> int:
    """The root's duration less the time its ``plcg.wait`` spans cover:
    the front end's own time."""
    waits = union((s.start_ns, s.end_ns) for s in root.spans
                  if s.name == "plcg.wait")
    return root.duration_ns - int(measure(waits))


def front_end_ms(ctx):
    """Front-end (self) milliseconds per right-hand side solved."""
    roots = window_roots(ctx)
    if roots is None:
        return None
    return sum(self_ns(r) for r in roots) * 1e-6 / ctx.run["rhs"]


def _counted(roots, key) -> int:
    return sum(r.counters.get(key, 0) for r in roots)


def useful_body_pct(ctx):
    """Scan bodies up to each real lane's last committed update, as a
    share of the bodies the engine ran."""
    roots = window_roots(ctx)
    if roots is None:
        return None
    bodies = _counted(roots, "bodies")
    if bodies <= 0:
        return None
    return 100.0 * _counted(roots, "useful") / bodies


def host_syncs(ctx):
    """Blocking device-to-host reads per right-hand side solved."""
    roots = window_roots(ctx)
    if roots is None:
        return None
    return _counted(roots, "syncs") / ctx.run["rhs"]
