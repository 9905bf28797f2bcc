"""Host spans and counters of the solver's front end and drivers.

Every entry a user calls opens one *root* span:

  ``solver.solve``   ``Solver.solve`` and the one-shot ``repro.core.solve``
  ``solver.submit``  one per queued request, carrying its request id
  ``solver.flush``   one per batched chunk of ``Solver.flush`` (which
                     ``SolverPool.flush`` reaches), carrying the request
                     ids of its lanes and ``rhs`` / ``lanes`` (real and
                     padded)

and the drivers below it open the same children on every path (single
device, vmap batch, mesh, each re-entry of the host restart loop):

  ``plcg.prepare``   host work before the sweep is dispatched
  ``plcg.dispatch``  the call into the jitted sweep; ``compiled=True`` when
                     the sweep's jit cache grew during the call
  ``plcg.wait``      ``jax.block_until_ready`` on the sweep's outputs: the
                     one child that is device time, not front-end time
  ``plcg.fetch``     one blocking device-to-host read (``what=`` names it)
  ``plcg.unpack``    building the result on the host

Each span is a ``jax.profiler.TraceAnnotation``, so a profile shows it on
the same clock as the device's ops, and a :class:`Span` record kept in
memory.  Counters live on the root record, counted where the work
happens:

  ``syncs``   the ``plcg.fetch`` spans under the root
  ``bodies``  bodies the engine ran, the exit trip each sweep
              returns, summed over lanes (padded lanes included) and
              over host-loop re-entries
  ``useful``  per real lane, the body index of its last committed update
              plus one, summed

The store is always on and keeps the newest :data:`MAX_ROOTS` roots, each
with its descendants; the oldest are dropped first.  :func:`roots` reads
it.  A root's front-end (self) time is its duration less the time its
``plcg.wait`` children cover.
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import itertools
import threading
import time
from typing import Optional

import jax
import numpy as np

#: roots the store keeps; older ones are dropped first
MAX_ROOTS = 4096


@dataclasses.dataclass(eq=False)
class Span:
    """One span: ``perf_counter_ns`` start and end, its id, the id of the
    span that opened it (``None`` for a root), the request ids it serves
    and its attributes.  A root also holds its ``counters`` and every
    descendant, in the order they opened (``spans``)."""
    name: str
    id: int
    parent: Optional[int]
    start_ns: int = 0
    end_ns: int = 0
    requests: tuple = ()
    attrs: dict = dataclasses.field(default_factory=dict)
    counters: dict = dataclasses.field(default_factory=dict)
    spans: list = dataclasses.field(default_factory=list)

    @property
    def duration_ns(self) -> int:
        return self.end_ns - self.start_ns


_ids = itertools.count(1)
_roots: collections.deque = collections.deque(maxlen=MAX_ROOTS)
_local = threading.local()


def _stack() -> list:
    stack = getattr(_local, "stack", None)
    if stack is None:
        stack = _local.stack = []
    return stack


@contextlib.contextmanager
def span(name: str, *, requests=(), **attrs):
    """Open a span named ``name`` and yield its :class:`Span` record: a
    root when no span is open on this thread, else a child of the
    innermost open one."""
    stack = _stack()
    rec = Span(name, next(_ids), stack[-1].id if stack else None,
               requests=tuple(requests), attrs=attrs)
    if stack:
        stack[0].spans.append(rec)
    stack.append(rec)
    with jax.profiler.TraceAnnotation(name):
        rec.start_ns = time.perf_counter_ns()
        try:
            yield rec
        finally:
            rec.end_ns = time.perf_counter_ns()
            stack.pop()
            if not stack:
                _roots.append(rec)


def request_ids(n: int) -> tuple:
    """``n`` fresh request ids (from the span id sequence)."""
    return tuple(next(_ids) for _ in range(n))


def count(key: str, n: int = 1) -> None:
    """Add ``n`` to counter ``key`` of the open root (no-op without one)."""
    stack = _stack()
    if stack:
        c = stack[0].counters
        c[key] = c.get(key, 0) + int(n)


def fetch(x, what: str, dtype=None):
    """``np.asarray(x, dtype)`` as one ``plcg.fetch`` span: a blocking
    device-to-host read, counted in the root's ``syncs``.  A tuple of
    arrays is read in one transfer (``jax.device_get``) and comes back as
    a tuple."""
    with span("plcg.fetch", what=what):
        count("syncs")
        if isinstance(x, tuple):
            return jax.device_get(x)
        return np.asarray(x, dtype=dtype)


def dispatch(fn, *args, program=None):
    """``fn(*args)`` as one ``plcg.dispatch`` span.  ``program`` is the
    jitted callable ``fn`` runs (``fn`` itself by default); where its jit
    cache size is known the span records ``compiled``: whether the cache
    grew during the call."""
    from ..kernels.introspect import jit_cache_size
    program = fn if program is None else program
    with span("plcg.dispatch") as rec:
        before = jit_cache_size(program)
        out = fn(*args)
        if before >= 0:
            rec.attrs["compiled"] = jit_cache_size(program) > before
    return out


def wait(out):
    """``jax.block_until_ready(out)`` as one ``plcg.wait`` span."""
    with span("plcg.wait"):
        return jax.block_until_ready(out)


def roots() -> list:
    """The stored root records, oldest first."""
    return list(_roots)


def clear() -> None:
    """Drop every stored root."""
    _roots.clear()
