"""Jaxpr introspection: count primitive equations in a traced function.

The structural acceptance gates of this repo are counted, not timed (CPU
interpret-mode timings are not probative of TPU launch overhead or of
collective latency):

* the ``backend="fused"`` scan body must contain exactly ONE
  ``pallas_call`` equation where the ``backend="pallas"`` tier has one
  per hot-path kernel (:func:`count_pallas_calls`);
* the mesh engine's scan body must contain exactly ONE ``psum`` for the
  stacked (nrhs, 2l+1) payload, vs TWO for the classic-CG baseline
  (:func:`count_primitive_in_scan_bodies` with ``"psum"``);
* under ``comm="overlap"`` the body must instead contain exactly one
  ``reduce_scatter`` + one ``all_gather`` and ZERO bare psums -- the
  split reduction is structurally in flight
  (:func:`count_collectives_in_scan_bodies` returns all four collective
  counts at once), and the staging depth is visible as the scattered
  slot block in the scan carry (:func:`scan_carry_shapes`).

Counting equations in the traced jaxpr verifies all of this without
running anything.  A "scan body" here is the body of any loop equation:
a ``lax.scan``, or the ``lax.while_loop`` that drives the p(l)-CG engine
until every lane is done.
"""
from __future__ import annotations

import jax
from jax.extend import core as jex_core


def jit_cache_size(fn) -> int:
    """Number of XLA compilations a jitted callable holds (-1 if the
    callable exposes no cache, e.g. a plain function).

    The serving-layer acceptance gate counts compilations, not time: a
    prepared ``repro.core.session.Solver`` must show ZERO cache growth
    across repeated same-shape calls after the first (each new RHS shape
    or tol override adds exactly one entry).
    """
    try:
        return int(fn._cache_size())
    except AttributeError:
        return -1


def count_primitive(fn, primitive: str, *args, **kwargs) -> int:
    """Number of ``primitive`` equations anywhere in ``fn``'s jaxpr
    (recursing into scan/cond/jit sub-jaxprs; cond counts every branch)."""
    closed = jax.make_jaxpr(fn)(*args, **kwargs)
    return _count(closed.jaxpr, primitive, set())


def count_pallas_calls(fn, *args, **kwargs) -> int:
    """Number of ``pallas_call`` equations anywhere in ``fn``'s jaxpr."""
    return count_primitive(fn, "pallas_call", *args, **kwargs)


def count_primitive_in_scan_bodies(fn, primitive: str, *args,
                                   **kwargs) -> list[int]:
    """Per-loop-body counts of ``primitive`` equations.

    One entry per scan or while equation reachable from ``fn``'s jaxpr,
    in traversal order -- i.e. the per-*iteration* cost of each loop.
    For the mesh solver sweeps (one loop) this returns
    ``[psums_per_iter]``.
    """
    closed = jax.make_jaxpr(fn)(*args, **kwargs)
    bodies: list = []
    _collect_scan_bodies(closed.jaxpr, bodies, set())
    return [_count(b, primitive, set()) for b in bodies]


#: jaxpr primitive names of the collectives a comm policy can emit (note
#: ``psum_scatter`` traces as ``reduce_scatter``).
COLLECTIVE_PRIMITIVES = ("psum", "reduce_scatter", "all_gather", "ppermute")


def count_collectives_in_scan_bodies(fn, *args, **kwargs) -> list[dict]:
    """Per-scan-body counts of every collective primitive at once.

    One dict per scan equation (same order as
    :func:`count_primitive_in_scan_bodies`), mapping each name in
    :data:`COLLECTIVE_PRIMITIVES` to its per-iteration count -- the
    structural signature of a comm policy: blocking
    ``{"psum": 1, ...}``, overlap ``{"psum": 0, "reduce_scatter": 1,
    "all_gather": 1, ...}``, ring all-zeros except ``ppermute`` (halo
    hops + reduction hops).
    """
    closed = jax.make_jaxpr(fn)(*args, **kwargs)
    bodies: list = []
    _collect_scan_bodies(closed.jaxpr, bodies, set())
    return [{p: _count(b, p, set()) for p in COLLECTIVE_PRIMITIVES}
            for b in bodies]


def collective_payload_shapes_in_scan_bodies(fn, *args,
                                             **kwargs) -> list[list[tuple]]:
    """Per-scan-body ``(primitive, operand shape)`` pairs for every
    collective equation -- the payload-width signature of the per-
    iteration reduction.

    The stability path of ``plcg_scan`` (``restart=`` /
    ``rr_period=``) widens the fused scalar payload by exactly one slot
    (the re-seed residual M-norm rides along): a blocking mesh sweep
    shows ``[("psum", (2l+2,))]`` per body instead of ``[("psum",
    (2l+1,))]`` -- still ONE collective, so the per-iteration collective
    *count* signature of every ``comm=`` policy is unchanged.  Under
    batched lanes the lane axis prepends (``(nrhs, 2l+2)``).
    """
    closed = jax.make_jaxpr(fn)(*args, **kwargs)
    bodies: list = []
    _collect_scan_bodies(closed.jaxpr, bodies, set())
    out = []
    for b in bodies:
        pairs: list = []
        _collect_collective_shapes(b, pairs, set())
        out.append(pairs)
    return out


def collective_payload_dtypes_in_scan_bodies(fn, *args,
                                             **kwargs) -> list[list[tuple]]:
    """Per-scan-body ``(primitive, operand shape, operand dtype)`` triples
    for every collective equation -- the full payload signature of the
    per-iteration reduction.

    The precision-policy acceptance gate: a ``precision="bf16"`` storage
    policy must change what each shard streams through HBM *locally* and
    NOTHING about the wire -- same collective primitives, same payload
    shapes, and payload dtype equal to the policy's f32/f64 *compute*
    dtype (never bfloat16).  Asserted structurally here, without running
    the mesh program.
    """
    closed = jax.make_jaxpr(fn)(*args, **kwargs)
    bodies: list = []
    _collect_scan_bodies(closed.jaxpr, bodies, set())
    out = []
    for b in bodies:
        pairs: list = []
        _collect_collective_shapes(b, pairs, set(), with_dtype=True)
        out.append(pairs)
    return out


def _collect_collective_shapes(jaxpr, out: list, seen: set,
                               with_dtype: bool = False) -> None:
    if id(jaxpr) in seen:
        return
    seen.add(id(jaxpr))
    for eqn in jaxpr.eqns:
        if eqn.primitive.name in COLLECTIVE_PRIMITIVES:
            aval = eqn.invars[0].aval
            out.append((eqn.primitive.name, tuple(aval.shape), aval.dtype)
                       if with_dtype
                       else (eqn.primitive.name, tuple(aval.shape)))
        for sub in _sub_jaxprs(eqn.params):
            _collect_collective_shapes(sub, out, seen, with_dtype)


def scan_carry_shapes(fn, *args, **kwargs) -> list[list[tuple]]:
    """Per-loop carry layouts: one list of ``(shape...)`` tuples per loop
    equation reachable from ``fn``'s jaxpr, in traversal order.

    The in-flight reduction queue lives in the scan carry, so its
    staging depth is readable here without running anything: a blocking
    p(l)-CG sweep carries one ``(l, 2l+1)`` payload block, an overlap
    sweep a ``(d, ceil((2l+1)/nshards))`` scattered-shard block (plus an
    ``(l-d, 2l+1)`` gathered block when ``d < l``), a ring sweep two
    ``(l, 2l+1)`` hop buffers.
    """
    closed = jax.make_jaxpr(fn)(*args, **kwargs)
    shapes: list = []
    _collect_scan_carries(closed.jaxpr, shapes, set())
    return shapes


def _collect_scan_carries(jaxpr, out: list, seen: set) -> None:
    if id(jaxpr) in seen:
        return
    seen.add(id(jaxpr))
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "scan":
            nc, ncarry = eqn.params["num_consts"], eqn.params["num_carry"]
            out.append([tuple(v.aval.shape)
                        for v in eqn.invars[nc:nc + ncarry]])
        elif eqn.primitive.name == "while":
            nc = eqn.params["cond_nconsts"] + eqn.params["body_nconsts"]
            out.append([tuple(v.aval.shape) for v in eqn.invars[nc:]])
        for sub in _sub_jaxprs(eqn.params):
            _collect_scan_carries(sub, out, seen)


def _count(jaxpr, primitive: str, seen: set) -> int:
    if id(jaxpr) in seen:       # guard against shared sub-jaxprs
        return 0
    seen.add(id(jaxpr))
    total = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == primitive:
            total += 1
        for sub in _sub_jaxprs(eqn.params):
            total += _count(sub, primitive, seen)
    return total


def _collect_scan_bodies(jaxpr, out: list, seen: set) -> None:
    if id(jaxpr) in seen:
        return
    seen.add(id(jaxpr))
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "scan":
            out.append(eqn.params["jaxpr"].jaxpr)
        elif eqn.primitive.name == "while":
            out.append(eqn.params["body_jaxpr"].jaxpr)
        for sub in _sub_jaxprs(eqn.params):
            _collect_scan_bodies(sub, out, seen)


def _sub_jaxprs(obj):
    """Yield every Jaxpr reachable from an eqn params value."""
    if isinstance(obj, jex_core.Jaxpr):
        yield obj
    elif isinstance(obj, jex_core.ClosedJaxpr):
        yield obj.jaxpr
    elif isinstance(obj, dict):
        for v in obj.values():
            yield from _sub_jaxprs(v)
    elif isinstance(obj, (list, tuple)):
        for v in obj:
            yield from _sub_jaxprs(v)
