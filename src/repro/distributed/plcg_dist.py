"""Mesh execution layer of the unified solver engine (paper Sec. 5 setup).

This module used to be a standalone distributed driver; it is now the
layer ``repro.core.solve(A, b, mesh=...)`` dispatches onto.  Any
:class:`~repro.distributed.operator.DistributedOperator` (or a
``LinearOperator`` with a ``stencil2d`` hint, auto-promoted to
:class:`DistPoisson`) runs a registry method on the mesh:

  ============  =========================================================
  ``plcg``      deep-pipelined p(l)-CG: ``jit(shard_map(plcg_scan))``
  ``plcg_scan`` alias of the same mesh engine (one scan engine everywhere)
  ``cg``        classic CG baseline: TWO synchronous psums per iteration
  ============  =========================================================

Per iteration of the pipelined engine:

  * SPMV: halo exchange (4 ``ppermute``; neighbor ICI traffic) + the
    local Pallas 5-point stencil kernel;
  * dot products: local partials only; ONE fused ``psum`` of the stacked
    (2l+1)-scalar payload per iteration -- the paper's single
    ``MPI_Iallreduce`` (Alg. 3 line 11);
  * the psum result lands in the depth-l in-flight queue of
    ``plcg_scan`` and is consumed l iterations later -- the ``MPI_Wait``
    of Alg. 3 line 5, giving XLA's scheduler l SPMVs of slack to hide
    the reduction.

Batched multi-RHS: a ``(nrhs, nx, ny)`` right-hand side runs domain
decomposition *inside* (``shard_map`` over the grid axes) and RHS
batching *outside* (``vmap`` of the engine body over lanes), so the
per-iteration payload stacks to ``(nrhs, 2l+1)`` and the batched
collective is STILL one psum -- all lanes' reductions ride one fused
all-reduce, the strong-scaling multi-solve workload of arXiv:1905.06850.
Convergence is masked per lane by the engine's commit select, and the
loop stops when every lane is done, identically to the single-device
batched path; every device computes the same exit predicate (see
``plcg_scan``).

Preconditioning composes: a structured ``repro.core.precond``
preconditioner with a shard-local apply (``BlockJacobi`` -- zero
communication; ``Chebyshev`` -- neighbor halos only; constant-diagonal
``Jacobi``) is resolved via ``operator.resolve_prec_local`` and applied
inside the shard_map body, so preconditioned p(l)-CG keeps exactly ONE
stacked psum per iteration (and preconditioned CG its two, by stacking
``<r,u>``/``<r,r>`` into one payload).

The injected local-partial dots bypass every kernel ``backend`` tier
(including ``"fused"``) by construction -- the distributed hot path is
the halo-exchange stencil kernel plus the collective schedule, not the
single-device megakernel.
"""
from __future__ import annotations

import weakref
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.compat import shard_map_compat
from repro.core import engine as _engine
from repro.core import telemetry
from repro.core.comm import as_comm_policy, build_comm_runtime
from repro.core.plcg_scan import (plcg_scan, read_batched,
                                  run_restart_driver, stab_iter_slack)
from repro.core.precision import as_precision_policy
from repro.core.results import SolveResult
from repro.core.solver_cache import WeakCallableCache

from .operator import (DistributedOperator, as_dist_operator,
                       resolve_prec_local)

#: Jitted mesh sweeps, keyed weakly on the operator (dropping the operator
#: releases the compiled shard_map program).
_MESH_SWEEP_CACHE = WeakCallableCache(maxsize=16)


def _batch_spec(spec: P) -> P:
    """Prepend an unsharded lane axis to a field PartitionSpec."""
    return P(*((None,) + tuple(spec)))


def _is_bindable_dist(op) -> bool:
    """True for a distributed operator carrying a rebindable context:
    ``matvec_local_ctx(context, v_local)`` plus ``context`` /
    ``context_specs()`` (the mesh twin of ``repro.core.linop.
    BindableOperator``)."""
    return (callable(getattr(op, "matvec_local_ctx", None))
            and hasattr(op, "context")
            and callable(getattr(op, "context_specs", None)))


def _shard_jit(op: DistributedOperator, one, *, batched: bool,
               n_extra: int = 0, n_out: int = 4, trace_event=None,
               ctx_specs=None):
    """Wrap a per-shard local body into the jitted shard_map program.

    ``one(b_blk, x_blk, *extra)`` maps one local field block (plus
    ``n_extra`` replicated scalar operands, e.g. an iteration budget) to
    ``(x_blk, *n_out replicated scalar/trace outputs)``; with
    ``batched`` the blocks carry a leading lane axis that ``one`` handles
    itself (extras are shared across lanes) and ``trace_event(shape)``,
    when given, logs a compile event like the single-device batched
    engine.

    ``ctx_specs`` (a pytree of ``PartitionSpec`` from a bindable
    operator's ``context_specs()``) prepends a traced context operand:
    ``one(ctx, b_blk, x_blk, *extra)``, shared across vmapped lanes, so
    rebinding the operator data between solves reuses the one compiled
    shard_map program.
    """
    spec = op.spec()
    n_ctx = 0 if ctx_specs is None else 1
    if batched:
        def local_run(*args):
            b_blk = args[n_ctx]
            if (trace_event is not None
                    and len(_engine.BATCH_TRACE_EVENTS) < 4096):
                _engine.BATCH_TRACE_EVENTS.append(
                    trace_event(tuple(b_blk.shape)))
            return one(*args)
        io_spec = _batch_spec(spec)
    else:
        local_run, io_spec = one, spec
    in_specs = ((ctx_specs,) if n_ctx else ()) \
        + (io_spec, io_spec) + (P(),) * n_extra
    fn = shard_map_compat(
        local_run, mesh=op.mesh,
        in_specs=in_specs,
        out_specs=(io_spec,) + (P(),) * n_out,
        check=False,
    )
    return jax.jit(fn)


def _weak_prec_resolver(op, prec):
    """Trace-time shard-local resolution of ``prec`` on ``op`` (pass the
    operator's ``weakref.proxy`` so neither object is pinned).

    The returned thunk runs INSIDE the traced ``one`` body, so the
    shard-local closure (which binds the preconditioner's arrays) lives
    only for the duration of the trace -- the cached compiled program
    never pins the Preconditioner object, exactly like the operator's
    ``weakref.proxy``.  When the preconditioner died and a retrace is
    attempted, this raises ``ReferenceError`` (and the weak cache key has
    already evicted the entry).
    """
    if prec is None:
        return lambda: None
    mref = weakref.ref(prec)

    def resolve():
        M = mref()
        if M is None:
            raise ReferenceError(
                "mesh preconditioner was garbage-collected; rebuild the "
                "sweep (see repro.core.clear_solver_cache)")
        return resolve_prec_local(op, M)

    return resolve


def plcg_mesh_sweep(op: DistributedOperator, *, l: int, iters: int,
                    sigma: Sequence[float], tol: float = 0.0,
                    exploit_symmetry: bool = True, batched: bool = False,
                    prec=None, comm=None, restart=None, rr_period=None,
                    ritz_refresh: bool = True, precision=None):
    """Build (cached) the jitted p(l)-CG mesh sweep.

    Returns a jitted callable ``(b, x0, k_budget) -> (x, resnorms,
    converged, breakdown, k_done, committed, restarts, replacements,
    trips)`` where ``b``/``x0`` are global fields
    of shape ``op.global_shape`` (``(nrhs, *global_shape)`` when
    ``batched``) and ``k_budget`` is the (traced) solution-update budget
    -- the restart driver passes the *remaining* global budget per sweep
    so every sweep reuses ONE compiled program.  ``restart`` /
    ``rr_period`` enable the scan engine's in-scan stability path
    (per-lane re-seed on breakdown / periodic true-residual replacement;
    see ``plcg_scan``); the widened reduction payload still rides the
    one per-iteration collective of the selected ``comm`` policy, so the
    per-iteration collective signature is unchanged.  ``prec`` is a
    structured
    ``repro.core.precond.Preconditioner`` resolved shard-locally via
    :func:`resolve_prec_local`; its apply is communication-free (or
    neighbor-halo only), so the traced program STILL contains exactly ONE
    reduction per scan body -- with the default blocking ``comm`` policy
    a single ``psum``, the structural acceptance gate verified by
    ``repro.kernels.introspect.count_primitive_in_scan_bodies``.

    ``precision`` (a ``repro.core.precision.PrecisionPolicy`` or spec
    accepted by ``as_precision_policy``) splits window *storage* dtype
    from scalar *compute* dtype inside the scan engine.  Every dot
    payload, in-flight queue slot and therefore every collective buffer
    (psum / psum_scatter / all_gather / ring ppermute) stays in the
    compute dtype -- a bf16-storage policy changes the bytes each shard
    streams locally, never the collective signature or its f32/f64
    payload dtype (gated structurally by
    ``collective_payload_dtypes_in_scan_bodies``).

    ``comm`` (a ``repro.core.comm.CommPolicy`` or mode string) selects
    how that reduction is realized: ``"overlap"`` splits it into a
    ``psum_scatter`` at issue and an ``all_gather`` ``depth`` iterations
    later (zero bare psums in the scan body -- the reduction is
    structurally in flight); ``"ring"`` stages circulate-accumulate
    ``ppermute`` hops across the queue shifts (and agrees the loop's exit
    with one scalar ``pmax`` per trip, since its devices sum in different
    orders).  The policy is part of the
    sweep cache key; its operator capabilities are validated here via
    ``build_comm_runtime`` (prepared sessions validate earlier, at
    construction).
    """
    sig = tuple(sigma)
    policy = as_comm_policy(comm)
    pp = as_precision_policy(precision)
    bind = _is_bindable_dist(op)

    def build():
        # the cached jitted program must not pin the operator (the cache
        # key holds it weakly and evicts on death): trace through a weak
        # proxy, like the single-device sweep's weakly_callable closures
        opref = weakref.proxy(op)
        resolve = _weak_prec_resolver(opref, prec)
        runtime = build_comm_runtime(policy, opref, l)

        def scan_body(matvec_local, b_blk, x_blk, k_budget):
            # a batch keeps its lane axis: the engine vmaps its body
            flat = b_blk.shape[:1] + (-1,) if batched else (-1,)
            out = plcg_scan(
                matvec_local, b_blk.reshape(flat), x_blk.reshape(flat),
                l=l, iters=iters, sigma=sig, tol=tol,
                prec=resolve(),
                dot_local=opref.dot_local,
                reduce_scalars=opref.reduce_scalars,
                exploit_symmetry=exploit_symmetry, k_budget=k_budget,
                comm=runtime,
                restart=restart, rr_period=rr_period,
                ritz_refresh=ritz_refresh, precision=pp,
            )
            return (out.x.reshape(b_blk.shape), out.resnorms, out.converged,
                    out.breakdown, out.k_done, out.committed, out.restarts,
                    out.replacements, out.trips)

        if bind:
            # the context is a traced leading operand of the shard_map
            # program (sharded per the operator's context_specs), so
            # rebinding operator data never retraces
            def one(ctx, b_blk, x_blk, k_budget):
                return scan_body(lambda v: opref.matvec_local_ctx(ctx, v),
                                 b_blk, x_blk, k_budget)
            ctx_specs = op.context_specs()
        else:
            def one(b_blk, x_blk, k_budget):
                return scan_body(opref.matvec_local, b_blk, x_blk, k_budget)
            ctx_specs = None

        return _shard_jit(op, one, batched=batched, n_extra=1, n_out=8,
                          trace_event=lambda shape: ("plcg@mesh", shape, l),
                          ctx_specs=ctx_specs)

    return _MESH_SWEEP_CACHE.get_or_build(
        (op, prec),
        ("plcg", l, iters, sig, tol, exploit_symmetry, batched, policy,
         restart, rr_period, ritz_refresh, pp, bind),
        build)


def cg_mesh_sweep(op: DistributedOperator, *, iters: int, tol: float = 0.0,
                  batched: bool = False, prec=None):
    """Build (cached) the jitted classic-CG mesh sweep (the two-psum
    baseline for the strong-scaling comparisons, paper Figs. 3-5).

    Same ``x0``/early-stop contract as the pipelined sweep: the initial
    guess seeds ``r0 = b - A x0``, converged state freezes through the
    ``done`` select, and the committed-update count ``k_done`` is
    reported.  With ``prec`` (shard-local, see :func:`resolve_prec_local`)
    this is preconditioned CG; the ``<r, u>`` and ``<r, r>`` reductions
    ride ONE stacked psum so the per-iteration collective count stays at
    the baseline's two.  Returns a jitted callable ``(b, x0) -> (x,
    resnorms, resnorm0, converged, k_done)``.
    """

    bind = _is_bindable_dist(op)

    def build():
        opref = weakref.proxy(op)       # see plcg_mesh_sweep
        resolve = _weak_prec_resolver(opref, prec)

        def cg_body(matvec_local, b_blk, x_blk):
            plocal = resolve()
            bflat = b_blk.reshape(-1)
            bnorm2 = opref.reduce_scalars(opref.dot_local(bflat, bflat))
            bnorm2 = jnp.where(bnorm2 == 0, 1.0, bnorm2)
            r0 = bflat - matvec_local(x_blk.reshape(-1))
            if plocal is None:
                gamma0 = opref.reduce_scalars(opref.dot_local(r0, r0))
                rr0 = gamma0
                u0 = r0
            else:
                u0 = plocal(r0)
                pay0 = opref.reduce_scalars(jnp.stack(
                    [opref.dot_local(r0, u0), opref.dot_local(r0, r0)]))
                gamma0, rr0 = pay0[0], pay0[1]
            done0 = rr0 <= (tol ** 2) * bnorm2

            # the preconditioned carry adds rr = <r, r> (for the stopping
            # test); the unpreconditioned carry stays identical to the
            # two-psum baseline (there rr IS gamma)
            def body(st, _):
                if plocal is None:
                    x, r, p, gamma, k, done = st
                    rr = gamma
                else:
                    x, r, p, gamma, rr, k, done = st
                s = matvec_local(p)
                sp = opref.reduce_scalars(
                    opref.dot_local(s, p))                  # sync psum 1
                alpha = gamma / sp
                x2 = x + alpha * p
                r2 = r - alpha * s
                if plocal is None:
                    gamma2 = opref.reduce_scalars(
                        opref.dot_local(r2, r2))            # sync psum 2
                    rr2 = gamma2
                    u2 = r2
                else:
                    u2 = plocal(r2)
                    pay = opref.reduce_scalars(jnp.stack(
                        [opref.dot_local(r2, u2),
                         opref.dot_local(r2, r2)]))         # sync psum 2
                    gamma2, rr2 = pay[0], pay[1]
                p2 = u2 + (gamma2 / gamma) * p
                conv = rr2 <= (tol ** 2) * bnorm2
                if plocal is None:
                    new = (x2, r2, p2, gamma2, k + 1, done | conv)
                else:
                    new = (x2, r2, p2, gamma2, rr2, k + 1, done | conv)
                out = jax.tree.map(lambda a, o: jnp.where(done, o, a),
                                   new, st)
                return out, jnp.sqrt(jnp.where(done, rr, rr2))

            st0 = ((x_blk.reshape(-1), r0, u0, gamma0, jnp.asarray(0),
                    done0) if plocal is None else
                   (x_blk.reshape(-1), r0, u0, gamma0, rr0,
                    jnp.asarray(0), done0))
            st, resn = jax.lax.scan(body, st0, jnp.arange(iters))
            return (st[0].reshape(b_blk.shape), resn, jnp.sqrt(rr0),
                    st[-1], st[-2])

        if bind:
            def one(ctx, b_blk, x_blk):
                return cg_body(lambda v: opref.matvec_local_ctx(ctx, v),
                               b_blk, x_blk)
            ctx_specs = op.context_specs()
            lane_axes = (None, 0, 0)
        else:
            def one(b_blk, x_blk):
                return cg_body(opref.matvec_local, b_blk, x_blk)
            ctx_specs = None
            lane_axes = (0, 0)
        if batched:
            one = jax.vmap(one, in_axes=lane_axes)

        return _shard_jit(op, one, batched=batched, ctx_specs=ctx_specs)

    return _MESH_SWEEP_CACHE.get_or_build(
        (op, prec), ("cg", iters, tol, batched, bind), build)


# --------------------------------------------------------------------------
# front-end dispatch (called by repro.core.solve when mesh= is given)
# --------------------------------------------------------------------------

def _canonicalize_b(op: DistributedOperator, b, x0):
    """Reshape flat inputs to the operator's global field shape.

    Accepts ``global_shape``, ``(nrhs, *global_shape)``, flat ``(n,)`` and
    stacked-flat ``(nrhs, n)``.  Returns (b, x0, batched, orig_shape).
    """
    gshape = tuple(op.global_shape)
    n = int(np.prod(gshape))
    b = jnp.asarray(b)
    orig_shape = b.shape
    if b.shape == (n,):
        b = b.reshape(gshape)
    elif b.ndim == 2 and b.shape[-1] == n and b.shape != gshape:
        b = b.reshape((b.shape[0],) + gshape)
    batched = b.ndim == len(gshape) + 1 and b.shape[1:] == gshape
    if not batched and b.shape != gshape:
        raise ValueError(
            f"b of shape {orig_shape} does not match the operator's global "
            f"field {gshape} (or (nrhs, *{gshape}) / flat ({n},))")
    x0 = jnp.zeros_like(b) if x0 is None else jnp.asarray(x0).reshape(b.shape)
    return b, x0, batched, orig_shape


def _mesh_plcg(op, b, x0, *, tol, maxiter, l, sigma, prec=None,
               exploit_symmetry: bool = True,
               max_restarts=None, comm=None, restart=None,
               residual_replacement=None, ritz_refresh: bool = True,
               precision=None, get_sweep=None, lanes=None) -> SolveResult:
    """p(l)-CG on the mesh: one RHS through the shared restart driver, or
    a stacked batch through one sweep whose lanes past the first
    ``lanes`` (default: none) are padding, counted in the ``bodies``
    telemetry counter and not in ``useful``."""
    with telemetry.span("plcg.prepare"):
        b, x0, batched, orig_shape = _canonicalize_b(op, b, x0)
        norm = None if batched else jnp.linalg.norm(b)
    sig = tuple(sigma)
    policy = as_comm_policy(comm)
    pp = as_precision_policy(precision)
    # the in-scan stability path (restart= / residual_replacement=,
    # normalized by engine._prepare_restart) runs ONE sweep whose lanes
    # re-seed themselves in-trace; the sweep needs stab_iter_slack extra
    # bodies so the update budget stays spendable through re-seeds
    stab = restart is not None or residual_replacement is not None
    slack = stab_iter_slack(l, restart, residual_replacement, maxiter)
    if get_sweep is None:
        def get_sweep(*, iters, batched):
            return plcg_mesh_sweep(op, l=l, iters=iters, sigma=sig,
                                   tol=tol,
                                   exploit_symmetry=exploit_symmetry,
                                   batched=batched, prec=prec, comm=policy,
                                   restart=restart,
                                   rr_period=residual_replacement,
                                   ritz_refresh=ritz_refresh, precision=pp)
    bind = _is_bindable_dist(op)

    def sweep_of(iters, batched):
        """``(callable, jitted program)``: a bindable operator's CURRENT
        context is bound at call time; the raw sweep (cached / strongly
        held by a session) takes it as a traced operand."""
        with telemetry.span("plcg.prepare"):
            raw = get_sweep(iters=iters, batched=batched)
        if not bind:
            return raw, raw
        ctx = op.context
        return (lambda bb, xx, kb: raw(ctx, bb, xx, kb)), raw
    base_info = {"l": l, "sigma": list(sig), "backend": None,
                 "mesh": dict(op.mesh.shape), "comm": policy.mode,
                 "precision": None if pp.is_default else pp,
                 # a split/ring policy leaves ZERO blocking psums in the
                 # scan body (the init reduction outside it stays a psum)
                 "psums_per_iter": 1 if policy.is_blocking else 0,
                 "restart": restart,
                 "residual_replacement": residual_replacement,
                 "prec": getattr(prec, "name", None)}
    if policy.mode == "overlap":
        base_info["overlap_depth"] = policy.resolve_depth(l)

    if batched:
        if max_restarts is not None:
            # mirror the single-device batched engine: don't silently
            # drop a flag the caller believes is active (the in-scan
            # restart= knob is the batched-capable replacement)
            raise ValueError(
                "options ['max_restarts'] are not supported by the "
                "batched mesh engine (the host restart loop is "
                "single-RHS; use the in-scan restart= knob for per-lane "
                "recovery)")
        # one sweep, per-lane convergence masking inside the scan; with
        # restart=/residual_replacement= lanes also re-seed themselves
        # in-trace (still ONE compiled sweep, zero host round-trips)
        fn, program = sweep_of(maxiter + l + 1 + slack, True)
        out = telemetry.dispatch(fn, b, x0, maxiter + 1, program=program)
        telemetry.wait(out)
        (resnorms, conv, brk, k_done, restarts_pl, repl_pl) = read_batched(
            out[1:], l=l, stab=stab, lanes=lanes, prec=prec is not None)
        return SolveResult(
            x=out[0].reshape(orig_shape),
            resnorms=resnorms,
            iters=int(k_done.max()) + 1,
            converged=bool(conv.all()),
            breakdowns=int(brk.sum()) + int(restarts_pl.sum()),
            restarts=int(restarts_pl.sum()),
            replacements=int(repl_pl.sum()),
            info={**base_info, "method": f"p({l})-CG[scan,mesh+vmap]",
                  "batched": "shard_map+vmap", "nrhs": int(b.shape[0]),
                  "per_rhs_converged": conv,
                  "per_rhs_iters": k_done + 1,
                  "per_rhs_breakdown": brk,
                  "per_rhs_restarts": restarts_pl,
                  "per_rhs_replacements": repl_pl},
        )

    # single RHS: ONE restart semantics, shared with the single-device
    # plcg_solve via run_restart_driver.  In-scan mode (restart= /
    # residual_replacement=) runs one compiled sweep whose re-seeds
    # happen in-trace; the legacy host loop (deprecated, shift-free
    # re-init) re-enters the sweep with the remaining budget when only
    # the max_restarts escape hatch is given.  Either way the budget is
    # a traced operand of ONE fixed-size compiled program, so restarts
    # never retrace/recompile the shard_map sweep.
    if stab:
        fn, program = sweep_of(maxiter + l + 1 + slack, False)
    else:
        fn, program = sweep_of(maxiter + l, False)
    x, resnorms, info = run_restart_driver(
        fn, b, x0, tol=tol, maxiter=maxiter,
        max_restarts=5 if max_restarts is None else max_restarts,
        bnorm=float(telemetry.fetch(norm, "bnorm")) or 1.0, l=l,
        in_scan=stab, program=program, prec=prec is not None)
    return SolveResult(
        x=x.reshape(orig_shape), resnorms=resnorms,
        iters=info["iterations"], converged=info["converged"],
        breakdowns=info["breakdowns"], restarts=info["restarts"],
        replacements=info.get("replacements", 0),
        info={**base_info, "method": f"p({l})-CG[scan,mesh]"},
    )


def _mesh_cg(op, b, x0, *, tol, maxiter, prec=None,
             get_sweep=None) -> SolveResult:
    b, x0, batched, orig_shape = _canonicalize_b(op, b, x0)
    if get_sweep is None:
        def get_sweep(*, iters, batched):
            return cg_mesh_sweep(op, iters=iters, tol=tol, batched=batched,
                                 prec=prec)
    fn = get_sweep(iters=maxiter, batched=batched)
    if _is_bindable_dist(op):
        raw, ctx = fn, op.context
        fn = lambda bb, xx: raw(ctx, bb, xx)  # noqa: E731
    x, resn, resn0, conv, k_done = fn(b, x0)
    base_info = {"method": "cg[mesh]", "mesh": dict(op.mesh.shape),
                 "psums_per_iter": 2,
                 "prec": getattr(prec, "name", None)}
    if batched:
        resn = np.asarray(resn)
        resn0 = np.asarray(resn0)
        conv = np.asarray(conv)
        k_done = np.asarray(k_done)
        return SolveResult(
            x=x.reshape(orig_shape),
            resnorms=[[float(r0)] + [float(r) for r in row[:int(k)]]
                      for row, r0, k in zip(resn, resn0, k_done)],
            iters=int(k_done.max()), converged=bool(conv.all()),
            info={**base_info, "batched": "shard_map+vmap",
                  "nrhs": int(b.shape[0]),
                  "per_rhs_converged": conv, "per_rhs_iters": k_done},
        )
    k = int(k_done)
    return SolveResult(
        x=x.reshape(orig_shape),
        resnorms=[float(resn0)] + [float(r) for r in np.asarray(resn)[:k]],
        iters=k, converged=bool(conv), info=base_info,
    )


#: method name -> mesh adapter.  The CAPABILITY lives in the registry
#: (``MethodSpec.supports_mesh``, checked by ``solve()``); this dict is
#: only the dispatch table, and a skew between the two raises loudly in
#: :func:`solve_on_mesh` instead of producing a second error message.
_MESH_METHODS = {
    "cg": _mesh_cg,
    "plcg": _mesh_plcg,
    "plcg_scan": _mesh_plcg,
}


def mesh_methods() -> tuple:
    """Registry methods with a mesh-aware execution path (derived from
    the ``supports_mesh`` capability flags -- single source of truth)."""
    return _engine.methods_supporting("mesh")


class PreparedMeshSolver:
    """One-time-validated mesh solver session (``repro.core.session``'s
    mesh back-end).

    Construction performs everything ``solve(..., mesh=...)`` used to
    redo per call: method/adaptor dispatch, operator promotion
    (:func:`as_dist_operator`), early shard-local preconditioner
    resolution, option validation and sigma resolution.  The jitted
    shard_map sweeps are built through the same weak-key cache as the
    one-shot path (so the two entry points share compilations) but are
    additionally held **strongly** in ``self._sweeps`` -- a live session
    keeps its compiled programs through ``clear_solver_cache()`` and
    weak-cache eviction, and ``solve()`` never re-derives them through
    the cache lookup.

    ``backend`` is ignored on this path (the front-end already warned):
    the injected local-partial dots bypass every kernel tier by
    construction.
    """

    def __init__(self, spec, A, mesh, *, M, l, sigma, spectrum,
                 comm=None, restart=None, residual_replacement=None,
                 precision=None, **options):
        if l == "auto" or comm == "auto":
            # the sentinels are resolved by prepare_on_mesh (which owns
            # the tol the calibration clamps against); reaching this
            # constructor with one is a wiring error, not a user error
            raise ValueError(
                "l='auto' / comm='auto' must be resolved before "
                "PreparedMeshSolver construction; build the session via "
                "prepare_on_mesh(...) (or session.Solver), which "
                "calibrates and passes the concrete depth/policy")
        if spec.name not in _MESH_METHODS:
            if getattr(spec, "supports_mesh", False):
                raise RuntimeError(
                    f"method {spec.name!r} declares supports_mesh=True but "
                    "has no adapter in distributed.plcg_dist._MESH_METHODS; "
                    "register one (the registry flag and the dispatch table "
                    "must move together)")
            raise ValueError(
                f"method {spec.name!r} has no mesh-aware execution path; "
                f"methods available on a mesh: {', '.join(mesh_methods())}")
        self.spec = spec
        self.op = as_dist_operator(A, mesh)
        self.prec = M
        if M is not None:
            resolve_prec_local(self.op, M)      # early, uniform validation
        # mesh-path option restriction + comm policy: both validated once
        # here through the engine's declarative tables (MethodSpec.
        # mesh_options / supports_comm) -- the adapters carry no
        # allow-lists of their own anymore
        _engine._prepare_mesh_options(spec, options)
        self.comm = _engine._prepare_comm(spec, comm, on_mesh=True)
        if spec.name == "cg":
            # same contract as the single-device cg adapter: l/sigma/
            # spectrum are pipelined-method knobs and are ignored
            self.sig = None
        else:
            self.sig = tuple(_engine._resolve_sigma(sigma, spectrum, l))
            # early, uniform validation of the operator's split-phase /
            # ring capability and the depth/hop constraints against l --
            # a prepared session never fails at first solve
            build_comm_runtime(self.comm, self.op, l)
        self.l = l
        # normalized stability knobs (engine._prepare_restart ran in the
        # session front end); baked into every prepared plcg sweep
        self.restart = restart
        self.residual_replacement = residual_replacement
        # normalized precision policy (engine._prepare_precision gated it
        # on the capability flag); collective payloads stay in its
        # compute dtype by construction of the scan engine
        self.precision = as_precision_policy(precision)
        self.auto = None            # AutoDecision when prepare_on_mesh
        self.options = dict(options)    # calibrated l/comm
        self._sweeps: dict = {}         # strong refs to jitted sweeps

    @property
    def builds(self) -> int:
        """Number of distinct jitted sweeps this session holds."""
        return len(self._sweeps)

    def _get_sweep(self, kind: str, tol: float):
        """Memoizing sweep getter bound to one (kind, tol); the returned
        callable has the ``get_sweep(iters=, batched=)`` signature of the
        ``_mesh_plcg`` / ``_mesh_cg`` runners."""

        def get(*, iters, batched):
            key = (kind, float(tol), int(iters), bool(batched))
            if key not in self._sweeps:
                if kind == "plcg":
                    self._sweeps[key] = plcg_mesh_sweep(
                        self.op, l=self.l, iters=iters, sigma=self.sig,
                        tol=tol, batched=batched, prec=self.prec,
                        comm=self.comm,
                        restart=self.restart,
                        rr_period=self.residual_replacement,
                        ritz_refresh=self.options.get("ritz_refresh", True),
                        precision=self.precision,
                        exploit_symmetry=self.options.get(
                            "exploit_symmetry", True))
                else:
                    self._sweeps[key] = cg_mesh_sweep(
                        self.op, iters=iters, tol=tol, batched=batched,
                        prec=self.prec)
            return self._sweeps[key]

        return get

    def prepare(self, *, tol: float, maxiter: int,
                batched: bool = False) -> None:
        """Eagerly build (and strongly hold) the sweep for one
        (tol, maxiter, batched) configuration -- jit wrapping only, the
        XLA compile itself still happens at the first real call."""
        if self.spec.name == "cg":
            self._get_sweep("cg", tol)(iters=maxiter, batched=batched)
        else:
            stab = (self.restart is not None
                    or self.residual_replacement is not None)
            if stab:
                iters = maxiter + self.l + 1 + stab_iter_slack(
                    self.l, self.restart, self.residual_replacement,
                    maxiter)
            else:
                iters = maxiter + self.l + (1 if batched else 0)
            self._get_sweep("plcg", tol)(iters=iters, batched=batched)

    def solve(self, b, x0=None, *, tol: float, maxiter: int) -> SolveResult:
        if self.spec.name == "cg":
            return _mesh_cg(self.op, b, x0, tol=tol, maxiter=maxiter,
                            prec=self.prec,
                            get_sweep=self._get_sweep("cg", tol))
        return _MESH_METHODS[self.spec.name](
            self.op, b, x0, tol=tol, maxiter=maxiter, l=self.l,
            sigma=self.sig, prec=self.prec, comm=self.comm,
            restart=self.restart,
            residual_replacement=self.residual_replacement,
            precision=self.precision,
            get_sweep=self._get_sweep("plcg", tol), **self.options)


def prepare_on_mesh(spec, A, mesh, *, M, l, sigma, spectrum, backend=None,
                    comm=None, restart=None, residual_replacement=None,
                    precision=None, tol: float = 1e-8,
                    **options) -> PreparedMeshSolver:
    """Build the prepared mesh session behind ``session.Solver(mesh=...)``
    (validation / promotion / resolution once; see
    :class:`PreparedMeshSolver`).  ``comm`` selects the reduction policy
    (``repro.core.comm.CommPolicy`` or mode string); ``restart`` /
    ``residual_replacement`` are the engine-normalized in-scan stability
    knobs baked into every prepared pipelined sweep.

    ``l="auto"`` / ``comm="auto"`` (the sentinels ``engine._prepare_depth``
    / ``engine._prepare_comm`` pass through) are resolved HERE, once: the
    operator is promoted early and ``repro.core.autotune.resolve_auto``
    measures its SPMV / per-mode reduction / per-depth sweep latencies on
    the live mesh (cached weakly per operator+config, so same-shape
    sessions re-measure nothing), then solves the paper's latency model
    for the fastest ``(l, comm, d)`` whose precision floor still reaches
    ``tol`` -- which is why this entry point takes the session ``tol``.
    The decision lands on ``session.auto`` (reported per solve as
    ``SolveResult.info["auto"]``)."""
    del backend     # front-end warned; bypassed by construction here
    decision = None
    if l == "auto" or comm == "auto":
        from repro.core.autotune import resolve_auto
        op = as_dist_operator(A, mesh)      # cached; the session reuses it
        decision = resolve_auto(op, l=l, comm=comm, tol=tol,
                                precision=precision)
        l, comm = decision.l, decision.comm
        A, mesh = op, None                  # already bound to its mesh
    sess = PreparedMeshSolver(spec, A, mesh, M=M, l=l, sigma=sigma,
                              spectrum=spectrum, comm=comm, restart=restart,
                              residual_replacement=residual_replacement,
                              precision=precision, **options)
    sess.auto = decision
    return sess


def solve_on_mesh(spec, A, b, *, mesh, x0, tol, maxiter, M, l, sigma,
                  spectrum, backend, comm=None, restart=None,
                  residual_replacement=None, precision=None,
                  **options) -> SolveResult:
    """One-shot mesh-aware dispatch behind ``repro.core.solve(mesh=...)``:
    a thin wrapper preparing a :class:`PreparedMeshSolver` and running it
    on ``b`` (the session API is the primary entry point; this keeps the
    legacy call-per-solve contract)."""
    return prepare_on_mesh(spec, A, mesh, M=M, l=l, sigma=sigma,
                           spectrum=spectrum, backend=backend, comm=comm,
                           restart=restart,
                           residual_replacement=residual_replacement,
                           precision=precision, tol=tol,
                           **options).solve(b, x0, tol=tol, maxiter=maxiter)
