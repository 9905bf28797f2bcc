"""Production p(l)-CG engine: jittable, windowed, pipeline-queued (JAX).

This is the TPU-native realization of paper Alg. 2 + Alg. 3:

* vectors live in fixed-size **sliding windows** (Appendix B), stored
  **lane-major**: ``Zw (n, l+1)`` holds the last l+1 auxiliary vectors,
  ``Vw (n, 2l+1)`` the last 2l+1 basis vectors (slot 0 newest), so the
  memory footprint is exactly the paper's 3l+2 vectors (3l+5
  preconditioned) and the 2l+1-entry band of one grid point is contiguous
  -- the layout the fused Pallas kernels stream block-by-block, and the
  layout under which a batched multi-RHS ``vmap`` lowers every kernel to
  ONE ``(B, n, window)`` launch instead of B replays;
* G is stored **banded by column** (Lemma 5): row c of ``Gb`` holds the
  2l+1-entry band of G's column c;
* the 2l+1 dot products of iteration i form one fused payload (the paper's
  single ``MPI_Iallreduce``) that is pushed into a depth-l **in-flight
  queue** carried through the loop state and *read l iterations later*
  (the ``MPI_Wait`` of Alg. 3).  Nothing in body i consumes the freshly
  reduced payload, so XLA's latency-hiding scheduler / collective pipeliner
  is free to overlap the all-reduce with the l interleaved SPMVs -- the
  compiler-scheduled equivalent of asynchronous MPI progress;
* the bodies run in a device-side ``lax.while_loop`` that stops after the
  body in which the last lane is done (converged, broken down or out of
  budget), or after ``iters`` bodies: time follows the iterations a solve
  needs, not its cap.  A stacked batch runs ``vmap`` of the body inside
  the one loop, so lanes that finish early are frozen by the body's own
  commit select until the last one is done.

``dot_local`` and ``reduce_payload`` are injected so the same engine drives:
  - the single-device path (dot = full dot, reduce = identity),
  - the shard_map distributed path (dot = local partial, reduce = one psum),
  - the Newton-pCG parameter-space path (flat parameter vectors).
"""
from __future__ import annotations

import functools
from typing import Callable, NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from . import telemetry
from .precision import as_precision_policy
from .solver_cache import WeakCallableCache, weakly_callable
from .solver_cache import clear_solver_cache  # noqa: F401  (re-export)

BACKENDS = (None, "pallas", "ref", "fused")


class PLCGState(NamedTuple):
    Zw: jax.Array          # (n, l+1)  z_{i}   .. z_{i-l}     (slot 0 newest)
    Vw: jax.Array          # (n, 2l+1) v_{i-l} .. v_{i-3l}    (slot 0 newest)
    Zhw: jax.Array         # (n, 3) zhat window (preconditioned) or (1,1) dummy
    Gb: jax.Array          # (ncols, 2l+1) banded G, row c = band of column c
    gam: jax.Array         # (ncols,)
    dlt: jax.Array         # (ncols,)
    inflight: tuple        # in-flight reduction queue: (l, 2l+1) array
    #                        (blocking) or the comm policy's slot pytree
    #                        (overlap: scattered shards [+ gathered tail];
    #                        ring: (acc, circ) hop buffers)
    x: jax.Array           # (n,) current solution x_{i-l}
    p: jax.Array           # (n,) search direction p_{i-l}
    eta: jax.Array         # scalar eta_{i-l}
    zeta: jax.Array        # scalar zeta_{i-l}
    k_done: jax.Array      # TOTAL solution updates committed minus one
    done: jax.Array        # bool: converged or broken down (frozen)
    converged: jax.Array   # bool
    breakdown: jax.Array   # bool
    # ---- stability autopilot (in-scan restart / residual replacement) ----
    # constants when the machinery is disabled (restart/rr_period unset)
    ph: jax.Array          # int32 phase-local body counter (== loop index i
    #                        until the first restart re-zeroes it)
    wait: jax.Array        # int32 restart micro-state: 0 active, l+1 reseed
    #                        body, l..2 waiting for the reseed reduction,
    #                        1 seed body
    beta: jax.Array        # beta0 of the CURRENT phase (||r0||_M at the
    #                        most recent (re)start)
    sig_c: jax.Array       # (l,) per-lane shifts, Ritz-refreshed at restart
    #                        (0-d dummy unless stab && ritz_refresh)
    restarts: jax.Array    # int32 per-lane in-scan restarts taken
    repl: jax.Array        # int32 per-lane residual replacements taken
    since_rr: jax.Array    # int32 committed updates since last (re)seed


class _LaneConsts(NamedTuple):
    """Per-lane constants the bodies read, carried beside the state so
    that a batch can ``vmap`` the body (not the loop)."""
    bnorm: jax.Array       # ||b||_M (1 where b = 0): the convergence scale
    bC: Optional[jax.Array]   # b in the compute dtype (re-seeds only)
    Mb: Optional[jax.Array]   # M b (re-seeds only)


class PLCGOut(NamedTuple):
    x: jax.Array
    resnorms: jax.Array    # (iters,) |zeta_k| per body (0 where not computed
    #                        or not run)
    k_done: jax.Array
    converged: jax.Array
    breakdown: jax.Array
    committed: jax.Array   # (iters,) bool: body committed a solution update
    #                        (resnorms[committed] is the residual history in
    #                        order; robust to restarts scattering the rows)
    restarts: jax.Array    # in-scan restarts taken (0 on the legacy path)
    replacements: jax.Array  # residual replacements taken
    trips: jax.Array       # int32 bodies this sweep ran: the loop's exit
    #                        trip, <= iters (shared by a batch's lanes)


def _default_dot(a, b):
    return jnp.vdot(a, b)


def resolve_backend(backend: Optional[str]) -> Optional[str]:
    """The kernel tier ``backend`` runs on this platform: ``"auto"`` is
    ``"pallas"`` on TPU and ``"ref"`` elsewhere; the others name
    themselves."""
    if backend == "auto":
        return "pallas" if jax.default_backend() == "tpu" else "ref"
    if backend not in BACKENDS:
        raise ValueError(
            "backend must be None, 'auto', 'pallas', 'ref' or 'fused', "
            f"got {backend!r}")
    return backend


def plcg_scan(
    matvec: Callable,
    b: jax.Array,
    x0: Optional[jax.Array] = None,
    *,
    l: int,
    iters: int,
    sigma: Sequence[float],
    tol: float = 0.0,
    prec: Optional[Callable] = None,
    prec_diag=None,
    dot_local: Optional[Callable] = None,
    reduce_scalars: Optional[Callable] = None,
    exploit_symmetry: bool = True,
    unroll: int = 1,
    backend: Optional[str] = None,
    stencil_hw: Optional[tuple] = None,
    k_budget: Optional[jax.Array] = None,
    comm=None,
    restart: Optional[int] = None,
    rr_period: Optional[int] = None,
    ritz_refresh: bool = True,
    precision=None,
) -> PLCGOut:
    """Run p(l)-CG bodies until every lane is done, at most ``iters``
    of them (solution index reaches at most iters-l-1).

    ``b`` is one right-hand side ``(n,)`` or a stacked batch ``(nrhs,
    n)`` (``x0`` alike): a batch runs the ``vmap`` of one body per loop
    trip, each lane frozen by the body's commit select once it is done.
    The loop stops after the body in which the last lane sets ``done``
    (converged, broken down, or out of ``k_budget``) or after ``iters``
    bodies; ``unroll`` bodies run back to back per loop trip.  A frozen
    body changes no output, so the result equals running all ``iters``
    bodies, and ``trips`` reports the bodies actually run.  Per-body
    outputs (``resnorms``, ``committed``) are ``(iters,)`` buffers whose
    unrun tail holds what a frozen body writes (0 and False).  Shapes
    are static, so solves that stop at different bodies share one
    compiled program.  Works under jit / inside shard_map, where every
    device computes the same exit predicate: ``done`` derives from the
    replicated reduction results under the blocking and ``"overlap"``
    policies, and ``"ring"`` (whose devices accumulate in their own
    order) agrees it with one scalar ``pmax`` over the ring's axes per
    trip.  ``reduce_scalars(payload)`` performs the global sum of a
    stacked scalar payload (identity on a single device, ``psum`` in the
    distributed runtime) -- exactly one call per iteration.

    ``k_budget`` (optional, may be a traced scalar shared by all lanes)
    freezes the state -- without setting ``converged`` or ``breakdown``
    -- once that many solution updates have been committed: restart
    drivers with a global iteration budget pass the *remaining* budget
    per sweep instead of recompiling a differently-sized loop.

    ``comm`` (optional) is a resolved ``repro.core.comm.CommRuntime``
    selecting how the per-iteration reduction is realized inside the
    depth-l queue: ``None`` keeps the blocking form (one fused
    ``reduce_scalars`` call per iteration); ``"overlap"`` splits it into
    ``comm.start`` (psum_scatter) at push and ``comm.finish``
    (all_gather) ``comm.depth`` iterations later, carrying scattered
    shard slots in the queue; ``"ring"`` replaces the all-reduce with
    circulate-accumulate ``ppermute`` hops applied while the queue
    shifts.  The total consumption delay stays EXACTLY l in every mode
    -- the recurrences finalize column i-l+1 from the dots of body i-l
    -- so the policy changes only which collective runs and where inside
    the l-body window it completes.  Only meaningful on the distributed
    path (``reduce_scalars`` injected); collectives still execute
    unconditionally on frozen lanes, matching the blocking psum.

    ``backend`` selects the implementation of the iteration hot path:

      * ``None``      -- inline jnp math (bit-exact legacy path);
      * ``"ref"``     -- the fused jnp oracles from ``kernels.ref`` for the
        (K4) window AXPY and (K5) multi-dot (CPU reference fallback);
      * ``"pallas"``  -- the per-kernel Pallas tier: one launch each for
        the (K4) AXPY and the two (K5) multi-dots (interpret mode on CPU);
      * ``"fused"``   -- the single-launch Pallas megakernel fusing the
        whole steady-state body: (K4) v/z/zhat recurrences + (K5) payload,
        and additionally the (K1) SPMV when ``stencil_hw`` marks the
        operator as the 2-D Poisson stencil.  A *diagonal* preconditioner
        (``prec_diag`` set -- the ``inv_diag`` hint of a structured
        ``Preconditioner``) folds into the same single launch (SPMV +
        diag apply + zhat recurrence in-kernel); a general ``prec``
        callable falls back to a 2-launch split (Pallas stencil SPMV,
        then the megakernel) when the stencil hint is present, or streams
        the externally computed t/t_hat into one launch otherwise.  Each
        basis vector is read from HBM exactly once per iteration;
      * ``"auto"``    -- ``"pallas"`` on TPU, ``"ref"`` elsewhere.

    The kernel path is only taken on the single-device full-vector dots
    (``dot_local is None``); the distributed shard_map runtime keeps its
    injected local-partial dots and single psum, bypassing every kernel
    tier including ``"fused"``.

    ``restart`` (optional int >= 0) enables IN-SCAN restart-on-breakdown
    (paper Remark 8 executed in-trace): a lane hitting square-root
    breakdown re-seeds its Krylov window from the current iterate --
    ``r = b - A x`` recomputed with the body's own SPMV, its M-norm
    riding one extra slot of the fused reduction payload, the window
    re-normalized exactly one queue delay (l bodies) later -- up to
    ``restart`` times per lane, with zero host round-trips.  Every lane
    (batched vmap, mesh shard, pooled) restarts independently; the
    per-iteration collective signature is unchanged (the payload widens
    from 2l+1 to 2l+2 inside the SAME reduction).  ``restart=0`` turns
    on the machinery (NaN-safe freeze, widened payload) without taking
    restarts.  ``rr_period`` (optional int >= 1) adds periodic residual
    replacement: every ``rr_period`` committed updates the lane re-seeds
    from the explicitly recomputed true residual through the same
    mechanism, resetting the rounding-error gap between the recursive
    and true residuals (arXiv:1706.05988 / 1804.02962).
    ``ritz_refresh`` (default True, only meaningful with the above)
    re-derives the l shifts at each re-seed from the Ritz values of the
    committed gamma/delta tridiagonal (Leja-ordered, Remark 3) instead
    of reusing the initial shift choice.

    ``precision`` (optional; anything ``as_precision_policy`` accepts)
    splits the state into a *storage* dtype -- the window arrays
    ``Zw``/``Vw``/``Zhw`` and the SPMV input/output stream, where the
    HBM traffic lives -- and a *compute* dtype carrying ALL scalar
    state: the gamma/delta/eta/zeta recurrences, the banded ``Gb``
    rows, the dot-product payloads and in-flight queue (hence every
    mesh collective buffer), ``x``/``p``, and the convergence/breakdown
    tests.  Casts happen at the window-write boundary only; the kernel
    tiers already load storage, accumulate in
    ``promote_types(storage, f32)`` and store back storage.  The
    default policy is bit-identical to the pre-policy engine.
    """
    if l < 1:
        raise ValueError("l must be >= 1")
    if restart is not None and int(restart) < 0:
        raise ValueError(f"restart must be >= 0, got {restart}")
    if rr_period is not None and int(rr_period) < 1:
        raise ValueError(f"rr_period must be >= 1, got {rr_period}")
    backend = resolve_backend(backend)
    use_fused = backend == "fused" and dot_local is None
    use_kernels = backend in ("pallas", "ref") and dot_local is None
    if use_kernels:
        from ..kernels.ops import multidot_apply, window_axpy_apply
        _pl = backend == "pallas"

        def _mdot(Wm, zz):
            return multidot_apply(Wm, zz, use_pallas=_pl).astype(zz.dtype)

        def _waxpy(Vm, zz, gg, gcc):
            return window_axpy_apply(Vm, zz, gg, gcc,
                                     use_pallas=_pl).astype(zz.dtype)
    if use_fused:
        from ..kernels import ops as kops
    dot = dot_local or _default_dot
    red = reduce_scalars or (lambda p: p)
    W = 2 * l + 1
    # precision policy: sdt = window/stream storage dtype, cdt = scalar
    # compute dtype.  Under the default policy both equal b.dtype and
    # every astype below is a no-op -- the graph is bit-identical to the
    # single-dtype engine.
    sdt, cdt = as_precision_policy(precision).resolve(b.dtype)
    # stability autopilot: in-scan restart / residual replacement enabled?
    stab = restart is not None or rr_period is not None
    restart_cap = int(restart) if restart is not None else 0
    rp = int(rr_period) if rr_period is not None else 0
    # the reduction payload grows by ONE slot carrying ||r_new||_M^2 of
    # re-seeding lanes (0 elsewhere) -- same collective, one wider band
    P = W + 1 if stab else W

    # ---- in-flight reduction queue (comm policy) -------------------------
    # queue_pop reads the head (the payload produced exactly l bodies ago)
    # plus, for split policies, the auxiliary value that must transit the
    # queue this body (the freshly gathered payload); queue_push shifts the
    # queue and inserts this body's payload at the tail.  Collectives live
    # ONLY inside these two closures, run unconditionally every body (the
    # freeze/convergence select gates the state commit, never the
    # collective), and the head-to-tail distance is l in every mode.
    if comm is None or comm.mode == "blocking":
        inflight0 = jnp.zeros((l, P), cdt)

        def queue_pop(q):
            return q[0], None

        def queue_push(q, payload, aux):
            del aux
            return jnp.concatenate([q[1:], red(payload)[None]], axis=0)
    elif comm.mode == "overlap":
        # scattered shards ride d slots, then (d < l) the gathered full
        # payload rides the remaining l-d: scatter at push, gather when
        # leaving the scattered stage -- the reduction is structurally in
        # flight for d bodies of local work (arXiv:1905.06850)
        d = comm.depth
        C = -(-P // comm.nshards)          # zero-padded chunk per shard

        def queue_pop(q):
            if d == l:
                return comm.finish(q[0][0], P), None
            return q[1][0], comm.finish(q[0][0], P)

        def queue_push(q, payload, aux):
            scat2 = jnp.concatenate([q[0][1:], comm.start(payload)[None]],
                                    axis=0)
            if d == l:
                return (scat2,)
            return (scat2, jnp.concatenate([q[1][1:], aux[None]], axis=0))

        inflight0 = ((jnp.zeros((d, C), cdt),) if d == l else
                     (jnp.zeros((d, C), cdt),
                      jnp.zeros((l - d, P), cdt)))
    else:                                   # ring
        # circulate-accumulate all-reduce spread across the queue shifts:
        # the element landing in slot j has completed l-1-j neighbor hops,
        # so the head (slot 0) is fully reduced iff l-1 >= len(schedule)
        # (validated at runtime construction) -- pure ppermute traffic,
        # no all-reduce primitive in the reduction (the loop's exit
        # agreement below is one scalar pmax per trip)
        from .comm import ring_hop
        sched = comm.schedule

        def queue_pop(q):
            return q[0][0], None

        def queue_push(q, payload, aux):
            del aux
            acc, circ = q
            new_a, new_c = [], []
            for j in range(l - 1):
                a, cc = acc[j + 1], circ[j + 1]
                h = l - 1 - j               # hops completed once in slot j
                if 1 <= h <= len(sched):
                    a, cc = ring_hop(sched[h - 1], a, cc)
                new_a.append(a)
                new_c.append(cc)
            new_a.append(payload)
            new_c.append(payload)
            return jnp.stack(new_a), jnp.stack(new_c)

        inflight0 = (jnp.zeros((l, P), cdt),
                     jnp.zeros((l, P), cdt))

    lanes = b.ndim == 2
    x0 = jnp.zeros_like(b) if x0 is None else x0
    sig = jnp.asarray(list(sigma), dtype=cdt)
    ncols = iters + 2 * l + 2
    n = b.shape[-1]
    # fused-tier dispatch on the preconditioner structure:
    #   fuse_diag    -- M^{-1} is a diagonal multiply (the inv_diag hint):
    #                   apply it in-kernel, staying at ONE launch/iteration;
    #   fuse_stencil -- the (K1) SPMV also runs in-kernel (stencil hint and
    #                   either no prec or a fused diagonal one);
    #   split_stencil-- general prec with a stencil hint: Pallas stencil
    #                   SPMV + megakernel, a 2-launch split.
    # With the stability autopilot the re-seed needs t_hat/t OUTSIDE the
    # kernel (the SPMV input switches to x on re-seeding lanes and the
    # true residual is assembled from t), so the fully fused SPMV and the
    # in-kernel diag apply are disabled: stencil operators take the
    # 2-launch split (Pallas stencil SPMV + megakernel) for every prec.
    fuse_diag = (use_fused and prec is not None and prec_diag is not None
                 and not stab)
    fuse_stencil = (use_fused and stencil_hw is not None
                    and (prec is None or fuse_diag) and not stab)
    split_stencil = (use_fused and stencil_hw is not None
                     and not fuse_stencil)
    if (fuse_stencil or split_stencil) and stencil_hw[0] * stencil_hw[1] != n:
        raise ValueError(f"stencil_hw {stencil_hw} inconsistent with n={n}")
    invd = None
    if fuse_diag:
        # the fused diag apply rides the storage stream (t = invd * t_hat
        # inside the kernel, f32 accumulation) -- storage dtype
        invd = jnp.asarray(prec_diag, sdt)
        if invd.ndim not in (0, 1) or (invd.ndim == 1
                                       and invd.shape[0] != n):
            raise ValueError(
                f"prec_diag must be a scalar or ({n},), got {invd.shape}")

    Gb0 = jnp.zeros((ncols, W), cdt).at[0, 2 * l].set(1.0)
    use_ritz = stab and ritz_refresh

    def init(b, x0):
        """Alg. 2 lines 1-3 for one lane: its initial state and the
        per-lane constants the bodies read."""
        x0 = x0.astype(cdt)
        bC = b.astype(cdt)   # scalar-side view of b (init/reseed residuals)
        rhat0 = bC - matvec(x0).astype(cdt)
        if prec is not None:
            with jax.named_scope("plcg.precond"):
                r0, Mb = prec(rhat0), prec(bC)
        else:
            r0, Mb = rhat0, bC
        init_pay = jnp.stack([dot(rhat0, r0), dot(bC, Mb)]).astype(cdt)
        init_pay = red(init_pay)
        beta0 = jnp.sqrt(init_pay[0])
        bnorm = jnp.sqrt(init_pay[1])
        bnorm = jnp.where(bnorm == 0, 1.0, bnorm)
        v0 = r0 / beta0

        Zw = jnp.zeros((n, l + 1), sdt).at[:, 0].set(v0.astype(sdt))
        Vw = jnp.zeros((n, W), sdt).at[:, 0].set(v0.astype(sdt))
        Zhw = (jnp.zeros((n, 3), sdt).at[:, 0].set(
            (rhat0 / beta0).astype(sdt))
            if prec is not None else jnp.zeros((1, 1), sdt))
        state = PLCGState(
            Zw=Zw, Vw=Vw, Zhw=Zhw, Gb=Gb0,
            gam=jnp.zeros(ncols, cdt), dlt=jnp.zeros(ncols, cdt),
            inflight=inflight0,
            x=x0, p=jnp.zeros_like(x0),
            eta=jnp.asarray(0.0, cdt), zeta=jnp.asarray(0.0, cdt),
            k_done=jnp.asarray(-1), done=jnp.asarray(False),
            converged=jnp.asarray(False), breakdown=jnp.asarray(False),
            ph=jnp.asarray(0, jnp.int32), wait=jnp.asarray(0, jnp.int32),
            # beta0 of the current phase; without the stability path it
            # stays the initial one
            beta=beta0,
            sig_c=(sig if use_ritz else jnp.zeros((), cdt)),
            restarts=jnp.asarray(0, jnp.int32),
            repl=jnp.asarray(0, jnp.int32),
            since_rr=jnp.asarray(0, jnp.int32),
        )
        return state, _LaneConsts(bnorm=bnorm,
                                  bC=bC if stab else None,
                                  Mb=Mb if stab else None)

    def gb_row(Gb, r):
        """Safe banded-G row read (negative rows -> zeros)."""
        row = jax.lax.dynamic_slice_in_dim(Gb, jnp.maximum(r, 0), 1, 0)[0]
        return jnp.where(r >= 0, row, jnp.zeros_like(row))

    def scalar_block(st: PLCGState, ph, c, col_in, sig_arr):
        """(K2)+(K3): finalize column c of G from the arrived payload
        ``col_in`` (the queue head popped by the caller) and update the
        gamma/delta recurrences.  O(l^2) scalar work; values are garbage
        during warmup (ph < l) and discarded by the caller's select,
        exactly like the legacy evaluate-both-phases body."""
        # -------- arrived payload = raw band of column c ------------------
        col = col_in
        # symmetric fill (eq. 14): rows c-2l+k, k<l, from earlier columns
        if exploit_symmetry:
            filled = []
            for k in range(l):
                r = c - 2 * l + k
                src = gb_row(st.Gb, c - l + k)[2 * l - k]
                use_fill = (ph >= 3 * l - 1) & (r >= 0)
                filled.append(jnp.where(use_fill, src, col[k]))
            col = jnp.concatenate([jnp.stack(filled), col[l:]])
        # -------- (K2) Gram-Schmidt correction (lines 7-8) ----------------
        rows = [gb_row(st.Gb, c - 2 * l + k) for k in range(l + 1, 2 * l)]
        col_list = [col[k] for k in range(W)]
        for k in range(l + 1, 2 * l):          # z-rows r = c-2l+k
            r = c - 2 * l + k
            grow = rows[k - (l + 1)]
            s = sum(grow[k2 - k + 2 * l] * col_list[k2] for k2 in range(k))
            denom = jnp.where(r >= 0, grow[2 * l], 1.0)
            corrected = (col_list[k] - s) / denom
            col_list[k] = jnp.where(r >= 0, corrected, col_list[k])
        arg = col_list[2 * l] - sum(col_list[k2] ** 2 for k2 in range(2 * l))
        # non-finite arg (a NaN/Inf-poisoned lane) IS a breakdown: `arg <= 0`
        # alone is False for NaN, which used to leave the lane neither
        # converging nor breaking down until the budget ran out
        brk = (arg <= 0.0) | jnp.logical_not(jnp.isfinite(arg))
        gcc = jnp.sqrt(jnp.maximum(arg, jnp.finfo(cdt).tiny))
        col_list[2 * l] = gcc
        col = jnp.stack(col_list)
        Gb2 = jax.lax.dynamic_update_slice_in_dim(st.Gb, col[None], c, 0)
        # -------- (K3) gamma_{c-1}, delta_{c-1} (lines 10-16) -------------
        rowm1 = gb_row(Gb2, c - 1)
        gd = rowm1[2 * l]                       # g_{c-1,c-1}
        g_cm1_c = col[2 * l - 1]                # g_{c-1,c}
        sub = jnp.where(c >= 2, rowm1[2 * l - 1]
                        * st.dlt[jnp.maximum(c - 2, 0)], 0.0)
        sig_c = sig_arr[jnp.clip(c - 1, 0, l - 1)]
        gam_lo = (g_cm1_c + sig_c * gd - sub) / gd
        dlt_lo = gcc / gd
        idx = jnp.maximum(c - 1 - l, 0)
        gam_hi = (gd * st.gam[idx] + g_cm1_c * st.dlt[idx] - sub) / gd
        dlt_hi = gcc * st.dlt[idx] / gd
        early = ph < 2 * l
        gam_c1 = jnp.where(early, gam_lo, gam_hi)
        dlt_c1 = jnp.where(early, dlt_lo, dlt_hi)
        gam2 = st.gam.at[jnp.maximum(c - 1, 0)].set(gam_c1)
        dlt2 = st.dlt.at[jnp.maximum(c - 1, 0)].set(dlt_c1)
        dsub = jnp.where(c >= 2, st.dlt[jnp.maximum(c - 2, 0)], 0.0)
        return col, gcc, brk, Gb2, gam2, dlt2, gam_c1, dlt_c1, dsub

    def solution_update(st: PLCGState, ph, gam2, v_k):
        """(K6) solution update (lines 22-31).  ``k_done`` counts TOTAL
        committed updates (minus one) across restart phases, so the
        committed count -- and the ``k_budget`` contract -- is global
        while ``k`` indexes the phase-local gamma/delta arrays."""
        k = ph - l
        at_first = ph == l
        eta0 = gam2[0]
        lam = jnp.where(at_first, 0.0, st.dlt[jnp.maximum(k - 1, 0)]
                        / jnp.where(st.eta == 0, 1.0, st.eta))
        dkm1 = st.dlt[jnp.maximum(k - 1, 0)]
        eta_k = jnp.where(at_first, eta0, gam2[jnp.maximum(k, 0)] - lam * dkm1)
        zeta_k = jnp.where(at_first, st.beta, -lam * st.zeta)
        x2 = jnp.where(at_first, st.x, st.x + st.zeta * st.p)
        eta_safe = jnp.where(eta_k == 0, 1.0, eta_k)
        p2 = jnp.where(at_first, v_k / eta_safe,
                       (v_k - dkm1 * st.p) / eta_safe)
        return x2, p2, eta_k, zeta_k, st.k_done + 1

    def finalize(st: PLCGState, bnorm, ph, payload, q_aux, brk, x2, p2, eta2,
                 zeta2, k2, Vw2, Zw2, Zhw2, Gb2, gam2, dlt2, *,
                 reseed_now=None, seed_now=None, beta_new=None,
                 seed_ok=None, beta2=None):
        """Queue push + convergence/freeze commit, shared by both bodies.

        With the stability autopilot the classical commit select is
        followed by explicit per-lane overlays that drive the restart
        micro-state machine: a scheduled lane runs one RESEED body (SPMV
        redirected to x, true residual stashed into the zeroed windows,
        its M-norm pushed in the extra payload slot), waits l-1 bodies
        for that reduction to transit the queue, then runs one SEED body
        (windows normalized by the arrived beta, phase counter back to
        1) -- after which the lane is bit-for-bit a fresh solve started
        at x, sharing every collective with its still-active neighbors.
        """
        with jax.named_scope("plcg.reduce"):
            inflight2 = queue_push(st.inflight, payload, q_aux)
        with jax.named_scope("plcg.update"):
            # NaN/Inf-safe breakdown: a non-finite zeta fails BOTH the old
            # convergence and breakdown predicates, silently spending the
            # whole budget -- treat it as a breakdown of this body
            brk2 = brk | ((ph >= l) & jnp.logical_not(jnp.isfinite(zeta2)))
            if stab:
                active = (st.wait == 0) & jnp.logical_not(st.done)
            else:
                active = jnp.logical_not(st.done)
            commit = active & jnp.logical_not(brk2)
            conv_now = commit & (ph >= l) & (jnp.abs(zeta2) <= tol * bnorm)
            # budget freeze: k2 + 1 updates are committed after this body
            spent = (jnp.asarray(False) if k_budget is None
                     else k2 + 1 >= k_budget)
            if stab:
                can_restart = st.restarts < restart_cap
                want_restart = brk2 & active & can_restart & ~spent
                committed_update = commit & (ph >= l)
                rr_due = ((committed_update & (st.since_rr + 1 >= rp)
                           & ~conv_now & ~spent) if rp > 0
                          else jnp.asarray(False))
                schedule = want_restart | rr_due
                # the seed body's re-seeded residual norm doubles as a
                # convergence / hard-failure probe: beta == 0 at tolerance
                # means x is (numerically) exact, non-finite beta means the
                # lane is unrecoverable
                seed_conv = (seed_now & jnp.isfinite(beta2)
                             & (jnp.sqrt(jnp.maximum(beta2, 0.0))
                                <= tol * bnorm))
                seed_fail = seed_now & ~seed_ok & ~seed_conv
                brk_term = brk2 & active & ~want_restart
                conv_now = conv_now | seed_conv
            else:
                want_restart = rr_due = seed_fail = jnp.asarray(False)
                brk_term = brk2 & active
                committed_update = commit & (ph >= l)
            done_o = st.done | brk_term | conv_now | (spent & active) | seed_fail
            converged_o = st.converged | conv_now
            breakdown_o = st.breakdown | brk_term | seed_fail
            new = PLCGState(
                Zw=Zw2, Vw=Vw2, Zhw=Zhw2, Gb=Gb2, gam=gam2, dlt=dlt2,
                inflight=inflight2, x=x2, p=p2, eta=eta2, zeta=zeta2,
                k_done=k2, done=done_o, converged=converged_o,
                breakdown=breakdown_o,
                # stab fields pass through the commit select untouched
                # (same value on both sides); their real updates are
                # overlaid below
                ph=st.ph, wait=st.wait, beta=st.beta, sig_c=st.sig_c,
                restarts=st.restarts, repl=st.repl, since_rr=st.since_rr,
            )
            out = jax.tree.map(
                lambda a_new, a_old: jnp.where(commit, a_new, a_old), new,
                st._replace(done=done_o, converged=converged_o,
                            breakdown=breakdown_o))
        if stab:
            with jax.named_scope("plcg.stab"):
                reseed_or_seed = reseed_now | seed_now
                zcol = jnp.zeros(ncols, cdt)
                out = out._replace(
                    # re-seeding lanes bypass the commit mask: the stashed
                    # / seeded windows (already selected in the body) land,
                    # the banded G and the recurrences reset to the init
                    # state
                    Zw=jnp.where(reseed_or_seed, Zw2, out.Zw),
                    Vw=jnp.where(reseed_or_seed, Vw2, out.Vw),
                    Zhw=(jnp.where(reseed_or_seed, Zhw2, out.Zhw)
                         if prec is not None else out.Zhw),
                    Gb=jnp.where(reseed_now, Gb0, out.Gb),
                    gam=jnp.where(reseed_now, zcol, out.gam),
                    dlt=jnp.where(reseed_now, zcol, out.dlt),
                    p=jnp.where(reseed_now, jnp.zeros_like(st.p), out.p),
                    eta=jnp.where(reseed_now, 0.0, out.eta),
                    zeta=jnp.where(reseed_now, 0.0, out.zeta),
                    # the queue ALWAYS shifts: the re-seed reduction must
                    # transit it, and frozen lanes only ever push into it
                    inflight=inflight2,
                    wait=jnp.where(reseed_now, l,
                                   jnp.where(seed_now, 0,
                                             jnp.where(st.wait > 1,
                                                       st.wait - 1,
                                                       jnp.where(schedule,
                                                                 l + 1, 0)))
                                   ).astype(st.wait.dtype),
                    # the seed body IS body 0 of the new phase
                    ph=jnp.where(seed_now, 1,
                                 jnp.where(commit, ph + 1, ph)
                                 ).astype(st.ph.dtype),
                    beta=jnp.where(seed_now, beta_new, st.beta),
                    restarts=(st.restarts
                              + want_restart.astype(st.restarts.dtype)),
                    repl=st.repl + rr_due.astype(st.repl.dtype),
                    since_rr=jnp.where(seed_now, 0,
                                       st.since_rr
                                       + committed_update.astype(
                                           st.since_rr.dtype)
                                       ).astype(st.since_rr.dtype),
                )
                if use_ritz:
                    # Ritz-refresh the shifts from the tail of the
                    # COMMITTED tridiagonal of the phase that just ended
                    # (harvested at the reseed body, before gamma/delta
                    # reset): Leja-ordered eigenvalues of the MR x MR
                    # trailing block (Remark 3)
                    from .shifts import leja_order, ritz_values_from_tridiag
                    MR = min(max(4, 2 * l), ncols)
                    m = ph - l                # committed columns this phase
                    lo = jnp.clip(m - MR, 0, ncols - MR)
                    gw = jax.lax.dynamic_slice_in_dim(st.gam, lo, MR)
                    dw = jax.lax.dynamic_slice_in_dim(st.dlt, lo, MR)
                    okr = (reseed_now & (m >= MR)
                           & jnp.all(jnp.isfinite(gw))
                           & jnp.all(jnp.isfinite(dw)))
                    gw = jnp.where(okr, gw, 1.0)   # sanitized -> T = I
                    dw = jnp.where(okr, dw, 0.0)
                    sig_new = leja_order(ritz_values_from_tridiag(gw, dw), l)
                    out = out._replace(
                        sig_c=jnp.where(okr, sig_new.astype(cdt), st.sig_c))
        with jax.named_scope("plcg.update"):
            res = jnp.where(committed_update, jnp.abs(zeta2), 0.0)
        return out, (res, committed_update)

    def stab_ctx(st: PLCGState, i):
        """Per-body restart micro-state: phase counter, reseed/seed masks,
        and the SPMV input (redirected to x on the reseed body so the
        body's ONE operator apply recomputes the true residual)."""
        if not stab:
            return (i, jnp.asarray(False), jnp.asarray(False), st.Zw[:, 0],
                    sig)
        with jax.named_scope("plcg.stab"):
            reseed_now = st.wait == l + 1
            seed_now = st.wait == 1
            spmv_in = jnp.where(reseed_now, st.x, st.Zw[:, 0])
        sig_arr = st.sig_c if use_ritz else sig
        return st.ph, reseed_now, seed_now, spmv_in, sig_arr

    def stab_seed(st: PLCGState, lc: _LaneConsts, t, t_hat, col_in_full,
                  reseed_now, seed_now, sig_arr):
        """Reseed stash + seed re-normalization values (stab only).

        Reseed body: t_hat = A x, so the true residual is rhat = b - t_hat
        and its preconditioned twin r = M b - t by linearity -- zero extra
        operator/preconditioner applies.  The windows are stashed with the
        UN-normalized residual; its M-norm^2 rides payload slot W through
        the same reduction as every other dot and arrives -- like any
        payload -- exactly l bodies later, at the seed body, which
        normalizes the stash into the init-state windows of a fresh solve
        started at x.
        """
        rhat_new = lc.bC - t_hat.astype(cdt)
        r_new = (lc.Mb - t.astype(cdt)) if prec is not None else rhat_new
        slotW = jnp.where(reseed_now, dot(rhat_new, r_new).astype(cdt),
                          jnp.asarray(0.0, cdt))
        beta2 = col_in_full[W]
        seed_ok = (beta2 > 0) & jnp.isfinite(beta2)
        beta_new = jnp.sqrt(jnp.where(seed_ok, beta2, 1.0))
        inv_b = 1.0 / beta_new
        # seed body: the stash held r_new in Zw slot 0 (rhat_new in Zhw),
        # and this body's SPMV ran on it, so t/t_hat are beta * (M)A v0
        v0n = st.Zw[:, 0] * inv_b
        s0 = sig_arr[0]
        zn_seed = t * inv_b - s0 * v0n
        Zw_sd = (jnp.zeros_like(st.Zw).at[:, 0].set(zn_seed.astype(sdt))
                 .at[:, 1].set(v0n.astype(sdt)))
        Vw_sd = jnp.zeros_like(st.Vw).at[:, 0].set(v0n.astype(sdt))
        Zw_st = jnp.zeros_like(st.Zw).at[:, 0].set(r_new.astype(sdt))
        Vw_st = jnp.zeros_like(st.Vw)
        if prec is not None:
            zh0n = st.Zhw[:, 0] * inv_b
            zhn_seed = t_hat * inv_b - s0 * zh0n
            Zhw_sd = (jnp.zeros_like(st.Zhw).at[:, 0]
                      .set(zhn_seed.astype(sdt))
                      .at[:, 1].set(zh0n.astype(sdt)))
            Zhw_st = jnp.zeros_like(st.Zhw).at[:, 0].set(rhat_new.astype(sdt))
        else:
            Zhw_sd = Zhw_st = None

        def sel3(seeded, stash, normal):
            return jnp.where(seed_now, seeded,
                             jnp.where(reseed_now, stash, normal))

        return (slotW, beta2, seed_ok, beta_new, sel3,
                (Vw_sd, Zw_sd, Zhw_sd), (Vw_st, Zw_st, Zhw_st))

    def body(carry, i):
        # each phase runs under a named scope, so every HLO op of the body
        # carries its phase in its op_name metadata (plcg.spmv, .reduce,
        # .scalars, .recur, .dots, .update, .stab)
        st, lc = carry
        ph, reseed_now, seed_now, spmv_in, sig_arr = stab_ctx(st, i)
        # ---------------- (K1) SPMV --------------------------------------
        # SPMV arithmetic runs in the compute dtype (on a mesh this keeps
        # halo-exchange payloads cdt); the resulting t / t_hat STREAMS
        # are storage-dtype, rounded once -- exactly what the fused
        # megakernel tier stores.  Identity casts under the default policy.
        with jax.named_scope("plcg.spmv"):
            t_hat = matvec(spmv_in.astype(cdt)).astype(sdt)
        if prec is not None:
            with jax.named_scope("plcg.precond"):
                t = prec(t_hat).astype(sdt)
        else:
            t = t_hat
        # pop AFTER the SPMV + shard-local preconditioner apply in trace
        # order: with a split comm policy the head-of-queue gather is
        # issued here with no data dependence on t, so the prec apply is
        # free to overlap the in-flight reduction (paper Remark 13)
        with jax.named_scope("plcg.reduce"):
            col_in, q_aux = queue_pop(st.inflight)
        col_in_full, col_in = col_in, (col_in[:W] if stab else col_in)

        c = ph - l + 1                      # column being finalized

        def warmup(_):
            with jax.named_scope("plcg.recur"):
                s = sig_arr[jnp.minimum(ph, l - 1)]
                znew = t - s * st.Zw[:, 0]
                zhnew = ((t_hat - s * st.Zhw[:, 0]) if prec is not None
                         else None)
            return (st.Vw, st.Gb, st.gam, st.dlt, znew, zhnew,
                    jnp.asarray(False), st.x, st.p, st.eta, st.zeta,
                    st.k_done)

        def steady(_):
            with jax.named_scope("plcg.scalars"):
                (col, gcc, brk, Gb2, gam2, dlt2, gam_c1, dlt_c1,
                 dsub) = scalar_block(st, ph, c, col_in, sig_arr)
            with jax.named_scope("plcg.recur"):
                # -------- (K4) v recurrence (line 17) ---------------------
                # v_c = (z_c - sum_k col[k] v_{c-2l+k}) / gcc ;
                # v_{c-2l+k} = Vw[:, 2l-1-k]
                if use_kernels:
                    vnew = _waxpy(st.Vw[:, :2 * l], st.Zw[:, l - 1],
                                  col[:2 * l][::-1], gcc)
                else:
                    vsum = st.Vw[:, :2 * l] @ col[:2 * l][::-1]
                    vnew = (st.Zw[:, l - 1] - vsum) / gcc
                Vw2 = jnp.concatenate([vnew.astype(sdt)[:, None],
                                       st.Vw[:, :-1]], axis=1)
                # -------- (K4) z recurrence (line 18) ---------------------
                znew = ((t - gam_c1 * st.Zw[:, 0] - dsub * st.Zw[:, 1])
                        / dlt_c1)
                zhnew = ((t_hat - gam_c1 * st.Zhw[:, 0]
                          - dsub * st.Zhw[:, 1]) / dlt_c1
                         if prec is not None else None)
            # -------- (K6) solution update (lines 22-31) ------------------
            with jax.named_scope("plcg.update"):
                x2, p2, eta_k, zeta_k, k2 = solution_update(st, ph, gam2,
                                                            Vw2[:, 1])
            return (Vw2, Gb2, gam2, dlt2, znew, zhnew, brk,
                    x2, p2, eta_k, zeta_k, k2)

        # compute both phases and select on the (scalar) iteration index:
        # an actual lax.cond here lowers to an XLA Conditional whose branch
        # layouts clash with the matvec dot on the CPU thunk runtime when
        # the engine runs under vmap (batched multi-RHS); warmup is two
        # AXPYs so evaluating it alongside steady costs nothing, and the
        # discarded branch's values (incl. div-by-zero garbage during the
        # first l iterations) are dropped by the select
        stdy, warm = steady(None), warmup(None)
        with jax.named_scope("plcg.recur"):
            (Vw2, Gb2, gam2, dlt2, znew, zhnew, brk, x2, p2, eta2, zeta2,
             k2) = jax.tree.map(
                functools.partial(jnp.where, ph >= l), stdy, warm)

            Zw2 = jnp.concatenate([znew.astype(sdt)[:, None],
                                   st.Zw[:, :-1]], axis=1)
            Zhw2 = (jnp.concatenate([zhnew.astype(sdt)[:, None],
                                     st.Zhw[:, :-1]], axis=1)
                    if prec is not None else st.Zhw)
        # payload dots consume the pre-rounding compute-dtype lhs; only
        # the stored window is quantized to sdt
        lhs = zhnew if prec is not None else znew
        seed_kw = {}
        ph_pay = ph
        if stab:
            with jax.named_scope("plcg.stab"):
                (slotW, beta2, seed_ok, beta_new, sel3, seeded,
                 stash) = stab_seed(st, lc, t, t_hat, col_in_full,
                                    reseed_now, seed_now, sig_arr)
                # window selection BEFORE the payload dots so re-seeding
                # lanes push dots of the stashed/seeded windows through the
                # shared reduction (the seed body's payload IS fresh body
                # 0's)
                Vw2 = sel3(seeded[0], stash[0], Vw2)
                Zw2 = sel3(seeded[1], stash[1], Zw2)
                if prec is not None:
                    Zhw2 = sel3(seeded[2], stash[2], Zhw2)
                lhs = (Zhw2[:, 0] if prec is not None
                       else Zw2[:, 0]).astype(cdt)
                ph_pay = jnp.where(seed_now, 0, ph)
            seed_kw = dict(reseed_now=reseed_now, seed_now=seed_now,
                           beta_new=beta_new, seed_ok=seed_ok, beta2=beta2)
        # ---------------- (K5) dot-product payload for column i+1 --------
        with jax.named_scope("plcg.dots"):
            if exploit_symmetry:
                def vdots_full(_):
                    if use_kernels:
                        return _mdot(Vw2[:, :l + 1], lhs)
                    return lhs @ Vw2[:, :l + 1]

                def vdots_one(_):
                    out = jnp.zeros(l + 1, cdt)
                    return out.at[0].set(dot(Vw2[:, 0], lhs).astype(cdt))

                vd = jax.lax.cond(ph_pay < 2 * l - 1, vdots_full, vdots_one,
                                  None)
            elif use_kernels:
                vd = _mdot(Vw2[:, :l + 1], lhs)
            else:
                vd = jnp.stack([dot(Vw2[:, j], lhs) for j in range(l + 1)])
            if use_kernels:
                zd = _mdot(Zw2[:, :l], lhs)
            else:
                zd = jnp.stack([dot(Zw2[:, j], lhs) for j in range(l)])
            # mask payload slots whose row index i+1-2l+k is negative (the
            # v window is zero-initialized except v_0, which must not leak
            # into nonexistent rows during warmup)
            vmask = jnp.arange(l + 1) + (ph_pay + 1 - 2 * l) >= 0
            payload = jnp.concatenate([vd[::-1] * vmask, zd[::-1]])  # band
            if stab:
                payload = jnp.concatenate([payload, slotW[None]])
        out, per_body = finalize(st, lc.bnorm, ph, payload, q_aux, brk, x2,
                                 p2, eta2, zeta2, k2, Vw2, Zw2, Zhw2, Gb2,
                                 gam2, dlt2, **seed_kw)
        return (out, lc), per_body

    def body_fused(carry, i):
        """One launch per iteration: the fused_body megakernel computes
        (K1 when the stencil is fused) + (K4) + (K5); only the O(l^2)
        scalar recurrences (K2/K3/K6) stay in jnp.  With the stability
        autopilot the SPMV and preconditioner run OUTSIDE the kernel (the
        re-seed needs t/t_hat to assemble the true residual) and the
        payload dots are recomputed from the re-seed-selected windows --
        a documented small overhead of restart-enabled fused sweeps."""
        st, lc = carry
        ph, reseed_now, seed_now, spmv_in, sig_arr = stab_ctx(st, i)
        c = ph - l + 1
        with jax.named_scope("plcg.reduce"):
            col_in, q_aux = queue_pop(st.inflight)
        col_in_full, col_in = col_in, (col_in[:W] if stab else col_in)
        with jax.named_scope("plcg.scalars"):
            (col, gcc, brk, Gb2, gam2, dlt2, gam_c1, dlt_c1,
             dsub) = scalar_block(st, ph, c, col_in, sig_arr)
        with jax.named_scope("plcg.spmv"):
            if fuse_stencil:
                # in-kernel SPMV (+ in-kernel diag apply when
                # preconditioned)
                t = t_hat = None
            elif split_stencil:
                # stencil hint without full fusion: (K1) as the Pallas
                # stencil kernel (launch 1 of the 2-launch split), prec
                # applied between the launches
                H2d, W2d = stencil_hw
                z2d = spmv_in.reshape(H2d, W2d)
                zr = jnp.zeros_like
                t_hat = kops.stencil2d_apply(
                    z2d, zr(z2d[0]), zr(z2d[0]), zr(z2d[:, 0]),
                    zr(z2d[:, 0]), use_pallas=True).reshape(-1)
                t = t_hat
            else:
                # compute-dtype SPMV, storage-dtype streams (see body())
                t_hat = matvec(spmv_in.astype(cdt)).astype(sdt)
                # with fuse_diag the kernel applies invd to t_hat
                t = None if fuse_diag else t_hat
        if prec is not None and t is not None:
            with jax.named_scope("plcg.precond"):
                t = prec(t_hat).astype(sdt)
        with jax.named_scope("plcg.fused"):
            Vw2, Zw2, Zhw2k, dots = kops.fused_body_apply(
                st.Vw, st.Zw, st.Zhw if prec is not None else None,
                t, t_hat if prec is not None else None,
                l=l, steady=ph >= l,
                s_warm=sig_arr[jnp.minimum(ph, l - 1)],
                gam=gam_c1, dlt=dlt_c1, dsub=dsub, gcc=gcc,
                g=col[:2 * l][::-1], invd=invd,
                stencil_hw=stencil_hw if fuse_stencil else None,
                use_pallas=True)
        Zhw2 = Zhw2k if prec is not None else st.Zhw
        dots = dots.astype(cdt)
        vd_full, zd = dots[:l + 1], dots[l + 1:]
        with jax.named_scope("plcg.update"):
            x2, p2, eta_k, zeta_k, k2 = solution_update(st, ph, gam2,
                                                        Vw2[:, 1])
            # warmup select for the scalar state only -- the vector windows
            # were already phase-selected inside the kernel
            (Gb2, gam2, dlt2, brk, x2, p2, eta2, zeta2, k2) = jax.tree.map(
                functools.partial(jnp.where, ph >= l),
                (Gb2, gam2, dlt2, brk, x2, p2, eta_k, zeta_k, k2),
                (st.Gb, st.gam, st.dlt, jnp.asarray(False), st.x, st.p,
                 st.eta, st.zeta, st.k_done))
        seed_kw = {}
        ph_pay = ph
        if stab:
            with jax.named_scope("plcg.stab"):
                (slotW, beta2, seed_ok, beta_new, sel3, seeded,
                 stash) = stab_seed(st, lc, t, t_hat, col_in_full,
                                    reseed_now, seed_now, sig_arr)
                Vw2 = sel3(seeded[0], stash[0], Vw2)
                Zw2 = sel3(seeded[1], stash[1], Zw2)
                if prec is not None:
                    Zhw2 = sel3(seeded[2], stash[2], Zhw2)
                # recompute the payload from the selected windows: the
                # in-kernel dots saw the pre-selection windows
                lhs = (Zhw2[:, 0] if prec is not None
                       else Zw2[:, 0]).astype(cdt)
                vd_full = lhs @ Vw2[:, :l + 1]
                zd = lhs @ Zw2[:, :l]
                ph_pay = jnp.where(seed_now, 0, ph)
            seed_kw = dict(reseed_now=reseed_now, seed_now=seed_now,
                           beta_new=beta_new, seed_ok=seed_ok, beta2=beta2)
        with jax.named_scope("plcg.dots"):
            if exploit_symmetry:
                # mirror the legacy single-dot branch: beyond the startup
                # phase only <v_{i+1-2l}, z> is new, the rest comes from
                # the symmetric fill of (K2)
                vd = jnp.where(ph_pay < 2 * l - 1, vd_full,
                               jnp.zeros_like(vd_full).at[0].set(vd_full[0]))
            else:
                vd = vd_full
            vmask = jnp.arange(l + 1) + (ph_pay + 1 - 2 * l) >= 0
            payload = jnp.concatenate([vd[::-1] * vmask, zd[::-1]])
            if stab:
                payload = jnp.concatenate([payload, slotW[None]])
        out, per_body = finalize(st, lc.bnorm, ph, payload, q_aux, brk, x2,
                                 p2, eta2, zeta2, k2, Vw2, Zw2, Zhw2, Gb2,
                                 gam2, dlt2, **seed_kw)
        return (out, lc), per_body

    agree = None
    if comm is not None and comm.mode == "ring":
        # ring devices accumulate the payload in their own order, so their
        # done flags need not agree bit for bit: keep every device looping
        # while any of them has a live lane
        ring_axes = tuple(dict.fromkeys(hop[0] for hop in comm.schedule))
        if ring_axes:
            def agree(alive):
                return jax.lax.pmax(alive.astype(jnp.int32), ring_axes) > 0
    body_fn = body_fused if use_fused else body
    if lanes:
        carry = jax.vmap(init)(b, x0)
        body_fn = jax.vmap(body_fn, in_axes=(0, None))
    else:
        carry = init(b, x0)
    trips, (final, _), resnorms, committed = _run_bodies(
        body_fn, carry, iters=iters, unroll=unroll, dtype=cdt, agree=agree)
    if lanes:
        resnorms, committed = resnorms.T, committed.T
        trips = jnp.full(b.shape[:1], trips)
    return PLCGOut(x=final.x, resnorms=resnorms, k_done=final.k_done,
                   converged=final.converged, breakdown=final.breakdown,
                   committed=committed, restarts=final.restarts,
                   replacements=final.repl, trips=trips)


def _run_bodies(body, carry, *, iters: int, unroll: int, dtype, agree=None):
    """Drive ``body(carry, i) -> (carry, (resnorm, committed))`` in a
    ``lax.while_loop`` until every lane of ``carry[0].done`` is set or
    ``iters`` bodies have run.

    Each trip runs ``unroll`` bodies back to back and then re-evaluates
    the exit (``agree``, when given, maps the local "some lane is still
    live" flag to one every device shares); a second loop of single
    bodies runs the ``iters % unroll`` that do not fill a trip, so no
    body past ``iters`` ever runs.  The per-body outputs land in
    preallocated ``(iters, *lanes)`` buffers, zero / False where no body
    ran.  Returns ``(trips as int32, carry, resnorms, committed)``."""
    k = max(1, int(unroll))
    lane_shape = carry[0].done.shape
    res0 = jnp.zeros((iters,) + lane_shape, dtype)
    com0 = jnp.zeros((iters,) + lane_shape, bool)

    def trip(width):
        def run(loop):
            i, _, c, res, com = loop
            for u in range(width):
                c, (r, m) = body(c, i + u)
                res = jax.lax.dynamic_update_index_in_dim(res, r, i + u, 0)
                com = jax.lax.dynamic_update_index_in_dim(com, m, i + u, 0)
            alive = jnp.logical_not(jnp.all(c[0].done))
            if agree is not None:
                alive = agree(alive)
            return i + width, alive, c, res, com
        return run

    def fits(width):
        return lambda loop: (loop[0] + width <= iters) & loop[1]

    # the body index has the dtype jnp.arange would give a scan
    loop = (jnp.asarray(0), jnp.asarray(True), carry, res0, com0)
    loop = jax.lax.while_loop(fits(k), trip(k), loop)
    if k > 1 and iters % k:
        loop = jax.lax.while_loop(fits(1), trip(1), loop)
    i, _, carry, res, com = loop
    return i.astype(jnp.int32), carry, res, com


def plcg_jit(matvec, b, x0=None, *, l, iters, sigma, tol=0.0, prec=None,
             prec_diag=None, exploit_symmetry: bool = True, unroll: int = 1,
             backend: Optional[str] = None,
             stencil_hw: Optional[tuple] = None,
             restart: Optional[int] = None,
             rr_period: Optional[int] = None,
             ritz_refresh: bool = True, precision=None) -> PLCGOut:
    """Convenience jitted single-device entry point."""
    fn = functools.partial(
        plcg_scan, matvec, l=l, iters=iters, sigma=tuple(sigma), tol=tol,
        prec=prec, prec_diag=prec_diag,
        exploit_symmetry=exploit_symmetry, unroll=unroll,
        backend=backend, stencil_hw=stencil_hw,
        restart=restart, rr_period=rr_period, ritz_refresh=ritz_refresh,
        precision=precision)
    return jax.jit(lambda bb, xx: fn(bb, xx))(b, x0 if x0 is not None
                                              else jnp.zeros_like(b))


def stab_iter_slack(l: int, restart=None, rr_period=None,
                    maxiter: int = 0) -> int:
    """Extra scan bodies needed so a ``maxiter``-update budget stays
    spendable despite re-seed dead bodies: each restart / residual
    replacement event costs at most 2l+2 bodies that commit nothing
    (the triggering body, the reseed body, l-1 waiting bodies, the seed
    body, and the l-1 new warmup bodies overlap this bound)."""
    slack = 0
    if restart:
        slack += int(restart) * (2 * l + 2)
    if rr_period and maxiter:
        slack += (int(maxiter) // int(rr_period)) * (2 * l + 2)
    return slack


#: Jitted single-RHS sweeps, keyed weakly on the operator/preconditioner
#: callables (see solver_cache): dropping the operator releases the
#: compiled sweep instead of pinning it until 16 other configs evict it.
_SWEEP_CACHE = WeakCallableCache(maxsize=16)


def _jitted_sweep(matvec, l, iters, sigma, tol, prec, exploit_symmetry,
                  unroll, backend, stencil_hw, restart=None, rr_period=None,
                  ritz_refresh=True, precision=None, bindable=False):
    """Cached jitted single sweep so repeated solves with the same
    operator/settings compile once.  Keyed on ``matvec``/``prec`` object
    identity through weak references: reuse the same callable across calls
    to benefit (a fresh closure per call compiles, is cached until its
    closure dies, then is evicted -- no unbounded retention).

    The returned callable takes ``(b, x0, k_budget)``: the budget is a
    traced operand, so restart sweeps with shrinking budgets reuse the
    one compiled program.

    ``bindable=True`` interprets ``matvec`` as a two-argument
    ``matvec_ctx(context, v)`` (see :class:`~repro.core.linop.
    BindableOperator`) and the returned callable takes
    ``(context, b, x0, k_budget)``: the context pytree is a TRACED
    leading operand, so rebinding operator data (new parameters, new
    batch) between outer steps reuses the one compiled program.
    """

    def build():
        mv = weakly_callable(matvec)
        kwargs = dict(
            l=l, iters=iters, sigma=sigma, tol=tol,
            prec=weakly_callable(prec),
            # fusion hint of a structured Preconditioner (None for bare
            # callables); the captured array does not pin the object
            prec_diag=getattr(prec, "inv_diag", None),
            exploit_symmetry=exploit_symmetry, unroll=unroll,
            backend=backend, stencil_hw=stencil_hw,
            restart=restart, rr_period=rr_period, ritz_refresh=ritz_refresh,
            precision=precision)
        if bindable:
            return jax.jit(lambda ctx, bb, xx, kb: plcg_scan(
                lambda v: mv(ctx, v), bb, xx, k_budget=kb, **kwargs))
        fn = functools.partial(plcg_scan, mv, **kwargs)
        return jax.jit(lambda bb, xx, kb: fn(bb, xx, k_budget=kb))

    return _SWEEP_CACHE.get_or_build(
        (matvec, prec),
        (l, iters, sigma, tol, exploit_symmetry, unroll, backend,
         stencil_hw, restart, rr_period, ritz_refresh,
         as_precision_policy(precision), bindable),
        build)


def count_bodies(trips, l: int, k_done, committed=None,
                 lanes: Optional[int] = None, prec: bool = False) -> None:
    """Add one sweep's bodies to the open root span's counters
    (``repro.core.telemetry``).

    ``bodies`` sums ``trips``, the fetched trip count the sweep returns
    (``PLCGOut.trips``: a scalar, or one per lane of a batch), over every
    lane.  ``useful`` sums, over the first ``lanes`` lanes (all by
    default; the rest are padding), the body index of each lane's last
    committed update plus one: from the fetched ``committed`` mask where
    the caller has it, else ``l + k_done + 1`` (update k commits at body
    l + k).  With a preconditioner (``prec``) ``precond_applies`` adds
    the applies the sweep ran: two in its init (``M r0`` and ``M b``; an
    in-scan re-seed reuses the stashed ``M b``) and one a body, frozen or
    not, on every lane."""
    if committed is not None:
        m = np.asarray(committed, dtype=bool)
        m = m.reshape(-1, m.shape[-1])
        last = np.where(m.any(axis=1),
                        m.shape[-1] - np.argmax(m[:, ::-1], axis=1), 0)
    else:
        last = l + np.asarray(k_done).reshape(-1) + 1
    telemetry.count("bodies", int(np.sum(trips)))
    telemetry.count("useful", int(last[:lanes].sum()))
    if prec:
        telemetry.count("precond_applies",
                        int(np.sum(np.asarray(trips) + 2)))


def read_batched(out, *, l: int, stab: bool, lanes: Optional[int] = None,
                 prec: bool = False):
    """The host side of one batched sweep: ``out`` holds its device
    ``(resnorms, converged, breakdown, k_done, committed, restarts,
    replacements, trips)``, each lane's row first.  Reads them in that
    order, one ``plcg.fetch`` each (``committed`` / ``restarts`` /
    ``replacements`` only on the in-scan ``stab`` path; ``trips`` rides
    the ``k_done`` read), counts the sweep's bodies
    (:func:`count_bodies`; lanes past the first ``lanes`` are padding,
    ``prec`` says whether the sweep applied a preconditioner) and
    builds each lane's residual history in a ``plcg.unpack`` span.
    Returns ``(resnorms lists, converged, breakdown, k_done, restarts,
    replacements)`` on the host."""
    resn, conv, brk, k_done, committed, restarts, repl, trips = out
    resn = telemetry.fetch(resn, "resnorms")            # (nrhs, iters)
    conv = telemetry.fetch(conv, "converged")
    brk = telemetry.fetch(brk, "breakdown")
    k_done, trips = telemetry.fetch((k_done, trips), "k_done")
    if stab:
        # restart / replacement dead bodies interleave with committed
        # updates, so the in-order residual history is the committed mask
        # (not a contiguous count slice)
        committed = telemetry.fetch(committed, "committed", dtype=bool)
        restarts = telemetry.fetch(restarts, "restarts")
        repl = telemetry.fetch(repl, "replacements")
        count_bodies(trips, l, k_done, committed=committed, lanes=lanes,
                     prec=prec)
        with telemetry.span("plcg.unpack"):
            resnorms = [[float(r) for r in row[m]]
                        for row, m in zip(resn, committed)]
        return resnorms, conv, brk, k_done, restarts, repl
    count_bodies(trips, l, k_done, lanes=lanes, prec=prec)
    # lane j commits |zeta_k| for k = 0..k_done[j] at trace indices
    # l..l+k_done[j]; slicing by count (not value-filtering) keeps a
    # legitimate exact-zero residual in the trace
    with telemetry.span("plcg.unpack"):
        resnorms = [[float(r) for r in row[l: l + int(k) + 1]]
                    for row, k in zip(resn, k_done)]
    zeros = np.zeros(conv.shape[0], dtype=int)
    return resnorms, conv, brk, k_done, zeros, zeros


def run_restart_driver(sweep, b, x0, *, tol: float, maxiter: int,
                       max_restarts: int, bnorm: float, l: int,
                       in_scan: bool = False, program=None,
                       prec: bool = False):
    """Restart-on-breakdown with a global iteration budget (paper
    Remark 8), shared by the single-device and mesh drivers -- the ONE
    place restart semantics (budget accounting, happy breakdown,
    info packaging) is defined.

    ``in_scan=True`` (the default execution mode of the engine front
    ends) runs ONE sweep that was built with ``restart=``/``rr_period=``
    -- breakdown recovery happens per lane inside the compiled scan
    (Ritz-refreshed shifts, zero host round-trips) and this wrapper only
    unpacks the result.  ``sweep(b, x, budget)`` returns ``(x, resnorms,
    converged, breakdown, k_done, committed, restarts, replacements,
    trips)`` on either path, ``trips`` being the bodies it ran.

    ``in_scan=False`` is the legacy host loop retained for parity
    testing and as a compatibility escape hatch: the sweep is re-entered
    from the host after each breakdown with the *remaining* budget.
    .. deprecated:: its shift-free re-init (the restarted sweep reuses
       the original sigma instead of Ritz-refreshing) and its
       single-RHS-only reach are superseded by the in-scan path.
    The loop reads ``x``, ``resnorms``, ``k_done`` (with ``trips``) and
    ``converged`` (with ``breakdown``) of each pass.

    Either way a breakdown-looping system performs at most ``maxiter``
    updates in total (not ``max_restarts x maxiter``); happy breakdown
    at tolerance counts as convergence.  Returns
    ``(x, resnorms list, info dict)``.

    Every pass is a ``plcg.dispatch`` of ``sweep`` (``program``, the
    jitted callable it runs, tells whether the call compiled; ``sweep``
    itself by default), a ``plcg.wait`` and one ``plcg.fetch`` per read
    of an output (``trips`` rides the ``k_done`` read), and counts its
    bodies (:func:`count_bodies`, depth ``l``; ``prec`` says whether the
    sweep applies a preconditioner) on the open root span.
    """
    if in_scan:
        out = telemetry.dispatch(sweep, b, x0, maxiter, program=program)
        telemetry.wait(out)
        (x, resn, conv, brk, k_done, committed, n_restarts,
         n_repl, trips) = out
        mask = telemetry.fetch(committed, "committed", dtype=bool)
        resn_h = telemetry.fetch(resn, "resnorms")
        converged = bool(telemetry.fetch(conv, "converged"))
        breakdown = bool(telemetry.fetch(brk, "breakdown"))
        n_restarts = int(telemetry.fetch(n_restarts, "restarts"))
        n_repl = int(telemetry.fetch(n_repl, "replacements"))
        k_done, trips = telemetry.fetch((k_done, trips), "k_done")
        k_done = int(k_done)
        count_bodies(trips, l, k_done, committed=mask, prec=prec)
        with telemetry.span("plcg.unpack"):
            resnorms = [float(r) for r in resn_h[mask]]
        if (not converged and breakdown and resnorms
                and resnorms[-1] <= 4 * tol * bnorm):
            converged = True              # happy breakdown at tolerance
        return x, resnorms, {
            "converged": converged,
            "breakdowns": n_restarts + int(breakdown),
            "restarts": n_restarts,
            "replacements": n_repl,
            "iterations": k_done + 1,
        }
    x = x0
    # every (re-)entry must present the SAME placement to hit one
    # compiled program: a restart re-enters with the previous sweep's
    # OUTPUT -- committed, and on a mesh operator-sharded -- while x0's
    # placement is whatever the caller chose, and both committedness
    # and sharding key the jit cache.  Pin every entry to x0's sharding,
    # but ONLY when x0 is itself committed: an uncommitted x0 (host-
    # built zeros) has a default single-device sharding that is not an
    # intended placement, and committing x to it would conflict with a
    # mesh sweep's shard_map
    x0_sharding = (getattr(x0, "sharding", None)
                   if getattr(x0, "_committed", False) else None)
    resnorms: list[float] = []
    restarts = breakdowns = 0
    total_k = 0
    converged = False
    while total_k < maxiter:
        remaining = maxiter - total_k
        if x0_sharding is not None:
            with telemetry.span("plcg.prepare"):
                x = jax.device_put(x, x0_sharding)
        out = telemetry.dispatch(sweep, b, x, remaining, program=program)
        telemetry.wait(out)
        x, resn, conv, brk, k_done = out[:5]
        resn_h = telemetry.fetch(resn, "resnorms")
        with telemetry.span("plcg.unpack"):
            resnorms.extend(float(r) for r in resn_h if r > 0)
        k, trips = telemetry.fetch((k_done, out[8]), "k_done")
        k = int(k)
        count_bodies(trips, l, k, prec=prec)
        total_k += max(k + 1, 1)
        # one read for both flags: a solve that stops on its budget (as
        # with tol=0) needs the breakdown flag too
        conv, brk = telemetry.fetch((conv, brk), "converged")
        if bool(conv):
            converged = True
            break
        if bool(brk):
            breakdowns += 1
            if resnorms and resnorms[-1] <= 4 * tol * bnorm:
                converged = True          # happy breakdown at tolerance
                break
            if restarts >= max_restarts:
                break
            restarts += 1
            continue
        break                             # iteration budget exhausted
    return x, resnorms, {
        "converged": converged, "breakdowns": breakdowns,
        "restarts": restarts, "replacements": 0, "iterations": total_k,
    }


def plcg_solve(matvec, b, x0=None, *, l, sigma, tol=1e-8, maxiter=1000,
               prec=None, exploit_symmetry: bool = True, max_restarts: int = 5,
               unroll: int = 1, backend: Optional[str] = None,
               stencil_hw: Optional[tuple] = None, sweep=None,
               restart: Optional[int] = None,
               residual_replacement: Optional[int] = None,
               ritz_refresh: bool = True, precision=None, context=None):
    """Driver around the jitted engine: explicit restart on square-root
    breakdown (paper Remark 8), happy-breakdown detection, and a GLOBAL
    iteration budget across restart sweeps (via the sweep's ``k_budget``
    operand -- one compiled program regardless of restarts).

    ``restart``/``residual_replacement`` (either not None) switch to the
    IN-SCAN stability path: one sweep whose lanes re-seed themselves on
    breakdown (up to ``restart`` times, shifts Ritz-refreshed unless
    ``ritz_refresh=False``) and/or every ``residual_replacement``
    committed updates; ``max_restarts`` is ignored there.  With both
    None the legacy host restart loop runs (see ``run_restart_driver``).

    ``sweep`` (optional) is a pre-built jitted ``(b, x0, k_budget)``
    sweep -- a prepared ``repro.core.session.Solver`` passes the one it
    holds strongly, so the per-call weak-cache lookup (and any rebuild)
    is skipped; it must have been built with the same
    tol/sigma/backend/restart configuration and enough ``iters``
    (``maxiter + l + 1`` plus ``stab_iter_slack`` on the in-scan path).

    ``context`` (optional) switches to the bindable-operator protocol:
    ``matvec`` is then a two-argument ``matvec_ctx(context, v)``, the
    sweep (given or built) takes ``(context, b, x0, k_budget)``, and the
    context pytree is threaded through it as a traced operand (no
    retrace when it is rebound between solves).

    Returns (x, resnorms, info dict).
    """
    with telemetry.span("plcg.prepare"):
        x0 = jnp.zeros_like(b) if x0 is None else x0
        norm = jnp.linalg.norm(b)
        in_scan = restart is not None or residual_replacement is not None
        iters = maxiter + l + 1 + stab_iter_slack(
            l, restart, residual_replacement, maxiter)
        program = sweep if sweep is not None else _jitted_sweep(
            matvec, l, iters, tuple(sigma), tol, prec,
            exploit_symmetry, unroll, backend, stencil_hw,
            restart=restart, rr_period=residual_replacement,
            ritz_refresh=ritz_refresh, precision=precision,
            bindable=context is not None)
        if context is None:
            fn = program
        else:
            fn = lambda bb, xx, kb: program(context, bb, xx, kb)  # noqa: E731
    bnorm = float(telemetry.fetch(norm, "bnorm"))
    if bnorm == 0:
        bnorm = 1.0

    def run_sweep(bb, xx, remaining):
        out = fn(bb, xx, remaining)
        return (out.x, out.resnorms, out.converged, out.breakdown,
                out.k_done, out.committed, out.restarts, out.replacements,
                out.trips)

    return run_restart_driver(run_sweep, b, x0, tol=tol, maxiter=maxiter,
                              max_restarts=max_restarts, bnorm=bnorm, l=l,
                              in_scan=in_scan, program=program,
                              prec=prec is not None)
