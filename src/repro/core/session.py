"""Prepared-solver sessions: the serving API of the unified engine.

The paper hides the latency of the global reduction behind the next l
SPMVs; this module hides the latency of the *front end* behind session
state.  ``repro.core.solve`` pays validation, preconditioner
normalization, sigma defaulting, operator promotion and the weak-key
sweep-cache lookup on EVERY call -- negligible for one large solve,
dominant for the many-concurrent-small-solves serving workload
(ROADMAP "Serving layer").  The session API splits the lifecycle in two:

  * :class:`Solver` -- ``solver = Solver(A, method="plcg_scan", l=2,
    M=..., mesh=...)`` performs every per-problem step exactly ONCE and
    holds the resulting jitted sweeps **strongly** (the weak-key caches
    of ``solver_cache`` still deduplicate against the one-shot path, but
    a live session survives ``clear_solver_cache()`` and cache
    eviction).  ``solver(b)`` / ``solver.solve(b, x0=..., tol=...)``
    then run with zero Python-side re-setup: after the first call of a
    given RHS shape there are ZERO retraces (see
    :meth:`Solver.compile_counts`).
  * :meth:`Solver.submit` / :class:`SolverPool` -- micro-batched
    dispatch: ``submit(b)`` queues a right-hand side and returns a
    :class:`SolveHandle`; a flush packs the pending queue into one
    padded ``(nrhs, n)`` (or ``(nrhs, nx, ny)`` mesh) batch and runs it
    through the existing batched engines -- ``jit(vmap(scan))`` on a
    single device, ``jit(shard_map(vmap(scan)))`` on a mesh -- so every
    per-iteration reduction of the flush carries ALL queued systems
    (the strong-scaling multi-solve workload of arXiv:1905.06850).
    Per-RHS convergence masking already lives in the engines, so one
    compiled batched sweep serves every queue depth; pad bucketing
    (powers of two up to ``max_batch`` by default) keeps the number of
    distinct compilations at a handful.

Padding duplicates lane 0 (never zeros: a zero RHS would inject NaNs
through the ``v0 = r0/||r0||`` normalization; lanes are independent
under vmap, so a duplicated lane is merely discarded on extraction).

Restart-on-breakdown is an in-scan affair (``restart=`` /
``residual_replacement=``, normalized once by the engine's
``_prepare_restart``): a session constructed with the stability knobs
bakes them into every sweep it prepares, so pooled lanes re-seed
themselves *inside* the one masked sweep per flush -- per lane, zero
host round-trips, no second sweep.  The legacy host restart loop
(``max_restarts``) remains a deprecated single-RHS escape hatch, and
``record_G``-style introspection knobs still do not apply to pooled
lanes.

Attainable accuracy stays reportable per lane via
``repro.core.residual_gap(A, b_j, result)`` on the per-handle results
(arXiv:1804.02962).
"""
from __future__ import annotations

from typing import Any, Optional

import numpy as np

from . import engine, telemetry
from .linop import LinearOperator, is_bindable
from .results import SolveResult

Array = Any

__all__ = ["SolveHandle", "Solver", "SolverPool"]


class SolveHandle:
    """Future-like handle for one submitted right-hand side.

    ``done`` is True once a flush has produced this request's result;
    ``result()`` drains the owning queue on demand (so a bare
    ``solver.submit(b).result()`` is a correct, if unbatched, call).
    ``request`` is the request id its ``solver.submit`` span carries
    (``repro.core.telemetry``), which the flush that solves it serves.
    """

    __slots__ = ("_owner", "_result", "request")

    def __init__(self, owner, request: int):
        self._owner = owner
        self._result: Optional[SolveResult] = None
        self.request = request

    @property
    def done(self) -> bool:
        return self._result is not None

    def result(self) -> SolveResult:
        if self._result is None:
            self._owner.flush()
        if self._result is None:    # defensive: flush must have set it
            raise RuntimeError("flush did not produce a result for this "
                               "handle (was the queue cleared externally?)")
        return self._result

    def _set(self, result: SolveResult) -> None:
        self._result = result


def _lane_result(rb: SolveResult, xs: np.ndarray, j: int, *,
                 flush_nrhs: int, flush_pad: int) -> SolveResult:
    """Extract lane ``j`` of a batched SolveResult, whose ``x`` the caller
    fetched once as ``xs``, as a single-RHS SolveResult (the per-handle
    contract of pooled dispatch)."""
    info = rb.info
    x = xs[j]
    conv = info.get("per_rhs_converged")
    iters = info.get("per_rhs_iters")
    brk = info.get("per_rhs_breakdown")
    rst = info.get("per_rhs_restarts")
    repl = info.get("per_rhs_replacements")
    n_rst = int(np.asarray(rst)[j]) if rst is not None else 0
    return SolveResult(
        x=x,
        resnorms=list(rb.resnorms[j]),
        iters=int(np.asarray(iters)[j]) if iters is not None else rb.iters,
        converged=(bool(np.asarray(conv)[j]) if conv is not None
                   else rb.converged),
        breakdowns=(int(np.asarray(brk)[j]) + n_rst if brk is not None
                    else 0),
        restarts=n_rst,
        replacements=(int(np.asarray(repl)[j]) if repl is not None else 0),
        info={"method": info.get("method"), "l": info.get("l"),
              "prec": info.get("prec"), "batched": info.get("batched"),
              "pooled": True, "lane": j,
              "flush_nrhs": flush_nrhs, "flush_pad": flush_pad},
    )


def _default_buckets(max_batch: int) -> tuple:
    """Powers of two up to (and always including) ``max_batch``."""
    buckets = []
    p = 1
    while p < max_batch:
        buckets.append(p)
        p *= 2
    buckets.append(max_batch)
    return tuple(buckets)


class Solver:
    """A prepared solver session: compile once, solve many.

    Construction runs the ``_prepare_*`` pipeline of the engine exactly
    once -- method lookup, option validation against the method's
    declared set, ``as_preconditioner(...).runtime()`` normalization,
    shift-interval defaulting from ``M.precond_spectrum`` and operator
    promotion (``as_operator`` / ``as_dist_operator``); each jitted
    sweep is then built exactly once, at its first use, and held
    strongly in ``self._prepared``.  All constructor keywords have the same meaning
    as in :func:`repro.core.solve`; ``tol``/``maxiter`` become session
    defaults that individual :meth:`solve` calls may override (an
    override keys a new prepared sweep, strongly held like the first).

    ``n=`` gives the problem dimension when ``A`` is a bare matvec
    callable (the one-shot ``solve()`` infers it from ``b``; a session
    has no ``b`` yet).  Promotion is deferred to the first call when
    neither is available.

    Threading: sessions are not thread-safe; serve one queue per
    thread or lock externally.
    """

    def __init__(self, A, method: str = "plcg_scan", *, tol: float = 1e-8,
                 maxiter: int = 1000, M=None, l=1, sigma=None,
                 spectrum=None, backend: Optional[str] = None, mesh=None,
                 comm=None, restart="auto",
                 residual_replacement: Optional[int] = None,
                 precision=None,
                 n: Optional[int] = None, **options):
        spec = engine._prepare_method(method)
        engine._prepare_options(spec, options)
        on_mesh = mesh is not None or engine._is_mesh_operator(A)
        # the cross-cutting knob group (M=/mesh=/backend=/comm=/restart=/
        # residual_replacement=/precision=) is validated and normalized
        # ONCE here, through the engine's single knob table -- no layer
        # below re-validates per call
        M, comm, precision = engine._prepare_knobs(
            spec, M=M, backend=backend, mesh=mesh, comm=comm,
            precision=precision, on_mesh=on_mesh, A=A)
        l = engine._prepare_depth(spec, l)
        restart, residual_replacement = engine._prepare_restart(
            spec, restart, residual_replacement, options)
        spectrum = engine._prepare_spectrum(spec, M, sigma, spectrum)
        self.method = method
        self.spec = spec
        self.M = M
        self.tol = tol
        self.maxiter = maxiter
        self.l = l
        self.sigma = sigma
        self.spectrum = spectrum
        self.backend = backend
        self.comm = comm
        self.restart = restart
        self.residual_replacement = residual_replacement
        self.precision = precision
        self.auto = None            # AutoDecision once l/comm calibrated
        self.options = dict(options)
        self._pending: list = []
        self._prepared: dict = {}       # strong refs: config -> jitted fn
        self.stats = {"calls": 0, "prepared_builds": 0}

        self._mesh_session = None
        if on_mesh:
            # lazy import: keeps the core engine importable where the
            # distributed layer (shard_map et al.) is unavailable
            from ..distributed.plcg_dist import prepare_on_mesh
            self._mesh_session = prepare_on_mesh(
                spec, A, mesh, M=M, l=l, sigma=sigma, spectrum=spectrum,
                comm=comm, restart=restart,
                residual_replacement=residual_replacement,
                precision=precision, tol=tol, **options)
            self._op = self._mesh_session.op
            # auto sentinels resolve at mesh-session construction, where
            # the operator and its mesh are known; mirror the concrete
            # choice so session attributes always read as resolved
            self.l = self._mesh_session.l
            self.comm = self._mesh_session.comm
            self.auto = self._mesh_session.auto
            return

        # single-device operator promotion (deferred only for a bare
        # matvec callable with no dimension hint).  Bindable operators
        # must be caught before the bare-callable branch: they define
        # __call__, and wrapping one in a LinearOperator would bake its
        # context into the compiled sweeps as trace-time constants.
        if is_bindable(A):
            self._op = A
        elif isinstance(A, LinearOperator) or getattr(A, "ndim", None) == 2:
            self._op = engine.as_operator(A)
        elif callable(A) and n is not None:
            self._op = LinearOperator(matvec=A, n=int(n), name="matvec")
        elif callable(A):
            self._op = None
            self._A_raw = A
        else:
            raise TypeError(f"cannot interpret {type(A).__name__} as a "
                            "linear operator")
        if self.l == "auto":
            # calibration needs an operator to probe NOW (a prepared
            # session measures once, at construction -- never per call)
            if self._op is None:
                raise ValueError(
                    "l='auto' calibrates against the operator at session "
                    "construction, but a bare matvec callable has no "
                    "dimension yet; pass n= (or pin an integer l)")
            from .autotune import resolve_auto
            self.auto = resolve_auto(self._op, l="auto", comm=self.comm,
                                     tol=tol, precision=precision,
                                     backend=backend)
            self.l = self.auto.l
        # sweep building is lazy-once: the first call of each entry
        # point (single-RHS / batched / tol override) builds its jitted
        # sweep through the memoizing getters and holds it forever --
        # eager wrapping at construction would charge the one-shot
        # solve() path for engines it never runs (XLA compiles at the
        # first real call either way)

    # ---- prepared-sweep plumbing ----------------------------------------

    def _ensure_op(self, b) -> LinearOperator:
        if self._op is None:
            self._op = engine.as_operator(self._A_raw, b)
        return self._op

    def _single_sweep(self, tol: float, maxiter: int):
        """The strongly-held jitted single-RHS scan sweep for one
        (tol, maxiter) configuration (plcg_scan only)."""
        key = ("sweep", float(tol), int(maxiter))
        if key not in self._prepared:
            from .plcg_scan import _jitted_sweep, stab_iter_slack
            sig = tuple(engine._resolve_sigma(self.sigma, self.spectrum,
                                              self.l))
            iters = maxiter + self.l + 1 + stab_iter_slack(
                self.l, self.restart, self.residual_replacement, maxiter)
            bind = is_bindable(self._op)
            self._prepared[key] = _jitted_sweep(
                self._op.matvec_ctx if bind else self._op.matvec,
                self.l, iters, sig, tol,
                self.M, self.options.get("exploit_symmetry", True),
                self.options.get("unroll", 1), self.backend,
                getattr(self._op, "stencil2d", None),
                restart=self.restart,
                rr_period=self.residual_replacement,
                ritz_refresh=self.options.get("ritz_refresh", True),
                precision=self.precision, bindable=bind)
            self.stats["prepared_builds"] += 1
        return self._prepared[key]

    def _batched_engine_getter(self):
        """``get_engine`` hook for the engine's batched path: same
        arguments as ``engine._batched_engine``, memoized strongly here
        (the session holds the operator and preconditioner anyway, so
        the config key pins nothing extra)."""

        def get(*args):
            key = ("batched",) + args
            if key not in self._prepared:
                self._prepared[key] = engine._batched_engine(*args)
                self.stats["prepared_builds"] += 1
            return self._prepared[key]

        return get

    @property
    def prepared_sweeps(self) -> int:
        """Number of jitted sweeps this session holds strongly (single-
        device and mesh)."""
        n = len(self._prepared)
        if self._mesh_session is not None:
            n += self._mesh_session.builds
        return n

    def compile_counts(self) -> dict:
        """Per-prepared-sweep XLA compilation counts (jit cache sizes).

        After the first call of a given RHS shape, repeated calls must
        not grow any entry -- the "zero retraces" serving gate asserted
        by the tests and recorded by ``benchmarks/serve_bench.py``."""
        from ..kernels.introspect import jit_cache_size
        counts = {}
        for key, fn in self._prepared.items():
            counts[key] = jit_cache_size(fn)
        if self._mesh_session is not None:
            for key, fn in self._mesh_session._sweeps.items():
                counts[("mesh",) + key] = jit_cache_size(fn)
        return counts

    # ---- solving ---------------------------------------------------------

    def solve(self, b, x0=None, *, tol: Optional[float] = None,
              maxiter: Optional[int] = None) -> SolveResult:
        """Solve ``A x = b`` with the prepared session (same result
        contract as :func:`repro.core.solve`, including stacked batches).
        ``tol``/``maxiter`` default to the session values; an override
        prepares (and strongly holds) an additional sweep.  One
        ``solver.solve`` root span (``repro.core.telemetry``)."""
        with telemetry.span("solver.solve") as root:
            r = self._solve(b, x0, tol=tol, maxiter=maxiter)
            root.requests = telemetry.request_ids(r.info.get("nrhs", 1))
        return r

    __call__ = solve

    def _solve(self, b, x0=None, *, tol: Optional[float] = None,
               maxiter: Optional[int] = None) -> SolveResult:
        """:meth:`solve` inside the caller's root span."""
        tol = self.tol if tol is None else tol
        maxiter = self.maxiter if maxiter is None else maxiter
        self.stats["calls"] += 1
        if self._mesh_session is not None:
            r = self._mesh_session.solve(b, x0, tol=tol, maxiter=maxiter)
        else:
            op = self._ensure_op(b)
            spec = self.spec
            if getattr(b, "ndim", 1) == 2:
                r = engine._solve_batched(
                    spec, op, b, x0=x0, tol=tol, maxiter=maxiter, M=self.M,
                    l=self.l, sigma=self.sigma, spectrum=self.spectrum,
                    backend=self.backend, restart=self.restart,
                    rr_period=self.residual_replacement,
                    precision=self.precision,
                    get_engine=(self._batched_engine_getter()
                                if spec.batched == "vmap" else None),
                    **self.options)
            elif spec.name == "plcg_scan":
                # a bindable operator's CURRENT context is bound at call
                # time (plcg_solve's context=): the raw prepared sweep
                # (kept in _prepared for the compile_counts gate) takes it
                # as a traced operand
                with telemetry.span("plcg.prepare"):
                    sweep = self._single_sweep(tol, maxiter)
                r = engine._run_plcg_scan(
                    op, b, x0, tol=tol, maxiter=maxiter, M=self.M, l=self.l,
                    sigma=self.sigma, spectrum=self.spectrum,
                    backend=self.backend,
                    sweep=sweep,
                    restart=self.restart,
                    residual_replacement=self.residual_replacement,
                    precision=self.precision,
                    **self.options)
            else:
                r = spec.fn(op, b, x0, tol=tol, maxiter=maxiter, M=self.M,
                            l=self.l, sigma=self.sigma,
                            spectrum=self.spectrum,
                            backend=self.backend, **self.options)
        if self.auto is not None:
            r.info["auto"] = self.auto.as_info()
        return r

    def lower(self, b, x0=None):
        """Lower, without running it, the program that ``solve(b, x0)``
        runs on one device: a ``jax.stages.Lowered`` whose ``.compile()``
        shows the kernel launches (``as_text()``) and the device memory
        (``memory_analysis()``) of the solve.  ``b`` is ``(n,)`` or a
        stacked ``(nrhs, n)`` batch, as for :meth:`solve`; a flush of a
        :class:`SolverPool` runs the batched program of its padded batch.
        Single-device ``plcg_scan`` sessions only."""
        import jax.numpy as jnp
        if self._mesh_session is not None or self.spec.name != "plcg_scan":
            raise NotImplementedError(
                "Solver.lower covers single-device plcg_scan sessions")
        op = self._ensure_op(b)
        bj = jnp.asarray(b)
        if bj.ndim == 2:
            opts = {key: v for key, v in self.options.items()
                    if key in ("exploit_symmetry", "unroll", "ritz_refresh")}
            fn, args, _, _ = engine._batched_program(
                self.spec, op, bj, x0=x0, tol=self.tol, maxiter=self.maxiter,
                M=self.M, l=self.l, sigma=self.sigma, spectrum=self.spectrum,
                backend=self.backend, restart=self.restart,
                rr_period=self.residual_replacement,
                precision=self.precision,
                get_engine=self._batched_engine_getter(), **opts)
            return fn.lower(*args)
        sweep = self._single_sweep(self.tol, self.maxiter)
        x0j = jnp.zeros_like(bj) if x0 is None else jnp.asarray(x0)
        args = (bj, x0j, self.maxiter)
        if is_bindable(op):
            args = (op.context,) + args
        return sweep.lower(*args)

    # ---- micro-batched dispatch -----------------------------------------

    def submit(self, b, x0=None, *, _owner=None) -> SolveHandle:
        """Queue one right-hand side; returns a :class:`SolveHandle`.

        Nothing runs until a flush -- triggered explicitly
        (:meth:`flush` / ``SolverPool.flush``) or implicitly by
        ``handle.result()``.  One ``solver.submit`` root span, whose id is
        the request id (``handle.request``)."""
        with telemetry.span("solver.submit") as root:
            root.requests = (root.id,)
            handle = SolveHandle(_owner if _owner is not None else self,
                                 root.id)
            self._pending.append((b, x0, handle))
        return handle

    @property
    def pending(self) -> int:
        return len(self._pending)

    def flush(self, *, max_batch: Optional[int] = None,
              buckets: Optional[tuple] = None) -> list:
        """Drain the queue: pack pending RHS into batched sweep calls.

        Chunks of at most ``max_batch`` (default: everything in one) are
        padded up to the smallest bucket >= the chunk size (default: no
        padding) by duplicating lane 0, solved through the batched
        engine, and unpacked into the per-handle results; each chunk is
        one ``solver.flush`` root span serving its requests' ids.
        Returns a list of ``(real, padded)`` flush records.
        """
        records = []
        while self._pending:
            take = len(self._pending) if max_batch is None \
                else min(max_batch, len(self._pending))
            chunk, self._pending = (self._pending[:take],
                                    self._pending[take:])
            try:
                records.append(self._flush_chunk(chunk, buckets))
            except BaseException:
                # leave the failed chunk's UNRESOLVED requests queued
                # (their handles must stay resolvable once the caller
                # fixes the problem -- e.g. mixed shapes flushed per
                # shape); requests the chunk already resolved before the
                # failure must not be re-solved
                self._pending = ([p for p in chunk if not p[2].done]
                                 + self._pending)
                raise
        return records

    def _flush_chunk(self, chunk: list, buckets: Optional[tuple]) -> tuple:
        k = len(chunk)
        can_batch = (self.spec.batched == "vmap"
                     or self._mesh_session is not None)
        pad = k
        if can_batch and buckets:
            for size in sorted(buckets):
                if size >= k:
                    pad = size
                    break
        with telemetry.span("solver.flush",
                            requests=[h.request for _, _, h in chunk],
                            rhs=k, lanes=pad):
            if can_batch:
                self._solve_chunk(chunk, pad)
            else:
                # loop methods: per-RHS dispatch (restart semantics of the
                # plain solve apply -- there is no batched sweep to share)
                for b, x0, handle in chunk:
                    handle._set(self._solve(b, x0))
        return (k, pad)

    def _solve_chunk(self, chunk: list, pad: int) -> None:
        """One batched sweep over ``chunk`` padded to ``pad`` lanes, and
        the per-handle results.  Batchable methods ALWAYS take the
        batched sweep, even for a lone request: pooled lanes must have
        one contract (masked single sweep, no data-dependent restarts)
        regardless of how many requests happened to be co-queued."""
        import jax.numpy as jnp
        k = len(chunk)
        with telemetry.span("plcg.prepare"):
            bs = [jnp.asarray(b) for b, _, _ in chunk]
            shape = bs[0].shape
            if any(b.shape != shape for b in bs):
                raise ValueError(
                    f"cannot micro-batch mixed RHS shapes "
                    f"{sorted({tuple(b.shape) for b in bs})}; "
                    "flush per shape")
            # pad lanes duplicate lane 0, x0 included, so they finish with
            # it and never keep the engine's loop running past the real
            # lanes
            bs += [bs[0]] * (pad - k)
            B = jnp.stack(bs)
            X0 = None
            if any(x0 is not None for _, x0, _ in chunk):
                xs0 = [jnp.zeros_like(bs[0]) if x0 is None
                       else jnp.asarray(x0) for _, x0, _ in chunk]
                X0 = jnp.stack(xs0 + [xs0[0]] * (pad - k))
        rb = self._solve_batched_for_pool(B, X0, lanes=k)
        xs = telemetry.fetch(rb.x, "x")         # every lane's x, one read
        with telemetry.span("plcg.unpack"):
            for j, (_, _, handle) in enumerate(chunk):
                handle._set(_lane_result(rb, xs, j, flush_nrhs=k,
                                         flush_pad=pad))

    def _solve_batched_for_pool(self, B, X0, *, lanes: int) -> SolveResult:
        """Batched solve for pooled dispatch: legacy host-driver knobs
        (``max_restarts``, ``record_G``-style introspection) are stripped
        -- the batched engines would reject them loudly -- but the
        normalized in-scan stability knobs (``restart=`` /
        ``residual_replacement=``) thread through, so each pooled lane
        re-seeds itself independently inside the one masked sweep per
        flush.  Lanes past the first ``lanes`` are padding."""
        self.stats["calls"] += 1
        if self._mesh_session is not None:
            opts = {key: v for key, v in self.options.items()
                    if key in ("exploit_symmetry", "ritz_refresh")}
            sess = self._mesh_session
            if sess.spec.name == "cg":
                from ..distributed.plcg_dist import _mesh_cg
                return _mesh_cg(sess.op, B, X0, tol=self.tol,
                                maxiter=self.maxiter, prec=sess.prec,
                                get_sweep=sess._get_sweep("cg", self.tol))
            from ..distributed.plcg_dist import _mesh_plcg
            return _mesh_plcg(sess.op, B, X0, tol=self.tol,
                              maxiter=self.maxiter, l=sess.l,
                              sigma=sess.sig, prec=sess.prec,
                              comm=sess.comm, restart=sess.restart,
                              residual_replacement=sess.residual_replacement,
                              precision=sess.precision,
                              get_sweep=sess._get_sweep("plcg", self.tol),
                              lanes=lanes, **opts)
        op = self._ensure_op(B[0])
        opts = {key: v for key, v in self.options.items()
                if key in ("exploit_symmetry", "unroll", "ritz_refresh")}
        return engine._solve_batched(
            self.spec, op, B, x0=X0, tol=self.tol, maxiter=self.maxiter,
            M=self.M, l=self.l, sigma=self.sigma, spectrum=self.spectrum,
            backend=self.backend, restart=self.restart,
            rr_period=self.residual_replacement,
            precision=self.precision,
            get_engine=(self._batched_engine_getter()
                        if self.spec.batched == "vmap" else None),
            lanes=lanes, **opts)


class SolverPool:
    """Micro-batching policy over a :class:`Solver`: bounded flush size
    and pad bucketing, plus occupancy accounting.

    ``max_batch`` caps the lanes of one batched sweep call; ``pad_to``
    is the ascending bucket ladder a chunk is padded up to (default:
    powers of two up to ``max_batch``), so at most ``len(pad_to)``
    distinct batch shapes -- and therefore compilations -- ever exist
    per RHS shape.  ``submit`` delegates to the solver's queue;
    ``flush`` drains it under this policy and records occupancy
    (real lanes / padded lanes, the utilization of every flush's fused
    reductions).
    """

    def __init__(self, solver: Solver, *, max_batch: int = 8,
                 pad_to: Optional[tuple] = None):
        if max_batch < 1:
            raise ValueError(f"max_batch must be >= 1, got {max_batch}")
        self.solver = solver
        self.max_batch = int(max_batch)
        self.buckets = (tuple(sorted(int(p) for p in pad_to)) if pad_to
                        else _default_buckets(self.max_batch))
        if self.buckets[-1] < self.max_batch:
            raise ValueError(
                f"largest pad bucket {self.buckets[-1]} is below "
                f"max_batch={self.max_batch}; a full chunk could not be "
                "padded to any bucket")
        self.stats = {"batches": 0, "lanes_real": 0, "lanes_padded": 0}

    def submit(self, b, x0=None) -> SolveHandle:
        return self.solver.submit(b, x0, _owner=self)

    @property
    def pending(self) -> int:
        return self.solver.pending

    def flush(self) -> list:
        """Drain the solver's queue in batches of <= ``max_batch``,
        padded to the bucket ladder.  Returns the flush records."""
        records = self.solver.flush(max_batch=self.max_batch,
                                    buckets=self.buckets)
        self.stats["batches"] += len(records)
        for real, padded in records:
            self.stats["lanes_real"] += real
            self.stats["lanes_padded"] += padded
        return records

    @property
    def occupancy(self) -> float:
        """Mean fraction of real (non-pad) lanes across flushed batches
        (1.0 = every fused reduction fully utilized)."""
        if not self.stats["lanes_padded"]:
            return 1.0
        return self.stats["lanes_real"] / self.stats["lanes_padded"]
