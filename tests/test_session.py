"""Prepared-solver sessions (``repro.core.session``): the Solver /
SolverPool serving API.

Covers the two-phase lifecycle (validate/normalize/build once, then
zero Python-side re-setup per call), the zero-retrace gate for
same-shape right-hand sides, micro-batched dispatch through
``submit``/``SolveHandle``/``SolverPool`` with pad bucketing (single
device AND mesh), the thin-wrapper contract of ``engine.solve``, the
per-method declared-option validation, and the solver-cache
interactions: a live session survives ``clear_solver_cache()``, and
dropping the last Solver reference releases the operator.

Mesh coverage runs in-process on a (1, 1) mesh everywhere (collective
semantics identical) and on a live (2, 2) decomposition when the main
process has >= 4 devices (the CI serve lane forces 4 via XLA_FLAGS).
"""
import gc
import inspect
import weakref

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (SolveHandle, Solver, SolverPool, clear_batch_trace,
                        clear_solver_cache, solve)
from repro.core import engine
from repro.core.session import _default_buckets
from repro.launch.mesh import make_mesh_compat
from repro.operators import poisson2d


@pytest.fixture(scope="module", autouse=True)
def x64():
    old = jax.config.jax_enable_x64
    jax.config.update("jax_enable_x64", True)
    yield
    jax.config.update("jax_enable_x64", old)


@pytest.fixture(scope="module")
def poisson():
    A = poisson2d(20, 20)
    b = np.asarray(A @ np.ones(A.n))
    return A, b


@pytest.fixture(scope="module")
def mesh11():
    return make_mesh_compat((1, 1), ("data", "model"))


KW = dict(l=2, tol=1e-10, maxiter=200, spectrum=(0.0, 8.0))


def _batch(A, nrhs, seed=0):
    rng = np.random.default_rng(seed)
    return np.stack([np.asarray(A @ rng.standard_normal(A.n))
                     for _ in range(nrhs)])


# ------------------------- two-phase lifecycle ----------------------------

def test_prepared_solver_matches_one_shot_solve(poisson):
    """Solver(A, ...) then solver(b) returns exactly what the one-shot
    front-end returns (same compiled sweep, same SolveResult contract)."""
    A, b = poisson
    solver = Solver(A, "plcg_scan", **KW)
    r1 = solver(b)
    r2 = solve(A, b, method="plcg_scan", **KW)
    assert r1.converged and r2.converged
    assert np.array_equal(np.asarray(r1.x), np.asarray(r2.x))
    assert r1.iters == r2.iters
    assert r1.info["method"] == r2.info["method"]


def test_prepared_solver_zero_retraces_same_shape(poisson):
    """Acceptance: after the first call, repeated same-shape solves show
    ZERO retraces -- every prepared sweep's jit cache stays at its
    first-call size, and no new sweeps are built."""
    A, b = poisson
    solver = Solver(A, "plcg_scan", **KW)
    solver(b)
    builds1 = solver.stats["prepared_builds"]          # lazy-once build
    counts1 = solver.compile_counts()
    assert builds1 >= 1
    assert any(c >= 1 for c in counts1.values())
    for _ in range(5):
        solver(b)
    assert solver.compile_counts() == counts1          # zero retraces
    assert solver.stats["prepared_builds"] == builds1  # zero rebuilds
    assert solver.stats["calls"] == 6


def test_prepared_batched_compiles_once(poisson):
    """The batched engine of a prepared solver traces exactly once for a
    given RHS shape across repeated solver(B) calls."""
    A, _ = poisson
    B = _batch(A, 4)
    solver = Solver(A, "plcg_scan", **KW)
    clear_batch_trace()
    for _ in range(3):
        rb = solver(B)
    assert len(engine.BATCH_TRACE_EVENTS) == 1
    assert engine.BATCH_TRACE_EVENTS[0][1] == (4, A.n)
    assert rb.converged


@pytest.mark.parametrize("nrhs", [0, 3])
def test_lower_is_the_program_solve_runs(poisson, nrhs):
    """Solver.lower(b) gives the compiled program of the solve that ran:
    after the solve, compiling it adds no jit-cache entry, and the
    batched form is the program a pool flush runs."""
    A, b = poisson
    bb = b if nrhs == 0 else _batch(A, nrhs)
    solver = Solver(A, "plcg_scan", backend="auto", **KW)
    r = solver(bb)
    assert r.info["backend"] == "ref"        # "auto" resolved off the chip
    before = solver.compile_counts()
    compiled = solver.lower(bb).compile()
    assert solver.compile_counts() == before
    assert compiled.memory_analysis() is not None
    assert "while" in compiled.as_text()      # the scan of the sweep


def test_lower_rejects_mesh_sessions(poisson, mesh11):
    A, _ = poisson
    solver = Solver(A, "plcg_scan", mesh=mesh11, **KW)
    with pytest.raises(NotImplementedError):
        solver.lower(np.zeros((20, 20)))


def test_tol_override_prepares_new_sweep(poisson):
    """A per-call tol override keys an additional prepared sweep; the
    session default stays live alongside it."""
    A, b = poisson
    solver = Solver(A, "plcg_scan", **KW)
    r1 = solver(b)
    builds = solver.stats["prepared_builds"]
    r2 = solver.solve(b, tol=1e-6)
    assert solver.stats["prepared_builds"] == builds + 1
    assert r2.iters <= r1.iters
    r3 = solver(b)                      # default-tol sweep still prepared
    assert solver.stats["prepared_builds"] == builds + 1
    assert np.array_equal(np.asarray(r1.x), np.asarray(r3.x))


def test_matvec_callable_needs_dimension(poisson):
    """A bare matvec callable takes n= at construction (the one-shot
    path infers it from b; the session defers promotion otherwise)."""
    A, b = poisson
    solver = Solver(A.matvec, "plcg_scan", n=A.n, **KW)
    r = solver(b)
    assert r.converged
    deferred = Solver(A.matvec, "plcg_scan", **KW)
    assert deferred(b).converged        # promoted at first call


def test_solver_construction_validates_up_front(poisson):
    A, b = poisson
    with pytest.raises(ValueError, match="plcg_scan"):
        Solver(A, "nope")
    with pytest.raises(ValueError, match="does not support precondition"):
        Solver(A, "plminres", M=lambda v: v / 4.0)
    with pytest.raises(ValueError, match="options"):
        Solver(A, "plcg_scan", record_G=True)


# -------------------- engine.solve() thin-wrapper contract ----------------

def test_solve_signature_unchanged():
    """engine.solve keeps its public signature (the session redesign must
    not break any existing caller) -- extended only by appended
    keyword-only knobs (``comm=``, then the stability pair ``restart=`` /
    ``residual_replacement=``, then ``precision=``), so positional
    callers are unaffected."""
    params = list(inspect.signature(solve).parameters)
    assert params == ["A", "b", "method", "x0", "tol", "maxiter", "M", "l",
                      "sigma", "spectrum", "backend", "mesh", "comm",
                      "restart", "residual_replacement", "precision",
                      "options"]


def test_unknown_option_rejected_uniformly(poisson):
    """Satellite: unknown **options no longer leak into method bodies;
    every method raises one uniform error naming its accepted keys."""
    A, b = poisson
    with pytest.raises(ValueError, match=r"options.*record_G.*accepted"):
        solve(A, b, method="plcg_scan", maxiter=20, record_G=True)
    with pytest.raises(ValueError, match="trace_true_residual"):
        solve(A, b, method="cg", maxiter=20, bogus=1)
    with pytest.raises(ValueError, match="accepted options.*none"):
        solve(A, b, method="dlanczos", maxiter=20, prune=True)
    # session-only constructor keywords (n=) must not absorb a
    # same-named unknown option through the one-shot passthrough
    with pytest.raises(ValueError, match=r"options \['n'\]"):
        solve(A, b, method="plcg_scan", maxiter=20, n=999)
    # declared options still pass through to the method bodies
    r = solve(A, b, method="cg", tol=1e-8, maxiter=300,
              trace_true_residual=True)
    assert r.converged and r.true_resnorms is not None


# ------------------------- micro-batched dispatch -------------------------

def test_submit_returns_pending_handle_and_result_flushes(poisson):
    A, b = poisson
    solver = Solver(A, "plcg_scan", **KW)
    h = solver.submit(b)
    assert isinstance(h, SolveHandle) and not h.done
    assert solver.pending == 1
    r = h.result()                      # implicit flush
    assert h.done and solver.pending == 0
    assert r.converged
    # a lone request still takes the batched sweep: pooled lanes keep
    # ONE contract (masked single sweep) regardless of queue depth
    assert r.info["pooled"] and r.info["flush_nrhs"] == 1
    assert np.linalg.norm(b - np.asarray(A @ np.asarray(r.x))) < 5e-7


def test_pool_packs_queue_into_one_batched_call():
    """Acceptance: >= 4 queued RHS pack into ONE batched sweep call, with
    per-RHS results matching one-shot solve() -- bitwise against the
    shape-identical batched one-shot, <= 1e-10 rel against per-RHS
    single solves.  (Fresh operator: the trace-count gate must not hit
    engines other tests already compiled for the shared fixture.)"""
    A = poisson2d(20, 20)
    B = _batch(A, 4, seed=3)
    solver = Solver(A, "plcg_scan", **KW)
    pool = SolverPool(solver, max_batch=8)
    handles = [pool.submit(B[j]) for j in range(4)]
    clear_batch_trace()
    recs = pool.flush()
    assert recs == [(4, 4)]             # one batch, no padding (bucket 4)
    assert len(engine.BATCH_TRACE_EVENTS) == 1          # ONE sweep call
    assert engine.BATCH_TRACE_EVENTS[0][1] == (4, A.n)
    rb = solve(A, B, method="plcg_scan", **KW)          # one-shot batched
    for j, h in enumerate(handles):
        r = h.result()
        assert r.converged and r.info["pooled"] and r.info["lane"] == j
        assert np.array_equal(np.asarray(r.x), np.asarray(rb.x)[j])
        rj = solve(A, B[j], method="plcg_scan", **KW)   # one-shot single
        rel = (np.linalg.norm(np.asarray(r.x) - np.asarray(rj.x))
               / np.linalg.norm(np.asarray(rj.x)))
        assert rel <= 1e-10
    assert pool.occupancy == 1.0


def test_pool_pad_bucketing_bounds_compilations():
    """5 pending RHS pad to the 8-bucket; a later 3-RHS flush reuses a
    smaller bucket -- repeated ragged queue depths touch at most the
    bucket ladder's worth of batch shapes.  (Fresh operator, same reason
    as above.)"""
    A = poisson2d(20, 20)
    B = _batch(A, 5, seed=4)
    solver = Solver(A, "plcg_scan", **KW)
    pool = SolverPool(solver, max_batch=8)
    assert pool.buckets == (1, 2, 4, 8)
    hs = [pool.submit(B[j]) for j in range(5)]
    clear_batch_trace()
    assert pool.flush() == [(5, 8)]
    assert engine.BATCH_TRACE_EVENTS[0][1] == (8, A.n)  # padded shape
    for j, h in enumerate(hs):
        r = h.result()
        assert r.converged and r.info["flush_pad"] == 8
        rj = solve(A, B[j], method="plcg_scan", **KW)
        rel = (np.linalg.norm(np.asarray(r.x) - np.asarray(rj.x))
               / np.linalg.norm(np.asarray(rj.x)))
        assert rel <= 1e-8
    assert pool.occupancy == 5 / 8
    # ragged re-flush hits the 4-bucket: a second distinct shape, not a
    # third -- and a SECOND flush of depth 3 adds no new trace
    for j in range(3):
        pool.submit(B[j])
    assert pool.flush() == [(3, 4)]
    shapes = {e[1] for e in engine.BATCH_TRACE_EVENTS}
    assert shapes == {(8, A.n), (4, A.n)}
    for j in range(3):
        pool.submit(B[j])
    n_events = len(engine.BATCH_TRACE_EVENTS)
    assert pool.flush() == [(3, 4)]
    assert len(engine.BATCH_TRACE_EVENTS) == n_events   # zero retraces


def test_pool_chunks_above_max_batch(poisson):
    A, _ = poisson
    B = _batch(A, 6, seed=5)
    solver = Solver(A, "plcg_scan", **KW)
    pool = SolverPool(solver, max_batch=4)
    hs = [pool.submit(B[j]) for j in range(6)]
    assert pool.flush() == [(4, 4), (2, 2)]
    assert all(h.done for h in hs)
    assert pool.stats["lanes_real"] == 6


def test_pool_rejects_mixed_shapes_and_keeps_handles_resolvable(poisson):
    A, b = poisson
    solver = Solver(A, "plcg_scan", **KW)
    h1 = solver.submit(b)
    h2 = solver.submit(b[: A.n // 2])
    with pytest.raises(ValueError, match="mixed RHS shapes"):
        solver.flush()
    # the failed chunk stays queued (handles are not orphaned); dropping
    # the malformed request lets the good one resolve
    assert solver.pending == 2 and not h1.done
    solver._pending = [p for p in solver._pending if p[2] is not h2]
    assert h1.result().converged
    assert solver.pending == 0


def test_pool_loop_method_falls_back_per_rhs(poisson):
    """Micro-batching needs a batched engine; loop methods still serve
    the queue correctly, one solve per handle."""
    A, _ = poisson
    B = _batch(A, 3, seed=6)
    solver = Solver(A, "cg", tol=1e-10, maxiter=400)
    pool = SolverPool(solver, max_batch=4)
    hs = [pool.submit(B[j]) for j in range(3)]
    pool.flush()
    for j, h in enumerate(hs):
        rj = solve(A, B[j], method="cg", tol=1e-10, maxiter=400)
        assert np.allclose(np.asarray(h.result().x), np.asarray(rj.x))


def test_default_buckets():
    assert _default_buckets(8) == (1, 2, 4, 8)
    assert _default_buckets(6) == (1, 2, 4, 6)
    assert _default_buckets(1) == (1,)


# ------------------------------ mesh path ---------------------------------

def test_prepared_solver_on_mesh_matches_one_shot(poisson, mesh11):
    A, b = poisson
    solver = Solver(A, "plcg_scan", mesh=mesh11, **KW)
    r1 = solver(b.reshape(20, 20))
    r2 = solve(A, b.reshape(20, 20), method="plcg_scan", mesh=mesh11, **KW)
    assert r1.converged
    assert np.array_equal(np.asarray(r1.x), np.asarray(r2.x))
    assert r1.info["psums_per_iter"] == 1
    # repeated calls reuse the strongly-held mesh sweep: no new builds
    builds = solver._mesh_session.builds
    counts = solver.compile_counts()
    solver(b.reshape(20, 20))
    assert solver._mesh_session.builds == builds
    assert solver.compile_counts() == counts            # zero retraces


def test_pool_on_mesh_packs_into_one_sweep(mesh11):
    """Acceptance (mesh variant): >= 4 queued (nx, ny) fields pack into
    one shard_map(vmap) sweep; per-RHS results match one-shot mesh
    solve() bitwise and per-RHS single mesh solves to <= 1e-10."""
    A = poisson2d(20, 20)
    B = _batch(A, 4, seed=7).reshape(4, 20, 20)
    solver = Solver(A, "plcg_scan", mesh=mesh11, **KW)
    pool = SolverPool(solver, max_batch=8)
    hs = [pool.submit(B[j]) for j in range(4)]
    clear_batch_trace()
    assert pool.flush() == [(4, 4)]
    assert [e[0] for e in engine.BATCH_TRACE_EVENTS] == ["plcg@mesh"]
    rb = solve(A, B, method="plcg_scan", mesh=mesh11, **KW)
    for j, h in enumerate(hs):
        r = h.result()
        assert r.converged
        assert np.array_equal(np.asarray(r.x), np.asarray(rb.x)[j])
        rj = solve(A, B[j], method="plcg_scan", mesh=mesh11, **KW)
        rel = (np.linalg.norm(np.asarray(r.x) - np.asarray(rj.x))
               / np.linalg.norm(np.asarray(rj.x)))
        assert rel <= 1e-10


def test_pool_on_4device_mesh(poisson):
    """Acceptance: the pooled path on a REAL (2, 2) decomposition -- live
    halo pairs and a genuinely distributed psum -- matches per-RHS
    one-shot mesh solves to <= 1e-10."""
    if len(jax.devices()) < 4:
        pytest.skip("needs >= 4 host devices (CI serve lane forces 4)")
    A, _ = poisson
    mesh = make_mesh_compat((2, 2), ("data", "model"))
    B = _batch(A, 4, seed=8).reshape(4, 20, 20)
    solver = Solver(A, "plcg_scan", mesh=mesh, **KW)
    pool = SolverPool(solver, max_batch=4)
    hs = [pool.submit(B[j]) for j in range(4)]
    assert pool.flush() == [(4, 4)]
    for j, h in enumerate(hs):
        r = h.result()
        assert r.converged
        rj = solve(A, B[j], method="plcg_scan", mesh=mesh, **KW)
        rel = (np.linalg.norm(np.asarray(r.x) - np.asarray(rj.x))
               / np.linalg.norm(np.asarray(rj.x)))
        assert rel <= 1e-10


# -------------------- solver-cache interaction ----------------------------

def test_live_solver_survives_clear_solver_cache(poisson):
    """Satellite: a live Solver holds its compiled sweeps strongly --
    clear_solver_cache() empties the weak-key caches without touching
    the session, which keeps solving with zero rebuilds/retraces."""
    from repro.core.plcg_scan import _SWEEP_CACHE

    A, b = poisson
    clear_solver_cache()
    gc.collect()
    solver = Solver(A, "plcg_scan", **KW)
    r1 = solver(b)
    assert len(_SWEEP_CACHE) >= 1
    builds = solver.stats["prepared_builds"]
    counts = solver.compile_counts()
    clear_solver_cache()
    assert len(_SWEEP_CACHE) == 0
    r2 = solver(b)
    assert np.array_equal(np.asarray(r1.x), np.asarray(r2.x))
    assert solver.stats["prepared_builds"] == builds    # no rebuild
    assert solver.compile_counts() == counts            # no retrace
    clear_solver_cache()


def test_dropping_solver_releases_operator(poisson):
    """Satellite (extends the PR-2/PR-4 eviction tests): the session pins
    the operator while alive -- dropping the user's own reference leaks
    nothing new -- and dropping the LAST Solver reference releases the
    operator and evicts its weak-cache entries."""
    from repro.core.plcg_scan import _SWEEP_CACHE

    clear_solver_cache()
    gc.collect()
    A = poisson2d(16, 16)
    b = jnp.asarray(np.asarray(A @ np.ones(A.n)))
    wr = weakref.ref(A)
    solver = Solver(A, "plcg_scan", l=2, tol=1e-8, maxiter=100,
                    spectrum=(0.0, 8.0))
    assert solver(b).converged
    assert len(_SWEEP_CACHE) == 1
    del A
    gc.collect()
    assert wr() is not None             # the live session pins the operator
    assert solver(b).converged          # and keeps solving
    del solver
    gc.collect()
    assert wr() is None                 # last reference gone -> released
    assert len(_SWEEP_CACHE) == 0       # weak-cache entry evicted
    clear_solver_cache()
