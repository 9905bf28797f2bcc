"""The solver's own spans, counters and named scopes
(``repro.core.telemetry``, the scan body's ``jax.named_scope`` phases).

Spans nest under one root per user entry, carry request ids, mark the
dispatch that compiled; ``syncs`` equals the blocking device-to-host
reads the program makes (counted independently, at JAX's own host
materialization), ``bodies`` the engine's trip count and ``useful`` the
bodies up to each real lane's last committed update; the store is
bounded; every phase scope reaches the compiled HLO of the single,
batched and 2x2-mesh sweeps; the batched engine's tol warning fires once,
below a threshold the solver's own runs support."""
import json
import os
import re
import subprocess
import sys
import textwrap
import warnings

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import Solver, SolverPool, engine, solve, telemetry
from repro.core.plcg_scan import stab_iter_slack
from repro.operators import poisson2d

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
L, MAXITER = 2, 60
KW = dict(method="plcg_scan", l=L, tol=1e-5, maxiter=MAXITER,
          spectrum=(0.0, 8.0))
CHILDREN = {"plcg.prepare", "plcg.dispatch", "plcg.wait", "plcg.fetch",
            "plcg.unpack"}
SCOPES = ("plcg.spmv", "plcg.reduce", "plcg.scalars", "plcg.recur",
          "plcg.dots", "plcg.update")


@pytest.fixture(autouse=True)
def fresh_store():
    telemetry.clear()
    yield
    telemetry.clear()


@pytest.fixture
def problem():
    A = poisson2d(16)
    rng = np.random.default_rng(0)
    B = jnp.asarray(rng.standard_normal((4, A.n)), jnp.float32)
    return A, B


def _last(name):
    return [r for r in telemetry.roots() if r.name == name][-1]


def test_solve_spans_nest_under_one_root_and_mark_the_compile(problem):
    A, B = problem
    solver = Solver(A, **KW)
    solver.solve(B[0])
    solver.solve(B[1])
    first, second = telemetry.roots()
    for root in (first, second):
        assert root.name == "solver.solve" and root.parent is None
        assert len(root.requests) == 1
        names = [s.name for s in root.spans]
        assert set(names) <= CHILDREN
        assert all(s.parent == root.id for s in root.spans)
        assert all(root.start_ns <= s.start_ns <= s.end_ns <= root.end_ns
                   for s in root.spans)
        assert names.index("plcg.dispatch") < names.index("plcg.wait")
        assert [s.attrs["what"] for s in root.spans
                if s.name == "plcg.fetch"] == ["bnorm", "resnorms", "k_done",
                                               "converged"]
    ids = [first.id, second.id] + [s.id for r in (first, second)
                                   for s in r.spans]
    assert len(set(ids)) == len(ids)
    dispatch = [[s.attrs["compiled"] for s in r.spans
                 if s.name == "plcg.dispatch"] for r in (first, second)]
    assert dispatch == [[True], [False]]


def test_one_shot_solve_is_one_root_with_the_session_built_inside(problem):
    A, B = problem
    solve(A, B[0], **KW)
    (root,) = telemetry.roots()
    assert root.name == "solver.solve" and root.spans[0].name == \
        "plcg.prepare"
    assert root.counters["syncs"] == 4


def test_flush_serves_the_ids_of_its_submits(problem):
    A, B = problem
    pool = SolverPool(Solver(A, **KW), max_batch=4, pad_to=(4,))
    handles = [pool.submit(b) for b in B[:3]]
    pool.flush()
    roots = telemetry.roots()
    submits = [r for r in roots if r.name == "solver.submit"]
    flush = _last("solver.flush")
    assert [r.requests for r in submits] == [(r.id,) for r in submits]
    assert [h.request for h in handles] == [r.id for r in submits]
    assert flush.requests == tuple(h.request for h in handles)
    assert flush.attrs == {"rhs": 3, "lanes": 4}
    assert [s.attrs["what"] for s in flush.spans
            if s.name == "plcg.fetch"].count("x") == 1
    assert all(s.parent == flush.id for s in flush.spans)


#: the blocking reads of each path, in the order the program makes them
READS = {
    "default": ["bnorm", "resnorms", "k_done", "converged"],
    "restart": ["bnorm", "committed", "resnorms", "converged", "breakdown",
                "restarts", "replacements", "k_done"],
    "flush": ["resnorms", "converged", "breakdown", "k_done", "x"],
}


@pytest.mark.parametrize("path", ["default", "restart", "flush"])
def test_syncs_equal_the_reads_the_program_makes(problem, path,
                                                 monkeypatch):
    """``syncs`` counts the ``plcg.fetch`` spans, one per read; and no
    host conversion of a device array (``int``, ``bool``, ``float``, seen
    at JAX's own ``_value``) happens outside one, so no read escapes the
    count."""
    from jax._src import array as jarray
    A, B = problem
    kw = dict(KW, restart=2) if path == "restart" else KW
    solver = Solver(A, **kw)
    pool = SolverPool(solver, max_batch=4)

    def run():
        if path == "flush":
            handles = [pool.submit(b) for b in B[:3]]
            pool.flush()
            return handles
        return solver.solve(B[0])

    run()                                   # compile outside the count
    escaped = []
    value = jarray.ArrayImpl._value

    def watched(self):
        stack = telemetry._stack()
        if not stack or stack[-1].name != "plcg.fetch":
            escaped.append(self.shape)
        return value.fget(self)

    monkeypatch.setattr(jarray.ArrayImpl, "_value", property(watched))
    run()
    monkeypatch.undo()
    root = _last("solver.flush" if path == "flush" else "solver.solve")
    fetches = [s.attrs["what"] for s in root.spans if s.name == "plcg.fetch"]
    assert fetches == READS[path]
    assert root.counters["syncs"] == len(fetches)
    assert escaped == []


@pytest.mark.parametrize("path", ["default", "restart", "flush"])
def test_bodies_are_the_trip_count_and_useful_the_committed_ones(problem,
                                                                 path):
    """The engine stops after the body in which its last lane is done:
    ``trips`` is that exit trip, below the cap of ``maxiter + l + 1``
    (plus the stability slack), so a single solve's bodies are exactly
    its useful ones and a flush's are every lane times the last lane's
    useful bodies (the pad lane duplicates lane 0)."""
    A, B = problem
    restart = 2 if path == "restart" else None
    solver = Solver(A, **dict(KW, restart=restart))
    cap = MAXITER + L + 1 + stab_iter_slack(L, restart, None, MAXITER)
    if path == "flush":
        pool = SolverPool(solver, max_batch=4, pad_to=(4,))
        handles = [pool.submit(b) for b in B[:3]]
        pool.flush()
        root = _last("solver.flush")
        iters = [h.result().iters for h in handles]
        last = max(L + k for k in iters)
        # the engine's own per-body output on the flush's lanes: (lanes,
        # exit trip)
        fn, args, _, _ = engine._batched_program(
            solver.spec, solver._op, jnp.stack(list(B[:3]) + [B[0]]),
            x0=None, tol=solver.tol, maxiter=MAXITER, M=None, l=L,
            sigma=None, spectrum=solver.spectrum, backend=None,
            get_engine=solver._batched_engine_getter())
        assert np.asarray(fn(*args).trips).tolist() == [last] * 4
        assert last < cap
        assert root.counters["bodies"] == 4 * last        # padding too
        assert root.counters["useful"] == sum(L + k for k in iters)
    else:
        r = solver.solve(B[0])
        root = _last("solver.solve")
        out = solver._single_sweep(solver.tol, MAXITER)(
            B[0], jnp.zeros_like(B[0]), MAXITER)
        assert root.counters["bodies"] == int(out.trips) < cap
        assert root.counters["useful"] == L + r.iters
        assert root.counters["bodies"] == root.counters["useful"]
    assert root.counters["useful"] <= root.counters["bodies"]


@pytest.mark.parametrize("in_scan", [True, False])
def test_bodies_read_the_trip_count_the_sweep_returns(in_scan):
    """A sweep that ran fewer bodies than its per-body buffers hold (as
    an early exit would) is counted by the trip count it returns."""
    from repro.core.plcg_scan import run_restart_driver
    width, ran, k = 64, 9, 4
    committed = np.zeros(width, bool)
    committed[L:L + k + 1] = True
    resn = np.where(committed, 1e-6, 0.0)

    def sweep(b, x, budget):
        return tuple(jnp.asarray(v) for v in (
            x, resn, True, False, k, committed, 0, 0, ran))

    b = jnp.ones(8)
    with telemetry.span("solver.solve") as root:
        run_restart_driver(sweep, b, b * 0, tol=1e-5, maxiter=MAXITER,
                           max_restarts=0, bnorm=1.0, l=L, in_scan=in_scan)
    assert root.counters["bodies"] == ran
    assert root.counters["useful"] == L + k + 1


def test_store_keeps_the_newest_roots():
    first = None
    for k in range(telemetry.MAX_ROOTS + 3):
        with telemetry.span("t", k=k) as rec:
            with telemetry.span("child"):
                pass
        first = first or rec.id
    roots = telemetry.roots()
    assert len(roots) == telemetry.MAX_ROOTS
    assert [r.attrs["k"] for r in roots[:2]] == [3, 4]
    assert all(r.name == "t" and len(r.spans) == 1 for r in roots)
    assert first not in {r.id for r in roots}


_MESH_HLO = """
import json, re
import jax.numpy as jnp, numpy as np
from repro.core import Solver
from repro.launch.mesh import make_mesh_compat
from repro.operators import poisson2d
mesh = make_mesh_compat((2, 2), ("data", "model"))
s = Solver(poisson2d(16), mesh=mesh, method="plcg_scan", l=2, tol=1e-5,
           maxiter=20, spectrum=(0.0, 8.0), restart={restart!r})
from repro.core.plcg_scan import stab_iter_slack
sess = s._mesh_session
stab = {restart!r} is not None
iters = 20 + 2 + (1 + stab_iter_slack(2, {restart!r}, None, 20) if stab
                  else 0)
fn = sess._get_sweep("plcg", 1e-5)(iters=iters, batched=False)
b = jnp.ones((16, 16), jnp.float32)
txt = fn.lower(b, jnp.zeros_like(b), 20).compile().as_text()
print(json.dumps(sorted(set(re.findall(r'op_name="[^"]*?(plcg\\.[a-z]+)',
                                       txt)))))
"""


@pytest.mark.parametrize("restart", [None, 1])
@pytest.mark.parametrize("sweep", ["single", "batched", "mesh"])
def test_every_phase_scope_reaches_the_compiled_hlo(problem, sweep, restart):
    A, B = problem
    if sweep == "mesh":
        env = dict(os.environ, JAX_PLATFORMS="cpu",
                   XLA_FLAGS="--xla_force_host_platform_device_count=4",
                   PYTHONPATH=os.path.join(REPO, "src"))
        out = subprocess.run(
            [sys.executable, "-c",
             textwrap.dedent(_MESH_HLO.format(restart=restart))],
            env=env, capture_output=True, text=True, timeout=300)
        assert out.returncode == 0, out.stderr[-3000:]
        found = json.loads(out.stdout.strip().splitlines()[-1])
    else:
        solver = Solver(A, **dict(KW, restart=restart))
        b = B[0] if sweep == "single" else B
        txt = solver.lower(b).compile().as_text()
        # a batch vmaps the body inside the loop: ``vmap(plcg.spmv)/``
        assert re.search(r'op_name="[^"]*/while/body/[^"]*plcg\.spmv[/)]',
                         txt)
        found = sorted(set(re.findall(r'op_name="[^"]*?(plcg\.[a-z]+)',
                                      txt)))
    want = set(SCOPES) | ({"plcg.stab"} if restart is not None else set())
    assert set(found) == want


def test_fused_megakernel_runs_under_its_scope(problem):
    A, B = problem
    txt = Solver(A, **dict(KW, backend="fused")).lower(B[0]).compile() \
        .as_text()
    assert 'plcg.fused/' in txt


#: float32 p(3)-CG on 2-D Poisson 64^2, four lanes, against the modeled
#: floor ``autotune.attainable_floor(3, float32)`` = 8.3e-7.  Every lane
#: reports convergence on both sides of it; the float64 true residual
#: meets tol=1e-5 (above the floor, no warning) to within 2x, and misses
#: tol=1e-8 (below it, one warning per engine) by more than 10x
@pytest.mark.parametrize("tol, warns", [(1e-5, False), (1e-8, True)])
def test_batched_tol_warning_once_below_the_supported_floor(tol, warns):
    from repro.core.autotune import attainable_floor
    assert (tol < attainable_floor(3, jnp.float32)) == warns
    A = poisson2d(64)
    rng = np.random.default_rng(0)
    B = np.stack([np.asarray(A @ rng.standard_normal(A.n))
                  for _ in range(4)])
    solver = Solver(A, method="plcg_scan", l=3, tol=tol, maxiter=400,
                    spectrum=(0.0, 8.0))
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        results = [solver.solve(jnp.asarray(B, jnp.float32))
                   for _ in range(2)]
    hits = [w for w in caught if "attainable floor" in str(w.message)]
    assert len(hits) == (1 if warns else 0)
    if warns:
        assert hits[0].filename == __file__       # the caller's line
    r = results[0]
    assert np.asarray(r.info["per_rhs_converged"]).tolist() == [True] * 4
    x = np.asarray(r.x, np.float64)
    true = [np.linalg.norm(B[j] - A.matvec(x[j])) / np.linalg.norm(B[j])
            for j in range(4)]
    if warns:
        assert min(true) > 10 * tol
    else:
        assert max(true) <= 2 * tol
