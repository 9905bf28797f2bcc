"""HPCG's 27-point operator, its multigrid V-cycle and the preconditioned
p(3)-CG path, against the plain float64 reference
(``tests/hpcg_reference.py``); the spans, counters and scopes of that
path; and the unpreconditioned engine's answers, bit for bit those of
the engine before the preconditioned path gained its counter and scopes.
"""
import pathlib
import re
import sys

import hpcg_reference as R
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import Solver, precond, telemetry
from repro.core.precond import Multigrid, Preconditioner
from repro.operators import hpcg27, poisson3d

ROOT = pathlib.Path(__file__).resolve().parents[1]
N = 16
KW = dict(method="plcg_scan", l=3, tol=0.0, M="mg")


def _ones_rhs(n):
    """HPCG's ``b = A 1`` on an ``n^3`` grid, flat, float64."""
    return R.apply(np.ones((n, n, n))).reshape(-1)


def _true_res(b, x, n):
    x = np.asarray(x, np.float64).reshape(n, n, n)
    return float(np.linalg.norm(b - R.apply(x).reshape(-1))
                 / np.linalg.norm(b))


@pytest.fixture(scope="module")
def solver():
    """A prepared 16^3 solve with the V-cycle named, five iterations: short
    of the first square-root breakdown of float32 p(3)-CG there (at 7)."""
    return Solver(hpcg27(N), maxiter=5, **KW)


@pytest.fixture(scope="module")
def mg16(solver):
    return solver.M


@pytest.mark.parametrize("grid", [(6, 6, 6), (4, 5, 6)])
@pytest.mark.parametrize("xp", ["numpy", "jax"])
def test_hpcg27_is_the_dense_27_point_matrix(grid, xp):
    nx, ny, nz = grid
    n = nx * ny * nz
    dense = np.zeros((n, n))
    idx = np.arange(n).reshape(grid)
    for i, j, k in np.ndindex(*grid):
        for d in [(a, b, c) for a in (-1, 0, 1) for b in (-1, 0, 1)
                  for c in (-1, 0, 1)]:
            p, q, r = i + d[0], j + d[1], k + d[2]
            if 0 <= p < nx and 0 <= q < ny and 0 <= r < nz:
                dense[idx[i, j, k], idx[p, q, r]] = 26.0 if d == (0, 0, 0) \
                    else -1.0
    A = hpcg27(*grid)
    assert A.n == n and A.stencil27 == grid
    assert np.array_equal(A.diag, np.diag(dense))
    v = np.random.default_rng(0).standard_normal(n)
    got = A.matvec(v if xp == "numpy" else jnp.asarray(v, jnp.float32))
    np.testing.assert_allclose(np.asarray(got), dense @ v,
                               rtol=0, atol=1e-12 if xp == "numpy" else 1e-4)


#: float32 against float64: one V-cycle is ~60 dependent colour updates
#: per level, each a sum of 26 neighbours divided by 26, and the smoothing
#: contracts earlier errors; measured 4.9e-8 relative (16^3 and 32^3 on
#: the CPU, 7.5e-8 at 256^3 on the chip), so 1e-6 is 16 unit roundoffs
F32_VCYCLE_RTOL = 1e-6


@pytest.mark.parametrize("dtype", ["float64", "float32"])
def test_vcycle_matches_the_reference(mg16, dtype, request):
    if dtype == "float64":
        request.getfixturevalue("x64")
    rng = np.random.default_rng(1)
    for _ in range(3):
        u = rng.standard_normal(N ** 3)
        got = np.asarray(mg16.apply(jnp.asarray(u, dtype)), np.float64)
        want = R.vcycle(u.reshape(N, N, N)).reshape(-1)
        err = np.linalg.norm(got - want) / np.linalg.norm(want)
        assert err <= (1e-12 if dtype == "float64" else F32_VCYCLE_RTOL)


def _colour_order_fault(r, x):
    """A planted fault: the sweep run over colours 0..7 and back 7..0.  It
    ends on the coarse points' colour, whose residual is then at rounding
    size, so the V-cycle's coarse levels do nothing (at 256^3 the cell's
    set then misses its limit; PERF.md section 2)."""
    x = list(x)
    for col in (0, 1, 2, 3, 4, 5, 6, 7, 6, 5, 4, 3, 2, 1, 0):
        x[col] = (r[col] + precond._neighbour_sum(x, col)) / 26.0
    return x


def test_planted_colour_order_fault_is_caught(monkeypatch):
    monkeypatch.setattr(precond, "_symgs", _colour_order_fault)
    mg = Multigrid(hpcg27(N))
    u = np.random.default_rng(1).standard_normal(N ** 3)
    got = np.asarray(mg.apply(jnp.asarray(u, jnp.float32)), np.float64)
    want = R.vcycle(u.reshape(N, N, N)).reshape(-1)
    err = np.linalg.norm(got - want) / np.linalg.norm(want)
    assert err > 1e3 * F32_VCYCLE_RTOL, err


def test_vcycle_is_symmetric_and_positive(mg16, x64):
    rng = np.random.default_rng(2)
    for _ in range(3):
        u, v = (jnp.asarray(rng.standard_normal(N ** 3)) for _ in range(2))
        mu, mv = mg16.apply(u), mg16.apply(v)
        uv, vu = float(jnp.vdot(u, mv)), float(jnp.vdot(mu, v))
        assert abs(uv - vu) <= 1e-12 * abs(uv)
        assert float(jnp.vdot(u, mu)) > 0


def test_spectrum_comes_from_the_power_iteration(mg16):
    lo, hi = mg16.precond_spectrum()
    assert lo == 0.0
    # lam_max(M^-1 A) of a symmetric V-cycle with Gauss-Seidel smoothing
    # lies just under 1; the interval adds 5%
    assert 1.0 < hi <= 1.05


def test_named_preconditioner_is_built_from_the_operator(solver):
    assert isinstance(solver.M, Multigrid) and solver.M.grid == (N,) * 3
    assert solver.spectrum == solver.M.precond_spectrum()
    with pytest.raises(TypeError, match="known names: mg"):
        Solver(hpcg27(N), method="plcg_scan", M="amg")
    # a 3-D operator of another stencil is refused, not given a V-cycle
    # built for the 27-point matrix
    with pytest.raises(ValueError, match="stencil27 hint"):
        Solver(poisson3d(16), method="plcg_scan", M="mg")
    with pytest.raises(ValueError, match="divisible by 2\\*\\*levels"):
        Multigrid(hpcg27(24))
    with pytest.raises(ValueError, match="runs on one device"):
        solver.M.local_apply(None)


def test_plcg_iterates_are_the_reference_pcg_iterates(mg16, x64):
    """Before the float64 floor, p(3)-CG with the V-cycle builds CG's
    Krylov space: a set of ``maxiter = 8`` (the engine counts ``x0`` as
    its first update) returns the reference PCG's iterate 7."""
    b = _ones_rhs(N)
    x_ref, _ = R.pcg(b.reshape(N, N, N), 7)
    r = Solver(hpcg27(N), maxiter=8, **dict(KW, M=mg16)).solve(
        jnp.asarray(b))
    assert r.iters == 8 and r.breakdowns == 0
    x = np.asarray(r.x).reshape(N, N, N)
    assert np.linalg.norm(x - x_ref) <= 1e-10 * np.linalg.norm(x_ref)


def test_fifty_iteration_set_reaches_the_reference_pcg(x64):
    """A 50-iteration set at 32^3 (``maxiter = 51``: the engine counts
    ``x0`` as its first update): the true residual is within a factor of
    10 of the reference PCG's after 50 iterations.  Both end at the
    float64 floor, so the factor is rounding, not convergence."""
    n = 32
    b = _ones_rhs(n)
    _, hist = R.pcg(b.reshape(n, n, n), 50)
    r = Solver(hpcg27(n), maxiter=51, **KW).solve(jnp.asarray(b))
    assert r.iters == 51
    assert _true_res(b, r.x, n) <= 10 * hist[-1]


def test_power_of_two_scaled_b_does_the_same_work(solver):
    """``b`` times ``+-2^k`` gives the same iterations, breakdowns and
    residual trace times ``2^k``, and ``x`` times ``+-2^k``, bit for bit
    in float32: every seed of ``bench/inputs/hpcg_ones.py`` does the
    same work."""
    b = jnp.asarray(_ones_rhs(N), jnp.float32)
    base = solver.solve(b)
    x0 = np.asarray(base.x)
    for s in (2.0 ** -3, -(2.0 ** 4)):
        r = solver.solve(b * s)
        assert (r.iters, r.breakdowns, r.restarts) == (
            base.iters, base.breakdowns, base.restarts)
        assert np.array_equal(np.asarray(r.resnorms),
                              np.asarray(base.resnorms) * abs(s))
        assert np.array_equal(np.asarray(r.x), x0 * np.float32(s))


class _Counted(Preconditioner):
    """A V-cycle whose every apply the program runs calls back to the host
    and is counted there."""

    name = "mg-counted"

    def __init__(self, mg):
        self.mg, self.ran = mg, 0

    def _tick(self):
        self.ran += 1

    def apply(self, v):
        jax.debug.callback(self._tick)
        return self.mg.apply(v)

    def precond_spectrum(self, base=(0.0, 8.0)):
        return self.mg.precond_spectrum(base)


def test_vcycles_counted_and_scopes_in_the_hlo(solver, mg16):
    """``precond_applies`` of a prepared solve is the number of V-cycles
    the program ran (counted by a host callback in each apply: two in
    init, one a body), and ``syncs`` stays 4 a solve; every op of the
    V-cycle carries a ``mg.*`` scope under ``plcg.precond``."""
    maxiter = solver.maxiter
    counted = _Counted(mg16)
    b = jnp.asarray(_ones_rhs(N), jnp.float32)
    prepared = Solver(hpcg27(N), maxiter=maxiter, **dict(KW, M=counted))
    prepared.solve(b)                             # compiles
    jax.effects_barrier()
    counted.ran = 0
    telemetry.clear()
    prepared.solve(b)
    prepared.solve(b)
    jax.effects_barrier()
    roots = [r for r in telemetry.roots() if r.name == "solver.solve"]
    assert len(roots) == 2
    for root in roots:
        c = root.counters
        assert c["syncs"] == 4
        assert c["bodies"] == 3 + maxiter
    assert sum(r.counters["precond_applies"] for r in roots) == counted.ran
    assert counted.ran == 2 * (2 + 3 + maxiter)
    # the lowered program's op locations carry each op's scope path
    txt = solver.lower(b).as_text(debug_info=True)
    names = set(re.findall(r'loc\("([^"]*)"', txt))
    found = {m for name in names
             for m in re.findall(r"(?:mg|plcg)\.[a-z]+", name)}
    assert {"plcg.precond", "mg.smooth", "mg.residual", "mg.restrict",
            "mg.prolong", "mg.coarse"} <= found
    assert all("plcg.precond/" in name for name in names if "/mg." in name)
    telemetry.clear()


@pytest.mark.parametrize("name", ["p2d1000.solve", "p2d1000.batch8"])
def test_unpreconditioned_engine_is_unchanged_without_m(name):
    """The ``poisson2d`` cells at 24^2 (``bench.testing``), two steps:
    every answer and iteration count is bit for bit what the engine gave
    before the preconditioned path gained its counter and scopes
    (recorded in ``tests/data``)."""
    sys.path.insert(0, str(ROOT))
    from bench import harness, testing
    want = np.load(ROOT / "tests" / "data"
                   / "poisson24_x_before_multigrid.npz")
    spec = testing.small_resolve(name)
    cell = harness.Cell(spec["cfg"], spec["traffic"])
    ring = cell.ring(2 ** 31 + 17)
    res = cell.step(ring, 0) + cell.step(ring, 1)
    x = np.stack([np.asarray(r.x) for _, r in res])
    assert np.array_equal(x, want[name])
    assert [r.iters for _, r in res] == want[name + ".iters"].tolist()
