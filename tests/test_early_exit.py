"""The p(l)-CG engine stops after the body in which its last lane is done.

A frozen body is a no-op on every output, so the loop that stops early
returns, bit for bit, what the same engine returns with ``iters`` set to
its own exit trip or to 37 bodies more: ``x``, ``k_done``, ``converged``,
``breakdown`` and the per-body ``resnorms`` / ``committed`` buffers (zero
and False past the bodies run).  Checked for a single sweep, a batch
whose lanes finish at different bodies (with a pad lane), an in-loop
restart, ``unroll=2`` and, in a child process on four forced CPU devices,
the mesh sweep, whose devices must agree on the exit trip under every
comm policy.
"""
import json
import subprocess
import sys
import textwrap

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from repro.core import Solver  # noqa: E402
from repro.core.plcg_scan import plcg_scan, stab_iter_slack  # noqa: E402
from repro.core.shifts import chebyshev_shifts, monomial_shifts  # noqa: E402
from repro.operators import poisson2d  # noqa: E402

L, MAXITER = 3, 300
CAP = MAXITER + L + 1
STATE = ("x", "k_done", "converged", "breakdown")
PER_BODY = ("resnorms", "committed")


def _rhs(A, seed):
    """A rough right-hand side (the full spectrum excited)."""
    return np.asarray(A @ np.random.default_rng(seed).standard_normal(A.n))


def _smooth_rhs(A, m=16):
    """A right-hand side close to the lowest eigenvector: a few bodies."""
    i = np.arange(1, m + 1)
    v = np.outer(np.sin(np.pi * i / (m + 1)),
                 np.sin(np.pi * i / (m + 1))).reshape(-1)
    v += 1e-3 * np.random.default_rng(3).standard_normal(v.size)
    return np.asarray(A @ v)


def _sweep(A, iters, **kw):
    return jax.jit(lambda b: plcg_scan(A.matvec, b, l=L, iters=iters, **kw))


def _trip(out) -> int:
    trips = np.asarray(out.trips).reshape(-1)
    assert (trips == trips[0]).all()          # a batch shares its exit
    return int(trips[0])


def _assert_same(a, b, trip: int):
    for f in STATE:
        assert np.array_equal(np.asarray(getattr(a, f)),
                              np.asarray(getattr(b, f))), f
    for f in PER_BODY:
        u, v = np.asarray(getattr(a, f)), np.asarray(getattr(b, f))
        assert np.array_equal(u[..., :trip], v[..., :trip]), f
        assert not u[..., trip:].any() and not v[..., trip:].any(), f


CASES = {
    "single": dict(batch=False, kw=dict(tol=1e-8)),
    "batch_with_pad": dict(batch=True, kw=dict(tol=1e-8)),
    "restart": dict(batch=False, kw=dict(tol=1e-6, restart=2,
                                         k_budget=MAXITER)),
    "unroll2": dict(batch=False, kw=dict(tol=1e-8, unroll=2)),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_early_exit_equals_running_to_the_exit_trip_and_past_it(x64, case):
    A = poisson2d(16, 16)
    spec = CASES[case]
    kw = dict(spec["kw"])
    kw["sigma"] = (monomial_shifts(L) if "restart" in kw
                   else chebyshev_shifts(0, 8, L))
    cap = CAP + stab_iter_slack(L, kw.get("restart"), None, MAXITER)
    if spec["batch"]:
        # lanes that finish at different bodies, and a pad lane that
        # duplicates lane 0 (as SolverPool pads)
        b = jnp.asarray(np.stack([_rhs(A, 0), _smooth_rhs(A), _rhs(A, 1),
                                  _rhs(A, 0)]))
    else:
        b = jnp.asarray(_rhs(A, 0))
    early = _sweep(A, cap, **kw)(b)
    trip = _trip(early)
    assert bool(np.all(early.converged))
    assert trip < cap
    if spec["batch"]:
        k = np.asarray(early.k_done)
        assert len(set(k[:3].tolist())) == 3     # three different exits
        assert trip == L + int(k.max()) + 1      # the last lane's exit
    elif "restart" in kw:
        assert int(early.restarts) >= 1          # a restart was taken
    for iters in (trip, trip + 37):
        ref = _sweep(A, iters, **kw)(b)
        assert _trip(ref) == trip
        _assert_same(early, ref, trip)


def test_frozen_bodies_change_no_output_of_a_finished_lane(x64):
    """Lane 0 finishes first and rides along, frozen, until lane 1 is
    done; it reads bit for bit as in a batch where it is the last lane
    (the same compiled program, so the same arithmetic per lane)."""
    A = poisson2d(16, 16)
    fast, slow = _smooth_rhs(A), _rhs(A, 0)
    run = _sweep(A, CAP, sigma=chebyshev_shifts(0, 8, L), tol=1e-8)
    alone = run(jnp.asarray(np.stack([fast, fast])))
    riding = run(jnp.asarray(np.stack([fast, slow])))
    assert _trip(riding) > _trip(alone)
    for f in STATE + PER_BODY:
        assert np.array_equal(np.asarray(getattr(riding, f))[0],
                              np.asarray(getattr(alone, f))[0]), f


def test_the_loop_runs_every_body_when_nothing_converges(x64):
    A = poisson2d(16, 16)
    iters = 40
    b = jnp.asarray(_rhs(A, 0))
    out = _sweep(A, iters, sigma=chebyshev_shifts(0, 8, L), tol=0.0)(b)
    assert _trip(out) == iters
    assert not bool(out.converged) and not bool(out.breakdown)
    assert int(out.k_done) + 1 == iters - L      # every update committed
    # unroll=2 over an odd count: the remainder body runs, none past it
    out2 = _sweep(A, iters + 1, sigma=chebyshev_shifts(0, 8, L), tol=0.0,
                  unroll=2)(b)
    assert _trip(out2) == iters + 1
    assert int(out2.k_done) == int(out.k_done) + 1


def test_solves_that_stop_at_different_bodies_share_one_program():
    A = poisson2d(16, 16)
    solver = Solver(A, method="plcg_scan", l=L, tol=1e-5, maxiter=MAXITER,
                    spectrum=(0.0, 8.0))
    r1 = solver.solve(jnp.asarray(_rhs(A, 0), jnp.float32))
    r2 = solver.solve(jnp.asarray(_smooth_rhs(A), jnp.float32))
    assert r1.converged and r2.converged and r1.iters != r2.iters
    assert list(solver.compile_counts().values()) == [1]


_MESH = """
import json
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
jax.config.update("jax_enable_x64", True)
from repro.compat import shard_map_compat
from repro.core.comm import as_comm_policy, build_comm_runtime
from repro.core.plcg_scan import plcg_scan
from repro.core.shifts import chebyshev_shifts
from repro.distributed import DistPoisson, plcg_mesh_sweep
from repro.kernels.introspect import count_primitive_in_scan_bodies
from repro.launch.mesh import make_mesh_compat
from repro.operators import poisson2d

L, MAXITER, TOL = 3, 300, 1e-8
CAP = MAXITER + L + 1
mesh = make_mesh_compat((2, 2), ("data", "model"))
op = DistPoisson(16, 16, mesh)
A = poisson2d(16, 16)
sig = tuple(chebyshev_shifts(0, 8, L))
rng = np.random.default_rng(0)
b = jnp.asarray(np.asarray(A @ rng.standard_normal(A.n)).reshape(16, 16))
i = np.arange(1, 17)
v = np.outer(np.sin(np.pi * i / 17), np.sin(np.pi * i / 17))
v += 1e-3 * rng.standard_normal(v.shape)
B = jnp.stack([b, jnp.asarray(np.asarray(A @ v.reshape(-1)).reshape(16, 16)),
               b])
NAMES = ("x", "resnorms", "converged", "breakdown", "k_done", "committed")
res = {}


def same(a, c, trip):
    for name, u, w in zip(NAMES, a[:6], c[:6]):
        u, w = np.asarray(u), np.asarray(w)
        if name in ("resnorms", "committed"):
            ok = (np.array_equal(u[..., :trip], w[..., :trip])
                  and not u[..., trip:].any() and not w[..., trip:].any())
        else:
            ok = np.array_equal(u, w)
        if not ok:
            return name
    return None


for comm, batched in (("blocking", False), ("ring", False),
                      ("blocking", True)):
    rhs = B if batched else b
    key = comm + ("_batch" if batched else "")

    def sweep(iters):
        return plcg_mesh_sweep(op, l=L, iters=iters, sigma=sig, tol=TOL,
                               comm=comm, batched=batched)

    early = sweep(CAP)(rhs, rhs * 0, MAXITER + 1)
    trip = int(np.asarray(early[-1]).reshape(-1)[0])
    res[key] = {"trip": trip, "converged": bool(np.all(early[2])),
                "k_done": np.asarray(early[4]).reshape(-1).tolist(),
                "diff": [same(early, sweep(it)(rhs, rhs * 0, MAXITER + 1),
                              trip) for it in (trip, trip + 37)],
                "pmax": count_primitive_in_scan_bodies(
                    sweep(CAP), "pmax", rhs, rhs * 0, MAXITER + 1)}

# every device leaves the loop at the same trip: the exit predicate each
# one computes, read back per device
per_device = {}
for comm in ("blocking", "overlap", "ring"):
    rt = build_comm_runtime(as_comm_policy(comm), op, L)

    def one(b_blk, x_blk):
        out = plcg_scan(op.matvec_local, b_blk.reshape(-1),
                        x_blk.reshape(-1), l=L, iters=CAP, sigma=sig,
                        tol=TOL, dot_local=op.dot_local,
                        reduce_scalars=op.reduce_scalars, comm=rt,
                        k_budget=MAXITER + 1)
        return jnp.stack([out.trips, out.k_done.astype(jnp.int32),
                          out.converged.astype(jnp.int32)])[None]

    f = jax.jit(shard_map_compat(one, mesh=mesh, in_specs=(op.spec(),) * 2,
                                 out_specs=P(("data", "model")),
                                 check=False))
    per_device[comm] = np.asarray(f(b, b * 0)).tolist()
res["per_device"] = per_device
print(json.dumps(res))
"""


def test_mesh_sweep_exits_together_and_equals_running_on(dist_env):
    env = dict(dist_env,
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(_MESH)],
                         env=env, capture_output=True, text=True,
                         timeout=540)
    assert out.returncode == 0, out.stderr[-3000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    for key in ("blocking", "ring", "blocking_batch"):
        r = res[key]
        assert r["converged"] and r["trip"] < CAP, key
        assert r["trip"] == L + max(r["k_done"]) + 1, key
        assert r["diff"] == [None, None], key
        # the ring agrees its exit with one scalar pmax per trip; the
        # replicated reductions of the other policies need none
        assert r["pmax"] == [1 if key == "ring" else 0], key
    assert len(set(res["blocking_batch"]["k_done"][:2])) == 2
    for comm, rows in res["per_device"].items():
        assert len(rows) == 4 and all(row == rows[0] for row in rows), comm
        assert rows[0][2] == 1 and rows[0][0] < CAP, comm
