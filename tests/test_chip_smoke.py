"""CPU rehearsal of ``chip_smoke.py``: it refuses to run without a TPU, and
its phase functions, at a small size with interpret-mode kernels, compute
the float64 residual and the per-phase record the chip run prints."""
import importlib.util
import json
import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(REPO, "chip_smoke.py")


@pytest.fixture(scope="module")
def cs():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def clock(cs):
    return cs.CompileClock()


RECORD_KEYS = {"phase", "what", "platform", "kind", "count", "grid",
               "compile_s", "solve_s", "iters", "converged", "true_rel_res",
               "kernels", "backend"}


def test_refuses_to_run_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, SCRIPT], env=env, cwd=REPO,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout
    assert "no TPU" in out.stderr


@pytest.mark.parametrize("backend,tier", [(None, None), ("auto", "ref"),
                                          ("fused", "fused")])
def test_phase_solve_rehearsal(cs, clock, capsys, backend, tier):
    A, b = cs.make_problem(48, 0)
    rec = cs.check(cs.phase_solve("a", A, b, clock, backend=backend,
                                  grid=48),
                   tier=tier)
    assert RECORD_KEYS <= set(rec)
    assert rec["backend"] == tier           # "auto" is "ref" off the chip
    assert rec["converged"] and rec["true_rel_res"] <= 10 * cs.TOL
    assert rec["kernels"] == 0              # interpret mode: no TPU kernel
    assert rec["platform"] == "cpu"
    json.dumps(rec)                         # the line the chip run prints
    assert capsys.readouterr().out == ""    # phases print nothing


def test_phase_pool_rehearsal(cs, clock):
    A, B = cs.make_problem(24, 1, nrhs=5)
    rec = cs.check(cs.phase_pool("c", A, B, clock, backend="fused", grid=24,
                                 max_batch=4),
                   tier="fused")
    assert rec["requests"] == 5 and rec["batches"] == 2
    assert rec["memory"]["temp_size_in_bytes"] > 0
    assert rec["true_rel_res"] <= 10 * cs.TOL


def test_true_residual_is_float64_on_the_host(cs):
    import numpy as np
    from repro.operators import poisson2d_dense
    A, b = cs.make_problem(16, 2)
    x = np.linalg.solve(poisson2d_dense(16), b.astype(np.float64))
    assert cs.true_rel_residual(A, b, x) < 1e-12
    assert cs.true_rel_residual(A, b, np.zeros_like(x)) == pytest.approx(1.0)


@pytest.mark.parametrize("change,tier", [
    ({"converged": False}, None),
    ({"true_rel_res": 2e-4}, None),
    ({"kernels": 0}, "fused"),
    ({"kernels": 2}, "fused"),
    ({"kernels": 2}, "pallas"),
])
def test_check_rejects(cs, change, tier):
    rec = {"phase": "x", "what": "w", "platform": "tpu", "converged": True,
           "true_rel_res": 1e-6, "tol": 1e-5,
           "kernels": 1 if tier == "fused" else 3}
    cs.check(dict(rec), tier=tier)
    with pytest.raises(cs.PhaseFailure):
        cs.check({**rec, **change}, tier=tier)


def test_phase_mesh_rehearsal(dist_env):
    """The --mesh phases on a 2x2 mesh of forced host devices."""
    code = textwrap.dedent(f"""
        import importlib.util, json
        spec = importlib.util.spec_from_file_location("cs", {SCRIPT!r})
        cs = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(cs)
        from repro.launch.mesh import make_mesh_compat
        clock = cs.CompileClock()
        mesh = make_mesh_compat((2, 2), ("data", "model"))
        A, b = cs.make_problem(32, 0)
        recs = [cs.check(cs.phase_mesh("mesh", A, b.reshape(32, 32), mesh,
                                       clock, method=m, comm=c, grid=32))
                for m, c in (("plcg_scan", "blocking"),
                             ("plcg_scan", "overlap"), ("cg", None))]
        print(json.dumps(recs))
    """)
    out = subprocess.run([sys.executable, "-c", code], env=dist_env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    recs = json.loads(out.stdout.strip().splitlines()[-1])
    assert [r["psums_per_iter"] for r in recs] == [1, 0, 2]
    assert all(r["solution_devices"] == 4 for r in recs)
