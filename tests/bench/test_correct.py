"""The comparison that decides ``correct`` fails what it must fail.

Each run goes through ``harness.run`` (everything but ``bench/run.py``'s
look for a chip) at 24 x 24 on the CPU: a sound run is correct; the
control -- the configuration's ``control``, the nearest precision below
its own -- is not; nor is a run with
the timed path broken underneath by each fault the cells can have: a
solve that returns its state unchanged, half of a batch left out, an
answer altered where it is produced, and (on four CPU devices) the halo
exchange between chips left out.
"""
import json
import os
import pathlib
import subprocess
import sys
import time

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import harness, testing  # noqa: E402

MAN = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = {w["traffic"]: w["name"] for w in MAN["workloads"]
         if w["chips"] == 1}
SOLVE, BATCH = CELLS["solve"], CELLS["batch8"]
MESH = [w["name"] for w in MAN["workloads"] if w["chips"] > 1]
SEED = 2 ** 31 + 17


@pytest.fixture
def small(monkeypatch):
    monkeypatch.setattr(harness, "resolve", testing.small_resolve)


def _run(name):
    return harness.run(name, SEED, 0.2, False, t_start=time.perf_counter())


@pytest.mark.parametrize("name", [SOLVE, BATCH])
def test_sound_run_is_correct(small, name):
    out = _run(name)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert list(out)[-1] == "checks"


@pytest.mark.parametrize("name", [SOLVE, BATCH])
def test_bf16_control_is_not_correct(small, name):
    spec = testing.small_resolve(name)

    def control(cfg, traffic):
        return harness.Cell(harness.with_control(cfg), traffic)

    out = harness.run(name, SEED, 0.2, False, t_start=time.perf_counter(),
                      cell_factory=control)
    assert not out["correct"]
    assert out["checks"]["true_res_worst"]["value"] > \
        spec["cfg"]["limits"]["true_res_worst"]


def _wrap(monkeypatch, method, change):
    from repro.core.session import Solver
    orig = getattr(Solver, method)

    def broken(self, *a, **k):
        r = orig(self, *a, **k)
        r.x = change(r.x)
        return r

    monkeypatch.setattr(Solver, method, broken)


def test_solve_returning_its_state_unchanged_fails(small, monkeypatch):
    import jax.numpy as jnp
    _wrap(monkeypatch, "solve", jnp.zeros_like)          # x stays at x0
    assert not _run(SOLVE)["correct"]


def test_answer_altered_where_produced_fails(small, monkeypatch):
    _wrap(monkeypatch, "solve", lambda x: x.at[0].add(1.0))
    assert not _run(SOLVE)["correct"]


def test_half_of_the_batch_left_out_fails(small, monkeypatch):
    def half(x):
        h = x.shape[0] // 2
        return x.at[h:].set(x[:h])       # lanes h.. get others' answers
    _wrap(monkeypatch, "_solve_batched_for_pool", half)
    out = _run(BATCH)
    assert not out["correct"]
    assert out["failed"] >= out["attempted"] // 2


def test_unchanged_batch_fails(small, monkeypatch):
    import jax.numpy as jnp
    _wrap(monkeypatch, "_solve_batched_for_pool", jnp.zeros_like)
    assert not _run(BATCH)["correct"]


MESH_RUN = """
import json, sys, time
sys.path.insert(0, {root!r})
import jax.numpy as jnp
from bench import harness, testing
harness.resolve = testing.small_resolve
if {broken!r}:
    from repro.distributed.operator import DistPoisson
    from repro.kernels import ops as kops

    def no_exchange(self, xflat):
        H, W = self.local_shape
        x = xflat.reshape(H, W)
        zw, zh = jnp.zeros((W,), x.dtype), jnp.zeros((H,), x.dtype)
        return kops.stencil2d_apply(x, zw, zw, zh, zh).reshape(-1)

    DistPoisson.matvec_local = no_exchange
out = harness.run({name!r}, {seed}, 0.2, False, t_start=time.perf_counter())
print(json.dumps(out))
"""


@pytest.mark.parametrize("broken", [False, True],
                         ids=["sound", "halo_exchange_left_out"])
@pytest.mark.parametrize("name", MESH)
def test_mesh_halo_exchange_left_out_fails(name, broken):
    code = MESH_RUN.format(root=str(ROOT), name=name, seed=SEED,
                           broken=broken)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=str(ROOT / "src"),
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["correct"] is (not broken), out["checks"]
