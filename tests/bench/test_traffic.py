"""Each traffic mix's step on the CPU at 24 x 24, driven directly (never
through ``bench/run.py``), and ``bench/run.py``'s refusal to run without
a TPU."""
import json
import os
import pathlib
import shutil
import subprocess
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import harness, testing  # noqa: E402

MAN = json.loads((ROOT / "BENCHMARK.json").read_text())
ONE_CHIP = [w["name"] for w in MAN["workloads"] if w["chips"] == 1]
MESH = [w["name"] for w in MAN["workloads"] if w["chips"] > 1]


def _cpu_env(devices: int = 1) -> dict:
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=str(ROOT / "src"))
    env["XLA_FLAGS"] = (f"--xla_force_host_platform_device_count={devices}"
                        if devices > 1 else "")
    return env


def _steps(name: str, seed: int, steps: int = 2):
    spec = testing.small_resolve(name)
    cell = harness.Cell(spec["cfg"], spec["traffic"])
    ring = cell.ring(seed)
    out = []
    for k in range(steps):
        out += cell.step(ring, k)
    return spec, cell, ring, out


@pytest.mark.parametrize("name", ONE_CHIP)
def test_one_chip_step(name):
    spec, cell, ring, out = _steps(name, seed=2 ** 31 + 11)
    assert len(out) == 2 * cell.batch
    assert [i for i, _ in out] == [k % len(ring) for k in range(len(out))]
    for _, r in out:
        assert r.converged and 0 < r.iters <= spec["cfg"]["solver"]["maxiter"]
    verdict = harness.check(spec["cfg"], harness.host_ring(
        ring, [i for i, _ in out]), harness.to_host(out), len(out))
    assert verdict["correct"], verdict


def test_ring_depends_only_on_the_seed():
    spec = testing.small_resolve(ONE_CHIP[0])
    a = harness.make_ring(spec["cfg"], spec["traffic"], 2 ** 33 + 5)
    b = harness.make_ring(spec["cfg"], spec["traffic"], 2 ** 33 + 5)
    c = harness.make_ring(spec["cfg"], spec["traffic"], 2 ** 33 + 6)
    assert len(a) == spec["traffic"]["ring"]
    assert all(bool((x == y).all()) for x, y in zip(a, b))
    assert not bool((a[0] == c[0]).all())


MESH_STEP = """
import json, sys
sys.path.insert(0, {root!r})
from bench import harness, testing
spec = testing.small_resolve({name!r})
cell = harness.Cell(spec["cfg"], spec["traffic"])
ring = cell.ring(2 ** 31 + 3)
out = cell.step(ring, 0) + cell.step(ring, 1)
answers = harness.to_host(out)
v = harness.check(spec["cfg"], harness.host_ring(ring, [i for i, _ in out]),
                  answers, len(out), len(cell.devices))
print(json.dumps({{"devices": [a[2] for a in answers],
                  "iters": [int(r.iters) for _, r in out],
                  "correct": v["correct"]}}))
"""


@pytest.mark.parametrize("name", MESH)
def test_mesh_step_on_four_cpu_devices(name):
    code = MESH_STEP.format(root=str(ROOT), name=name)
    p = subprocess.run([sys.executable, "-c", code], env=_cpu_env(4),
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    rec = json.loads(p.stdout.strip().splitlines()[-1])
    assert rec["devices"] == [4, 4]
    assert all(0 < k <= testing.MAXITER for k in rec["iters"])
    assert rec["correct"]


def test_run_refuses_without_a_tpu():
    p = subprocess.run(
        [sys.executable, str(ROOT / "bench" / "run.py"), "--workload",
         ONE_CHIP[0], "--seed", "1", "--seconds", "1", "--trace", "0"],
        env=_cpu_env(), capture_output=True, text=True, timeout=300,
        cwd=ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_benchmark_files_alone_cannot_run(tmp_path):
    """A directory with only ``BENCHMARK.json`` and the benchmark's paths
    has no system under test: the cell cannot be built."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    for p in MAN["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    code = ("import sys; sys.path.insert(0, '.')\n"
            "from bench import harness\n"
            f"s = harness.resolve({ONE_CHIP[0]!r})\n"
            "harness.Cell(s['cfg'], s['traffic'])\n")
    env = _cpu_env()
    env.pop("PYTHONPATH")
    p = subprocess.run([sys.executable, "-c", code], env=env, cwd=tmp_path,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert "ModuleNotFoundError" in p.stderr
