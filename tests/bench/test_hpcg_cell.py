"""The cell ``hpcg256.set50`` through ``harness.run`` (everything but
``bench/run.py``'s look for a chip) at 16^3 on the CPU, with a 3-D
shrink of its own (``bench/testing.py`` shrinks to 2-D grids): a sound
run is correct; the control -- the configuration's ``control``, the
nearest precision below its own -- is not; nor is a solve that returns
its state unchanged; and two seeds make every solve do the same work.
"""
import json
import pathlib
import sys
import time

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import harness  # noqa: E402

MAN = json.loads((ROOT / "BENCHMARK.json").read_text())
CELL = "hpcg256.set50"
GRID = [16, 16, 16]
SEEDS = (2 ** 31 + 17, 2 ** 33 + 5)
_RESOLVE = harness.resolve


def small_resolve(name, root=harness.ROOT):
    """``harness.resolve`` of the cell at 16^3: only the grid shrinks;
    the solver settings, the traffic and the limits stay as committed."""
    spec = _RESOLVE(name, root)
    spec["cfg"] = dict(spec["cfg"], grid=GRID)
    return spec


@pytest.fixture
def small(monkeypatch):
    monkeypatch.setattr(harness, "resolve", small_resolve)


@pytest.fixture(scope="module")
def cells():
    """One prepared cell per configuration, shared by the tests (a cell
    compiles its V-cycle twice: in the spectrum estimate and the sweep)."""
    built = {}

    def get(cfg, traffic):
        key = json.dumps(cfg, sort_keys=True)
        if key not in built:
            built[key] = harness.Cell(cfg, traffic)
        return built[key]

    return get


def _run(cell_factory):
    return harness.run(CELL, SEEDS[0], 0.2, False,
                       t_start=time.perf_counter(), cell_factory=cell_factory)


def test_cell_is_in_the_manifest():
    cell = {w["name"]: w for w in MAN["workloads"]}[CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "hpcg-256", "set50", 1)
    cfg = _RESOLVE(CELL)["cfg"]
    assert cfg["grid"] == [256, 256, 256] and cfg["operator"] == "hpcg27"
    assert cfg["solver"]["M"] == "mg" and cfg["solver"]["tol"] == 0.0


def test_sound_run_is_correct(small, cells):
    out = _run(cells)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0


def test_bf16_control_is_not_correct(small, cells):
    def control(cfg, traffic):
        return cells(harness.with_control(cfg), traffic)

    out = _run(control)
    assert not out["correct"]
    assert out["checks"]["true_res_worst"]["value"] > \
        small_resolve(CELL)["cfg"]["limits"]["true_res_worst"]


def test_solve_returning_its_state_unchanged_fails(small, cells,
                                                   monkeypatch):
    import jax.numpy as jnp
    from repro.core.session import Solver
    orig = Solver.solve

    def unchanged(self, *a, **k):
        r = orig(self, *a, **k)
        r.x = jnp.zeros_like(r.x)                 # x stays at x0
        return r

    monkeypatch.setattr(Solver, "solve", unchanged)
    assert not _run(cells)["correct"]


def test_every_seed_does_the_same_work(cells):
    """The ring entries of two seeds are ``b`` scaled by different powers
    of two: every solve runs the same iterations, breakdowns and restarts,
    and its ``x`` is one answer scaled, bit for bit."""
    spec = small_resolve(CELL)
    cell = cells(spec["cfg"], spec["traffic"])
    runs = []
    for seed in SEEDS:
        ring = cell.ring(seed)
        for k in range(len(ring)):
            [(i, r)] = cell.step(ring, k)
            scale = np.asarray(ring[i]).flat[0] / 19    # a corner's b: 19 s
            runs.append((r.iters, r.breakdowns, r.restarts,
                         np.asarray(r.x) / np.float32(scale)))
    scales = {float(np.asarray(ring[i]).flat[0]) for i in range(len(ring))}
    assert len(scales) > 1                        # the entries do differ
    first = runs[0]
    for iters, brk, rst, x in runs[1:]:
        assert (iters, brk, rst) == first[:3]
        assert np.array_equal(x, first[3])
