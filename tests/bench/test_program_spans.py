"""``bench/program_spans.py`` and the six readers of the program's own
spans and counters, on records from real tiny solves on the CPU; and the
program's spans in a recorded CPU profile, read by ``bench/trace.py``."""
import pathlib
import sys
import types

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import harness, program_spans as ps, trace as tr  # noqa: E402

L = 2
KW = dict(method="plcg_scan", l=L, tol=1e-5, maxiter=60,
          spectrum=(0.0, 8.0))


def _reader(name):
    return harness.load_module(ROOT / "bench" / "metrics" / f"{name}.py")


@pytest.fixture
def store():
    from repro.core import telemetry
    telemetry.clear()
    yield telemetry
    telemetry.clear()


@pytest.fixture
def problem():
    import jax.numpy as jnp
    from repro.operators import poisson2d
    A = poisson2d(16)
    rng = np.random.default_rng(1)
    return A, jnp.asarray(rng.standard_normal((8, A.n)), jnp.float32)


def _window(traffic, store, problem, steps=2):
    """A warm-up step then ``steps`` steps of the cell's loop shape; the
    run record the harness would give the readers."""
    from repro.core import Solver, SolverPool
    A, B = problem
    solver = Solver(A, **KW)
    if traffic == "solve":
        def step(k):
            solver.solve(B[k])
        batch = 1
    else:
        pool = SolverPool(solver, max_batch=4, pad_to=(4,))

        def step(k):
            hs = [pool.submit(b) for b in B[4 * (k % 2): 4 * (k % 2) + 4]]
            pool.flush()
            [h.result() for h in hs]
        batch = 4
    step(0)                                       # warm-up: compiles
    warm = store.roots()
    for k in range(1, steps + 1):
        step(k)
    run = {"rhs": steps * batch, "steps": steps}
    return types.SimpleNamespace(run=run), warm, store.roots()[len(warm):]


@pytest.mark.parametrize("traffic", ["solve", "batch"])
def test_readers_read_the_window_and_never_the_warm_up(traffic, store,
                                                       problem):
    ctx, warm, window = _window(traffic, store, problem)
    work = [r for r in window if r.name in ps.WORK]
    assert [r.name for r in ps.window_roots(ctx)] == [r.name for r in window]
    assert any(s.attrs.get("compiled") for r in warm for s in r.spans)
    assert not any(s.attrs.get("compiled") for r in window for s in r.spans)
    rhs = ctx.run["rhs"]
    bodies = sum(r.counters["bodies"] for r in work)
    useful = sum(r.counters["useful"] for r in work)
    got = {m: _reader(f"{m}.{traffic}").read(ctx)
           for m in ("front_end_ms", "useful_body_pct", "host_syncs")}
    assert got["front_end_ms"] == pytest.approx(
        sum(ps.self_ns(r) for r in window) * 1e-6 / rhs)
    assert got["useful_body_pct"] == pytest.approx(100 * useful / bodies)
    assert 0 < got["useful_body_pct"] < 100
    assert got["host_syncs"] == {"solve": 4.0, "batch": 5 / 4}[traffic]
    # the warm-up's compile would have dominated the front end
    warm_ms = sum(ps.self_ns(r) for r in warm) * 1e-6
    assert got["front_end_ms"] < warm_ms


def test_a_window_the_store_no_longer_holds_reads_none(store, problem):
    ctx, _, _ = _window("solve", store, problem)
    bigger = types.SimpleNamespace(run={"rhs": 50, "steps": 50})
    for name in ("front_end_ms.solve", "useful_body_pct.solve",
                 "host_syncs.solve"):
        assert _reader(name).read(bigger) is None
    for k in range(store.MAX_ROOTS):              # push the window out
        with store.span("solver.submit", requests=(-1 - k,)):
            pass
    assert ps.window_roots(ctx) is None


def test_a_program_without_telemetry_reads_none(monkeypatch):
    monkeypatch.setitem(sys.modules, "repro.core.telemetry", None)
    ctx = types.SimpleNamespace(run={"rhs": 1, "steps": 1})
    for name in ("front_end_ms.batch", "useful_body_pct.batch",
                 "host_syncs.batch"):
        assert _reader(name).read(ctx) is None


def test_self_time_is_the_duration_less_the_wait_cover(store, problem):
    from repro.core.telemetry import Span
    root = Span("solver.solve", 1, None, start_ns=0, end_ns=100, spans=[
        Span("plcg.wait", 2, 1, 10, 30), Span("plcg.wait", 3, 1, 20, 40),
        Span("plcg.fetch", 4, 1, 40, 50), Span("plcg.wait", 5, 1, 60, 70)])
    assert ps.self_ns(root) == 100 - 40            # overlap counted once
    _, _, window = _window("solve", store, problem, steps=1)
    (real,) = window
    (wait,) = [s for s in real.spans if s.name == "plcg.wait"]
    assert ps.self_ns(real) == real.duration_ns - wait.duration_ns


def test_program_spans_land_in_a_cpu_profile_on_its_clock(tmp_path, store,
                                                          problem):
    import jax
    from repro.core import Solver
    A, B = problem
    solver = Solver(A, **KW)
    jax.block_until_ready(solver.solve(B[0]).x)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation("solve"):
            jax.block_until_ready(solver.solve(B[1]).x)
    finally:
        jax.profiler.stop_trace()
    t = tr.load(str(next(tmp_path.rglob("*.xplane.pb"))), cpu_ok=True)
    (outer,) = [(s, e) for n, s, e in t.host if n == "solve"]
    (root,) = [(s, e) for n, s, e in t.host if n == "solver.solve"]
    assert outer[0] <= root[0] <= root[1] <= outer[1]
    mine = [(n, s, e) for n, s, e in t.host if n.startswith("plcg.")]
    rec = store.roots()[-1]
    assert [n for n, _, _ in sorted(mine, key=lambda ev: ev[1])] == \
        [s.name for s in rec.spans]
    assert all(root[0] <= s <= e <= root[1] for _, s, e in mine)
    # the device's ops run inside the plcg.wait span of the same clock
    (wait,) = [(s, e) for n, s, e in mine if n == "plcg.wait"]
    ops = tr.busy(t.devices["/host:CPU"], *root)
    assert tr.measure(tr.clip(ops, *wait)) > 0
