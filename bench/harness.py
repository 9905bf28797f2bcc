"""The benchmark's driver: one cell, one seed, one timed window.

Everything cell-specific is found by name through ``BENCHMARK.json``: the
configuration file (operator, grid, dtype, mesh, the ``solver`` settings
handed to ``Solver`` as they stand, limits), its right-hand sides
``bench/inputs/<rhs>.py``, the traffic mix ``bench/traffic/<traffic>.json``
and the loop it names, ``bench/loops/<loop>.py`` (set-up and one step),
the plain reference ``bench/references/<operator>.py``, and one reader per
metric, ``bench/metrics/<metric>.py``.  A new configuration, mix, loop or
metric is new files and entries; nothing here changes.  The system under
test is the ``repro`` package under ``src/``: a prepared ``Solver``, on a
mesh where the configuration names one.
"""
from __future__ import annotations

import contextlib
import gc
import importlib.util
import json
import math
import os
import pathlib
import shutil
import sys
import tempfile
import time
import types

import numpy as np

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent


# ---- finding things by name -------------------------------------------------

def manifest(root: pathlib.Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def load_module(path: pathlib.Path) -> types.ModuleType:
    """Import a file by path (metric readers have dots in their names)."""
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def resolve(name: str, root: pathlib.Path = ROOT) -> dict:
    """Everything one cell needs, from its name in ``BENCHMARK.json``."""
    man = manifest(root)
    cells = {w["name"]: w for w in man["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; known: {sorted(cells)}")
    cell = cells[name]
    conf = {c["name"]: c for c in man["configs"]}[cell["config"]]
    cfg = json.loads((root / conf["file"]).read_text())
    traffic = json.loads(
        (root / "bench" / "traffic" / f"{cell['traffic']}.json").read_text())
    e2e = [m for m in man["end_to_end"]
           if name in m.get("workloads", [name])]
    reported = {m["name"] for m in e2e}
    per_layer = [m for m in man["per_layer"]
                 if name in m.get("workloads", [name])
                 and m["moves"] in reported]
    return {"cell": cell, "cfg": cfg, "traffic": traffic, "e2e": e2e,
            "per_layer": per_layer, "root": root}


def with_control(cfg: dict) -> dict:
    """The configuration with its ``control`` (the nearest lower precision,
    see ``PERF.md``) laid over it: top-level keys replaced, ``solver``
    keys merged."""
    ctl = dict(cfg.get("control", {}))
    out = dict(cfg, **{k: v for k, v in ctl.items() if k != "solver"})
    out["solver"] = dict(cfg["solver"], **ctl.get("solver", {}))
    return out


# ---- the device and the compile cache ---------------------------------------

def enable_compile_cache() -> str:
    """JAX's persistent compilation cache: ``$JAX_COMPILATION_CACHE_DIR``
    when set, else the fixed directory ``<checkout>/.jax_cache``."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_enable_compilation_cache", True)
    # cache every program, so that a warm run compiles nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


class CompileClock:
    """Compilations JAX starts (persistent-cache hits included), and the
    seconds they take, since construction."""

    _EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
               "/jax/core/compile/jaxpr_to_mlir_module_duration",
               "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax.monitoring
        self.seconds = 0.0
        self.compiles = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _duration(self, event, duration, **_):
        if event in self._EVENTS:
            self.seconds += duration
            if event == self._EVENTS[-1]:
                self.compiles += 1


def device_info(devices) -> dict:
    import jax
    d0 = devices[0]
    return {"platform": d0.platform, "kind": d0.device_kind,
            "count": len(jax.devices())}


def memory_peak(devices) -> int:
    """Peak bytes in use on the fullest of ``devices``."""
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


# ---- inputs -----------------------------------------------------------------

def key_words(seed: int) -> np.ndarray:
    """Two 32-bit words of a threefry key, from a seed of any size."""
    return np.random.SeedSequence(int(seed) % (1 << 64)).generate_state(
        2, dtype=np.uint32)


def make_ring(cfg: dict, traffic: dict, seed: int, sharding=None,
              flat: bool = True, root: pathlib.Path = ROOT) -> list:
    """``traffic["ring"]`` right-hand sides ``b_i = A x_hat_i``, with
    ``x_hat_i`` from ``bench/inputs/<cfg["rhs"]>.py``, made on the device
    from ``seed`` by the benchmark's own copy of the operator, in the
    configuration's dtype."""
    import jax
    import jax.numpy as jnp
    ref = load_module(root / "bench" / "references" / f"{cfg['operator']}.py")
    rhs = load_module(root / "bench" / "inputs" / f"{cfg['rhs']}.py")
    grid = tuple(cfg["grid"])
    dtype = jnp.dtype(cfg["dtype"])

    def one(words, i):
        key = jax.random.fold_in(jax.random.wrap_key_data(words), i)
        b = ref.apply(rhs.x_hat(key, grid, dtype), xp=jnp)
        return b.reshape(-1) if flat else b

    fn = jax.jit(one, out_shardings=sharding)
    words = jnp.asarray(key_words(seed))
    ring = [fn(words, jnp.uint32(i)) for i in range(traffic["ring"])]
    jax.block_until_ready(ring)
    return ring


# ---- the system under test --------------------------------------------------

def _hashable(v):
    return tuple(v) if isinstance(v, list) else v


class Cell:
    """The prepared solver of one configuration under one traffic mix, and
    the state of the mix's loop (``bench/loops/<traffic["loop"]>.py``)."""

    def __init__(self, cfg: dict, traffic: dict, root: pathlib.Path = ROOT):
        if str(ROOT / "src") not in sys.path:
            sys.path.insert(0, str(ROOT / "src"))
        import jax
        if np.dtype(cfg["dtype"]).itemsize == 8:
            jax.config.update("jax_enable_x64", True)
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro import operators
        from repro.core import Solver
        self.cfg, self.traffic, self.root = cfg, traffic, root
        self.batch = int(traffic["batch"])
        self.mesh = None
        self.sharding = None
        self.devices = jax.devices()[:1]
        if cfg.get("mesh"):
            from repro.launch.mesh import make_mesh_compat
            self.mesh = make_mesh_compat(tuple(cfg["mesh"]),
                                         ("data", "model"))
            self.sharding = NamedSharding(self.mesh, P("data", "model"))
            self.devices = list(self.mesh.devices.flat)
        A = getattr(operators, cfg["operator"])(*cfg["grid"])
        knobs = {k: _hashable(v) for k, v in cfg["solver"].items()}
        self.solver = Solver(A, mesh=self.mesh, **knobs)
        self.loop = load_module(root / "bench" / "loops"
                                / f"{traffic['loop']}.py")
        self.state = self.loop.setup(self)

    @property
    def n_local(self) -> int:
        n = math.prod(self.cfg["grid"])
        return n // len(self.devices)

    def ring(self, seed: int) -> list:
        return make_ring(self.cfg, self.traffic, seed,
                         sharding=self.sharding, flat=self.mesh is None,
                         root=self.root)

    def step(self, ring: list, k: int) -> list:
        """Step ``k`` of the loop: ``[(ring index, SolveResult)]`` of the
        right-hand sides it solved."""
        return self.loop.step(self, ring, k)


def run_window(cell: Cell, ring: list, seconds: float) -> dict:
    """The loop's own ``window`` where it has one; else a closed loop for
    ``seconds`` in which the step that crosses the end completes, and the
    window ends with it."""
    if hasattr(cell.loop, "window"):
        return cell.loop.window(cell, ring, seconds)
    results, k = [], 0
    t0 = time.perf_counter()
    while True:
        results += cell.step(ring, k)
        k += 1
        t1 = time.perf_counter()
        if t1 - t0 >= seconds:
            break
    return {"results": results, "expected": k * cell.batch, "steps": k,
            "window_s": t1 - t0}


# ---- correctness ------------------------------------------------------------

def check(cfg: dict, ring_host: dict, answers: list, expected: int,
          mesh_size: int = 0) -> dict:
    """Compare every solution of the window with the plain reference.

    ``answers`` holds ``(ring index, x on the host or None, devices x
    spanned)``.  Returns ``{"checks": {name: {"value", "limit"}},
    "failed": n, "correct": bool}``: the float64 true relative residual
    of the worst solution, answers that never came, and on a mesh
    solutions that did not span every device."""
    ref = load_module(BENCH / "references" / f"{cfg['operator']}.py")
    limits = cfg["limits"]
    n = math.prod(cfg["grid"])
    worst, over, unsharded, came = 0.0, 0, 0, 0
    for i, x, ndev in answers:
        if x is None or int(np.size(x)) != n:
            continue
        came += 1
        if mesh_size and ndev != mesh_size:
            unsharded += 1
        res = ref.true_rel_residual(ring_host[i], x, cfg["grid"])
        if not math.isfinite(res):
            res = math.inf
        over += res > limits["true_res_worst"]
        worst = max(worst, res)
    missing = expected - came
    checks = {"true_res_worst": {
        "value": worst if math.isfinite(worst) else None,
        "limit": limits["true_res_worst"]},
        "missing": {"value": missing, "limit": limits["missing"]}}
    if mesh_size:
        checks["unsharded"] = {"value": unsharded,
                               "limit": limits["unsharded"]}
    correct = all(c["value"] is not None and c["value"] <= c["limit"]
                  for c in checks.values())
    return {"checks": checks, "failed": over + missing, "correct": correct}


def to_host(results: list) -> list:
    """``(ring index, x as a host array, devices x spanned)`` of every
    result; frees the device copies."""
    out = []
    for i, r in results:
        x = getattr(r, "x", None)
        devs = getattr(getattr(x, "sharding", None), "device_set", ())
        out.append((i, None if x is None else np.asarray(x), len(devs)))
    return out


def host_ring(ring: list, used) -> dict:
    return {i: np.asarray(ring[i]) for i in sorted(set(used))}


# ---- metrics ----------------------------------------------------------------

def read_metrics(specs: list, ctx, root: pathlib.Path = ROOT) -> dict:
    """Each metric's reader ``bench/metrics/<name>.py`` on ``ctx``; a
    reader that finds nothing to read returns ``None`` and the metric is
    left out."""
    out = {}
    for m in specs:
        reader = load_module(root / "bench" / "metrics" / f"{m['name']}.py")
        value = reader.read(ctx)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


@contextlib.contextmanager
def profiled(on: bool):
    """Trace the block into a temporary directory outside the checkout
    and yield that directory (``None`` when tracing is off)."""
    if not on:
        yield None
        return
    import jax
    d = tempfile.mkdtemp(prefix="bench_trace_")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0        # host spans and runtime events only
    opts.enable_hlo_proto = False
    jax.profiler.start_trace(d, profiler_options=opts)
    try:
        yield d
    finally:
        jax.profiler.stop_trace()


# ---- one run ----------------------------------------------------------------

def run(name: str, seed: int, seconds: float, trace: bool, *, t_start: float,
        cell_factory=Cell) -> dict:
    """Set up, warm up, time one window, check it; the result line.
    ``cell_factory`` builds the system under test (tests put a control
    or a broken path in its place)."""
    spec = resolve(name)
    cfg, traffic, root = spec["cfg"], spec["traffic"], spec["root"]
    clock = CompileClock()
    phases = {"start": time.perf_counter() - t_start}
    cell = cell_factory(cfg, traffic)
    phases["solver"] = time.perf_counter() - t_start
    ring = cell.ring(seed)
    phases["inputs"] = time.perf_counter() - t_start
    cell.step(ring, 0)                         # warm-up: compiles every shape
    gc.collect()
    setup_s = time.perf_counter() - t_start
    phases["warm-up"] = setup_s
    print("setup: " + ", ".join(f"{k} by {v:.3f} s" for k, v in
                                phases.items())
          + f"; compiles {clock.compiles} ({clock.seconds:.3f} s)",
          file=sys.stderr)
    compiles0 = clock.compiles
    with profiled(trace) as trace_dir:
        win = run_window(cell, ring, seconds)
    window_compiles = clock.compiles - compiles0
    peak = memory_peak(cell.devices)
    info = device_info(cell.devices)
    results = win.pop("results")
    run_rec = {"rhs": len(results), "steps": win["steps"],
               "window_s": win["window_s"], "setup_s": setup_s,
               "iters": [int(r.iters) for _, r in results]}
    summary = None
    if trace_dir is not None:
        from bench import trace as tr
        t_read = time.perf_counter()
        xplane = next(pathlib.Path(trace_dir).rglob("*.xplane.pb"), None)
        if xplane is not None:
            summary = tr.summarize(tr.load(str(xplane)), cell.loop.SPANS)
            print(f"trace: {xplane.stat().st_size} bytes read in "
                  f"{time.perf_counter() - t_read:.3f} s", file=sys.stderr)
        shutil.rmtree(trace_dir, ignore_errors=True)
    mesh_size = len(cell.devices) if cell.mesh is not None else 0
    n_local, lanes = cell.n_local, cell.batch
    ring_host = host_ring(ring, [i for i, _ in results])
    answers = to_host(results)
    del cell, ring, results
    gc.collect()
    verdict = check(cfg, ring_host, answers, win["expected"], mesh_size)
    device = {**info, "memory_peak_bytes": peak}
    ctx = types.SimpleNamespace(
        cfg=cfg, traffic=traffic, run=run_rec, trace=summary,
        n_local=n_local, lanes=lanes, peaks=None)
    breakdown = None
    if trace:
        if info["platform"] == "tpu":
            from bench.peaks import peaks
            ctx.peaks = peaks(info["kind"])
        metrics = read_metrics(spec["per_layer"], ctx, root)
        if summary is not None:
            device.update(busy_s=summary["busy_s"],
                          window_s=summary["window_s"])
            breakdown = {"device_ops": summary["device_ops"],
                         "idle_gaps": summary["idle_gaps"]}
    else:
        metrics = read_metrics(spec["e2e"], ctx, root)
    out = {"correct": verdict["correct"], "attempted": win["expected"],
           "failed": verdict["failed"], "metrics": metrics,
           "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["window_compiles"] = window_compiles
    out["checks"] = verdict["checks"]
    return out
