#!/usr/bin/env python3
"""Bring-up check of the p(l)-CG solver on a TPU, through the entry points
a user calls, at the paper's own problem size.

    python3 chip_smoke.py              # one chip: phases a, b, c
    python3 chip_smoke.py --mesh 2x2   # four chips: the mesh path only

The problem is the paper's test setup 1 (arXiv:1801.04728 Sec. 5,
``repro.configs.poisson2d``): the 2-D Poisson stencil on a 1000 x 1000
grid (n = 10^6), p(3)-CG, ``tol=1e-5``, Chebyshev shifts on (0, 8).  The
right-hand side is ``b = A x_hat`` with ``x_hat`` drawn from ``--seed``;
the device works in float32.  The reference is the float64 true relative
residual ``||b - A x|| / ||b||``, computed with numpy on the host.

One chip:
  a. ``solve(A, b, method="plcg_scan", ...)`` with the default backend;
  b. the same call with ``backend="auto"`` (must resolve to ``"pallas"``)
     and with ``backend="fused"`` (the in-kernel stencil megakernel);
  c. serving: one ``Solver`` behind a ``SolverPool(max_batch=8)`` drains
     16 requests, on the largest grid whose 8-lane batched fused windows
     fit one chip's 16 GB.

``--mesh 2x2`` (four chips): ``solve(..., mesh=<2x2 mesh>)`` with
``comm="blocking"`` and ``comm="overlap"``, and the classic-CG baseline on
the same mesh; the solution must be sharded over all four devices.

Each phase prints one JSON line: device, compile and solve seconds (solve
timed warm, after ``block_until_ready``), iterations, convergence, the
float64 true residual and the ``tpu_custom_call`` kernels of the compiled
program.  A phase fails -- and the script exits non-zero -- when it does
not converge, when the true residual exceeds 10 * tol, or when a kernel
tier compiled without its kernels.  The last line is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
Without a TPU the script exits non-zero before solving anything.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))

GRID = 1000              # paper test setup 1: n = 10^6
POOL_GRID = 768          # B=8 fused windows: 12.1 of the chip's 16 GB
POOL_REQUESTS = 16
POOL_BATCH = 8
L = 3
TOL = 1e-5
#: the engine stops once every lane is done, so maxiter only caps the
#: loop: 300 is 2.3x the 132 iterations this problem takes on the chip
#: (the config's cap is 2000)
MAXITER = 300
SPECTRUM = (0.0, 8.0)

#: launches each kernel tier compiles to (README "Backends"): at least
#: three for the per-kernel tier, exactly one for the fused megakernel
KERNELS = {"pallas": (3, None), "fused": (1, 1)}

_COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                   "/jax/core/compile/jaxpr_to_mlir_module_duration",
                   "/jax/core/compile/backend_compile_duration")


class PhaseFailure(RuntimeError):
    pass


class CompileClock:
    """Seconds JAX spends tracing, lowering and compiling (persistent-cache
    retrievals included), and persistent-cache hits, since construction."""

    def __init__(self):
        import jax.monitoring
        self.seconds = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event in _COMPILE_EVENTS:
            self.seconds += duration

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


def device_info() -> dict:
    import jax
    dev = jax.devices()[0]
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(jax.devices())}


def make_problem(grid: int, seed: int, nrhs: int = 0):
    """``(A, b)``: the Poisson operator and ``b = A x_hat`` in float32
    (``(nrhs, n)`` stacked when ``nrhs`` > 0)."""
    import numpy as np
    from repro.operators import poisson2d
    A = poisson2d(grid)
    rng = np.random.default_rng(seed)
    xs = [rng.standard_normal(A.n) for _ in range(max(nrhs, 1))]
    bs = np.stack([A @ x for x in xs]).astype(np.float32)
    return A, (bs if nrhs else bs[0])


def true_rel_residual(A, b, x) -> float:
    """float64 ``||b - A x|| / ||b||`` on the host (the worst lane of a
    stacked batch)."""
    import numpy as np
    b64 = np.asarray(b, np.float64).reshape(-1, A.n)
    x64 = np.asarray(x, np.float64).reshape(-1, A.n)
    return max(float(np.linalg.norm(bb - A @ xx) / np.linalg.norm(bb))
               for bb, xx in zip(b64, x64))


def count_kernels(compiled) -> int:
    """``tpu_custom_call`` launches in a compiled program (Pallas kernels
    on TPU; 0 for interpret-mode kernels on the CPU)."""
    return compiled.as_text().count('custom_call_target="tpu_custom_call"')


def _timed(clock, fn):
    """``(out, compile_s, wall_s)`` of one call that ends in
    ``block_until_ready`` on the returned solutions."""
    import jax
    c0, t0 = clock.seconds, time.perf_counter()
    out = fn()
    jax.block_until_ready([r.x for r in out] if isinstance(out, list)
                          else out.x)
    return out, clock.seconds - c0, time.perf_counter() - t0


def _record(phase, what, clock, *, grid, run, A, b, tol, extra):
    """Run ``run()`` twice (cold, then warm for the solve time) and
    summarize the warm result as one phase record."""
    import numpy as np
    hits0 = clock.cache_hits
    _, compile_s, _ = _timed(clock, run)
    out, _, solve_s = _timed(clock, run)
    results = out if isinstance(out, list) else [out]
    x = np.stack([np.asarray(r.x) for r in results])
    rec = {"phase": phase, "what": what, **device_info(), "grid": grid,
           "compile_s": round(compile_s, 3), "solve_s": round(solve_s, 4),
           "cache_hits": clock.cache_hits - hits0,
           "iters": max(int(r.iters) for r in results),
           "converged": all(bool(r.converged) for r in results),
           "true_rel_res": true_rel_residual(A, b, x),
           "tol": tol}
    rec.update(extra(results))
    return rec


def check(rec: dict, *, tier=None) -> dict:
    """Raise :class:`PhaseFailure` unless the phase converged, its float64
    true residual is within 10 * tol, and -- on a TPU, where kernels are
    ``tpu_custom_call`` ops -- a kernel tier compiled to its launches."""
    problems = []
    if not rec["converged"]:
        problems.append("did not converge")
    if not rec["true_rel_res"] <= 10 * rec["tol"]:
        problems.append(f"true residual {rec['true_rel_res']:.3e} > "
                        f"10 * tol")
    if rec["platform"] == "tpu" and tier in KERNELS:
        lo, hi = KERNELS[tier]
        k = rec["kernels"]
        if k < lo or (hi is not None and k > hi):
            problems.append(f"{tier} tier compiled to {k} kernel launches, "
                            f"expected {lo}{'' if hi == lo else '+'}")
    if problems:
        raise PhaseFailure(f"phase {rec['phase']} ({rec['what']}): "
                           + "; ".join(problems))
    return rec


def phase_solve(phase, A, b, clock, *, backend, grid, tol=TOL, l=L,
                maxiter=MAXITER):
    """``solve(A, b, method="plcg_scan", backend=...)`` at one device, and
    the kernels of the program it ran (``Solver.lower``)."""
    from repro.core import Solver, solve
    kw = dict(method="plcg_scan", l=l, tol=tol, maxiter=maxiter,
              spectrum=SPECTRUM, backend=backend)

    def extra(results):
        compiled = Solver(A, **kw).lower(b).compile()
        return {"backend": results[0].info["backend"],
                "kernels": count_kernels(compiled)}

    return _record(phase, f"solve backend={backend}", clock, grid=grid,
                   run=lambda: solve(A, b, **kw), A=A, b=b, tol=tol,
                   extra=extra)


def phase_pool(phase, A, B, clock, *, backend, grid, tol=TOL, l=L,
               maxiter=MAXITER, max_batch=POOL_BATCH):
    """One ``Solver`` behind a ``SolverPool``: submit every row of ``B``,
    flush, collect; reports the batched program's device memory."""
    import jax.numpy as jnp
    from repro.core import Solver, SolverPool
    solver = Solver(A, method="plcg_scan", l=l, tol=tol, maxiter=maxiter,
                    spectrum=SPECTRUM, backend=backend)
    pool = SolverPool(solver, max_batch=max_batch)
    drained = {}

    def run():
        handles = [pool.submit(bj) for bj in B]
        drained["batches"] = len(pool.flush())
        return [h.result() for h in handles]

    def extra(results):
        compiled = solver.lower(jnp.asarray(B[:max_batch])).compile()
        ma = compiled.memory_analysis()
        return {"backend": backend, "requests": len(B),
                "max_batch": max_batch,
                "batches": drained["batches"],
                "kernels": count_kernels(compiled),
                "memory": {k: int(getattr(ma, k)) for k in (
                    "argument_size_in_bytes", "output_size_in_bytes",
                    "temp_size_in_bytes", "alias_size_in_bytes",
                    "generated_code_size_in_bytes")}}

    return _record(phase, f"SolverPool backend={backend}", clock, grid=grid,
                   run=run, A=A, b=B, tol=tol, extra=extra)


def phase_mesh(phase, A, b2d, mesh, clock, *, method, comm, grid, tol=TOL,
               l=L, maxiter=MAXITER):
    """``solve(..., mesh=mesh)``; the solution must span every device of
    the mesh."""
    from repro.core import solve
    kw = dict(method=method, tol=tol, maxiter=maxiter, mesh=mesh)
    if method != "cg":
        kw.update(l=l, spectrum=SPECTRUM, comm=comm)

    def extra(results):
        x = results[0].x
        return {"mesh": dict(mesh.shape), "comm": comm,
                "psums_per_iter": results[0].info["psums_per_iter"],
                "solution_devices": len(x.sharding.device_set)}

    rec = _record(phase, f"mesh {method} comm={comm}", clock, grid=grid,
                  run=lambda: solve(A, b2d, **kw), A=A, b=b2d, tol=tol,
                  extra=extra)
    if rec["solution_devices"] != mesh.size:
        raise PhaseFailure(f"phase {phase}: solution spans "
                           f"{rec['solution_devices']} of {mesh.size} "
                           "devices")
    return rec


def run_one_chip(clock, seed: int):
    A, b = make_problem(GRID, seed)
    yield check(phase_solve("a", A, b, clock, backend=None, grid=GRID))
    rec = phase_solve("b", A, b, clock, backend="auto", grid=GRID)
    if rec["backend"] != "pallas":
        raise PhaseFailure(f"backend='auto' resolved to {rec['backend']!r}"
                           ", expected 'pallas' on a TPU")
    yield check(rec, tier="pallas")
    yield check(phase_solve("b", A, b, clock, backend="fused", grid=GRID),
                tier="fused")
    Ap, B = make_problem(POOL_GRID, seed + 1, nrhs=POOL_REQUESTS)
    yield check(phase_pool("c", Ap, B, clock, backend="fused",
                           grid=POOL_GRID), tier="fused")


def run_mesh(clock, seed: int, shape):
    import jax
    from repro.launch.mesh import make_mesh_compat
    need = shape[0] * shape[1]
    if len(jax.devices()) < need:
        raise PhaseFailure(f"--mesh {shape[0]}x{shape[1]} needs {need} "
                           f"devices, found {len(jax.devices())}")
    mesh = make_mesh_compat(shape, ("data", "model"))
    A, b = make_problem(GRID, seed)
    b2d = b.reshape(GRID, GRID)
    for comm in ("blocking", "overlap"):
        yield check(phase_mesh("mesh", A, b2d, mesh, clock,
                               method="plcg_scan", comm=comm, grid=GRID))
    yield check(phase_mesh("mesh", A, b2d, mesh, clock, method="cg",
                           comm=None, grid=GRID))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--mesh", default=None, metavar="RxC",
                    help="run only the mesh path on an R x C device grid "
                    "(e.g. 2x2 on a four-chip host)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax
    info = device_info()
    if info["platform"] != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform {info['platform']!r})"
              "; nothing was run", file=sys.stderr)
        return 2
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.launch.cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    print(json.dumps({"setup": True, **info, "jax": jax.__version__,
                      "compile_cache": cache_dir}), flush=True)
    clock = CompileClock()
    if args.mesh:
        shape = tuple(int(v) for v in args.mesh.lower().split("x"))
        phases = run_mesh(clock, args.seed, shape)
    else:
        phases = run_one_chip(clock, args.seed)
    for rec in phases:
        print(json.dumps(rec), flush=True)
    print(json.dumps({"ok": True, "device": device_info()}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
