"""Solver launcher: the paper's own workload -- p(l)-CG Poisson solves.

Every path goes through the unified ``repro.core.solve`` front-end: on a
single device it dispatches any registered --method (incl. batched
--nrhs > 1); with multiple devices it passes ``mesh=`` so the same call
runs the mesh execution layer (shard_map domain decomposition inside,
vmap RHS batching outside, one fused psum per iteration).

  PYTHONPATH=src python -m repro.launch.solve --nx 200 --l 2 --tol 1e-5
  PYTHONPATH=src python -m repro.launch.solve --method plcg_scan --nrhs 8
  PYTHONPATH=src python -m repro.launch.solve --l auto --comm auto  # calibrated
  PYTHONPATH=src python -m repro.launch.solve --dryrun            # 16x16 mesh

``--serve --requests N`` switches to the prepared-solver serving mode:
one ``repro.core.session.Solver`` is built up front (validation /
normalization / sweep building once), N requests stream through a
``SolverPool`` that micro-batches them into padded batched sweeps
(``--max-batch`` lanes per flush), and the per-request outcomes plus
occupancy/compile stats are reported:

  PYTHONPATH=src python -m repro.launch.solve --serve --requests 32 \\
      --nx 64 --l 2 --max-batch 8
"""
from __future__ import annotations

import argparse
import json
import pathlib
import time


def _print_auto(info: dict) -> None:
    """One line per calibrated decision: the chosen (l, comm, budget)
    and the measured latencies that justified it (SolveResult.info["auto"],
    see repro.core.autotune)."""
    lat = info["latencies"]
    glred = " ".join(f"{m}={v:.0f}us"
                     for m, v in sorted(lat["glred_us"].items()))
    print(f"  auto: l={info['l']} comm={info['comm']} "
          f"budget={info['budget']} ({info['source']}; "
          f"spmv={lat['spmv_us']:.0f}us glred {glred}; "
          f"model score {info['score_us']:.0f}us/iter)")


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--nx", type=int, default=200)
    ap.add_argument("--ny", type=int, default=0)
    ap.add_argument("--l", type=str, default="2",
                    help="pipeline depth: an int, or auto to calibrate the "
                    "depth from measured latencies at session construction "
                    "(repro.core.autotune; the decision is reported)")
    ap.add_argument("--iters", type=int, default=1500)
    ap.add_argument("--tol", type=float, default=1e-5)
    ap.add_argument("--method", type=str, default="plcg_scan",
                    help="registered repro.core.solve method (single device: "
                    "cg|pcg|plcg|plcg_scan|dlanczos|plminres; on a mesh: "
                    "cg|plcg|plcg_scan)")
    ap.add_argument("--nrhs", type=int, default=1,
                    help="number of right-hand sides; > 1 runs the batched "
                    "multi-RHS engine (vmap(scan) on one device, "
                    "shard_map(vmap(scan)) on a mesh)")
    ap.add_argument("--backend", type=str, default=None,
                    help="scan-engine kernel backend: fused|pallas|ref|auto "
                    "(single-device only; the mesh path bypasses it)")
    ap.add_argument("--prec", type=str, default="none",
                    choices=["none", "jacobi", "blockjacobi", "chebyshev"],
                    help="preconditioner ladder: jacobi folds into the "
                    "fused megakernel, blockjacobi/chebyshev run "
                    "shard-local on a mesh (one psum per iteration)")
    ap.add_argument("--comm", type=str, default=None,
                    choices=["blocking", "overlap", "ring", "auto"],
                    help="mesh reduction schedule: blocking psum (default), "
                    "split psum_scatter + delayed all_gather (overlap), "
                    "staged ppermute ring (mesh runs only), or auto to pick "
                    "the measured-fastest schedule at session construction")
    ap.add_argument("--comm-depth", type=int, default=None,
                    help="overlap staging depth d, 1 <= d <= l "
                    "(--comm overlap only; default l)")
    ap.add_argument("--restart", type=str, default="auto",
                    help="in-scan breakdown recovery: auto (default), an "
                    "int cap of per-lane re-seeds, or none to disable "
                    "(plcg_scan; see the engine's restart= knob)")
    ap.add_argument("--residual-replacement", type=int, default=None,
                    help="period (committed updates) of the in-scan "
                    "true-residual recompute r = b - Ax (plcg_scan; "
                    "counters deep-pipeline residual drift)")
    ap.add_argument("--dryrun", action="store_true",
                    help="lower+compile on the production 16x16 (or 32x16 "
                    "with --multi-pod) mesh and report roofline terms")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--serve", action="store_true",
                    help="prepared-solver serving mode: build one Solver, "
                    "stream --requests RHS through a micro-batching "
                    "SolverPool, report per-request outcomes + occupancy")
    ap.add_argument("--requests", type=int, default=16,
                    help="number of serving requests (--serve only)")
    ap.add_argument("--max-batch", type=int, default=8,
                    help="max lanes per pooled flush (--serve only)")
    args = ap.parse_args(argv)

    if args.dryrun:
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core.shifts import chebyshev_shifts
    from repro.launch.mesh import make_solver_mesh, make_solver_mesh_for

    ny = args.ny or args.nx
    l = args.l if args.l == "auto" else int(args.l)
    if l == "auto" and args.dryrun:
        ap.error("--dryrun lowers one fixed-depth sweep; pass an int --l")
    # with l="auto" the depth is unknown until the session calibrates, so
    # the engine derives sigma from the (default) spectrum after resolution
    sigma = None if l == "auto" else chebyshev_shifts(0.0, 8.0, l)

    if args.dryrun:
        from repro.distributed import DistPoisson, plcg_mesh_sweep
        from repro.launch import hlo_analysis
        from repro.launch.peaks import roofline_terms
        mesh = make_solver_mesh(multi_pod=args.multi_pod)
        px, py = mesh.shape["data"], mesh.shape["model"]
        nx = max(args.nx, px * 128)       # production-scale local blocks
        nyy = max(ny, py * 128)
        op = DistPoisson(nx, nyy, mesh)
        fn = plcg_mesh_sweep(op, l=l, iters=args.iters,
                             sigma=tuple(sigma), tol=args.tol)
        b = jax.ShapeDtypeStruct((nx, nyy), jnp.float32)
        t0 = time.time()
        lowered = fn.lower(b, b, args.iters)
        compiled = lowered.compile()
        ma = compiled.memory_analysis()
        st = hlo_analysis.analyze(compiled.as_text())
        rec = {
            "arch": "poisson2d", "mesh": "multi" if args.multi_pod else "single",
            "grid": [nx, nyy], "l": l, "iters": args.iters,
            "compile_s": round(time.time() - t0, 1),
            "memory": {"peak_per_device":
                       ma.argument_size_in_bytes + ma.temp_size_in_bytes},
            "hlo": {"flops_per_device": st.flops,
                    "traffic_bytes_per_device": st.traffic_bytes,
                    "collective_bytes": dict(st.collective_bytes),
                    "collective_counts": dict(st.collective_counts)},
            "roofline": roofline_terms(st),
        }
        out = pathlib.Path("experiments/dryrun/solver")
        out.mkdir(parents=True, exist_ok=True)
        name = f"poisson2d__{'multi' if args.multi_pod else 'single'}__l{l}.json"
        (out / name).write_text(json.dumps(rec, indent=1))
        print(json.dumps(rec["roofline"], indent=1))
        print("memory/device GB:",
              rec["memory"]["peak_per_device"] / 1e9)
        return rec

    # real solve on available devices -- ONE front-end call either way
    from repro.core import solve
    from repro.operators import poisson2d
    ndev = len(jax.devices())
    A = poisson2d(args.nx, ny)
    b_flat = np.asarray(A @ np.ones(args.nx * ny))
    if args.nrhs > 1:
        rng = np.random.default_rng(0)
        B = np.stack([b_flat] + [np.asarray(A @ rng.standard_normal(A.n))
                                 for _ in range(args.nrhs - 1)])
    else:
        B = b_flat
    mesh = (make_solver_mesh_for(ndev, ny, nx=args.nx) if ndev > 1
            else None)
    comm = None
    if args.comm_depth is not None and args.comm != "overlap":
        ap.error("--comm-depth requires --comm overlap")
    if args.comm == "auto":
        comm = "auto"       # sentinel, resolved at session construction
    elif args.comm is not None:
        from repro.core import CommPolicy
        comm = CommPolicy(mode=args.comm, depth=args.comm_depth)
    if args.restart == "auto":
        restart = "auto"
    elif args.restart.lower() in ("none", "off"):
        restart = None
    else:
        restart = int(args.restart)
    stab_kw = {}
    if args.method in ("plcg_scan",):
        stab_kw = {"restart": restart,
                   "residual_replacement": args.residual_replacement}
    M = None
    if args.prec == "jacobi":
        from repro.operators import jacobi
        M = jacobi(A)
    elif args.prec == "blockjacobi":
        from repro.core import BlockJacobi
        M = (BlockJacobi.for_mesh(A, mesh) if mesh is not None
             else BlockJacobi((args.nx, ny)))
    elif args.prec == "chebyshev":
        from repro.core import Chebyshev
        M = Chebyshev(A, spectrum=(0.5, 8.0), degree=3)
    if args.serve:
        # prepared-solver serving mode: setup once, micro-batch requests
        from repro.core.session import Solver, SolverPool
        t0 = time.time()
        solver = Solver(A, args.method, l=l, tol=args.tol,
                        maxiter=args.iters,
                        sigma=None if M is not None else sigma,
                        M=M, backend=args.backend, mesh=mesh, comm=comm,
                        **stab_kw)
        pool = SolverPool(solver, max_batch=args.max_batch)
        setup_s = time.time() - t0
        rng = np.random.default_rng(1)
        shape = (args.nx, ny) if mesh is not None else (A.n,)
        reqs = [np.asarray(A @ rng.standard_normal(A.n)).reshape(shape)
                for _ in range(args.requests)]
        t0 = time.time()
        handles = [pool.submit(rb) for rb in reqs]
        pool.flush()
        results = [h.result() for h in handles]
        dt = time.time() - t0
        nconv = sum(1 for r in results if r.converged)
        where = (f"{ndev}-device mesh {dict(mesh.shape)}" if mesh
                 else "1 device")
        print(f"served {args.requests} requests ({args.method}, l={solver.l}, "
              f"prec={args.prec}) on {args.nx}x{ny} over {where}: "
              f"setup {setup_s:.2f}s, drain {dt:.2f}s "
              f"({args.requests / max(dt, 1e-9):.1f} req/s), "
              f"{nconv}/{args.requests} converged")
        if solver.auto is not None:
            _print_auto(solver.auto.as_info())
        print(f"  batches={pool.stats['batches']} "
              f"occupancy={pool.occupancy:.3f} "
              f"lanes={pool.stats['lanes_real']}/"
              f"{pool.stats['lanes_padded']} "
              f"prepared_sweeps={solver.prepared_sweeps}")
        worst = max(range(len(results)),
                    key=lambda j: np.linalg.norm(
                        reqs[j].reshape(-1)
                        - np.asarray(A @ np.asarray(
                            results[j].x).reshape(-1))))
        res = np.linalg.norm(reqs[worst].reshape(-1) - np.asarray(
            A @ np.asarray(results[worst].x).reshape(-1)))
        print(f"  worst |b-Ax| = {res:.3e} (request {worst}, "
              f"{results[worst].iters} iters)")
        return results

    t0 = time.time()
    # with a preconditioner the engine derives the shift interval from
    # M.precond_spectrum; the hand-picked (0, 8) sigma is only for M=None
    r = solve(A, B, method=args.method, l=l, tol=args.tol,
              maxiter=args.iters, sigma=None if M is not None else sigma,
              M=M, backend=args.backend, mesh=mesh, comm=comm, **stab_kw)
    dt = time.time() - t0
    x = np.asarray(r.x).reshape(args.nrhs, -1) if args.nrhs > 1 \
        else np.asarray(r.x).reshape(-1)
    res = np.linalg.norm(b_flat - A @ (x[0] if args.nrhs > 1 else x))
    where = f"{ndev}-device mesh {dict(mesh.shape)}" if mesh else "1 device"
    print(f"{args.method} (l={r.info.get('l', l)}, nrhs={args.nrhs}, "
          f"prec={args.prec}, comm={r.info.get('comm', 'n/a')}) "
          f"on {args.nx}x{ny} over {where}: "
          f"{r.iters} iters, {dt:.2f}s, |b-Ax| = {res:.3e}, "
          f"converged={r.converged}")
    if "auto" in r.info:
        _print_auto(r.info["auto"])
    if args.nrhs > 1 and "per_rhs_iters" in r.info:
        # a batched lane that hits square-root breakdown re-seeds itself
        # in-scan when restart= is enabled (per-lane counters below);
        # with restart=None it freezes with breakdown=True -- either way
        # make the per-lane outcome visible instead of just reporting
        # converged=False for the whole batch
        print("  per-lane iters:",
              [int(k) for k in r.info["per_rhs_iters"]],
              "converged:",
              [bool(c) for c in r.info["per_rhs_converged"]],
              "breakdown:",
              [bool(c) for c in r.info.get("per_rhs_breakdown", [])],
              "restarts:",
              [int(c) for c in r.info.get("per_rhs_restarts", [])],
              "replacements:",
              [int(c) for c in r.info.get("per_rhs_replacements", [])])
    elif r.restarts or r.replacements:
        print(f"  in-scan recovery: {r.restarts} restart(s), "
              f"{r.replacements} residual replacement(s)")
    if M is not None and args.nrhs == 1:
        from repro.core import residual_gap
        gap = residual_gap(A, b_flat, r)
        print(f"residual gap (attainable accuracy): true={gap['true_resnorm']:.3e} "
              f"implicit={gap['implicit_resnorm']:.3e} rel_gap={gap['rel_gap']:.1e}")
    return x


if __name__ == "__main__":
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    main()
