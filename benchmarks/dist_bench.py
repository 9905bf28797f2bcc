"""Distributed (mesh execution layer) benchmarks.

Runs each payload in a subprocess with a FORCED host platform device
count (``--xla_force_host_platform_device_count=N``) so the shard_map
mesh path is real even on single-device CI runners; the parent process
keeps its single device.  The device count is parameterized per row
family: the original ``dist/`` rows stay on 4 devices so they remain
comparable to the committed ``BENCH_*.json`` trajectory, while the
``dist/overlap_*`` rows force 8 devices -- enough shards that splitting
the reduction (``psum_scatter`` + delayed ``all_gather``) is a real
schedule change, not a 2x2 toy.

The probative columns are structural, not wall-clock (CPU collective
timings say nothing about ICI): ``psums_per_iter`` counted in the traced
scan body (1 for the pipelined engine's fused payload vs 2 for the
classic-CG baseline), ``ppermutes_per_iter`` (the 4 halo exchanges),
lane-scaling efficiency of the batched ``shard_map(vmap(scan))`` sweep,
and for the comm policies the full per-iteration collective signature
(blocking: one bare psum; overlap: one reduce_scatter + one all_gather,
zero psums; ring: ppermutes only).  The ``us_per_iter`` columns still
ride along so the hiding ratio is diffable across revisions.
"""
from __future__ import annotations

import json
import os
import pathlib
import subprocess
import sys

_PAYLOAD = r"""
import json, time
import jax
jax.config.update("jax_enable_x64", True)
import jax.numpy as jnp
from repro.core.shifts import chebyshev_shifts
from repro.distributed import DistPoisson, cg_mesh_sweep, plcg_mesh_sweep
from repro.kernels.introspect import count_primitive_in_scan_bodies
from repro.launch.mesh import make_mesh_compat

mesh = make_mesh_compat((2, 2), ("data", "model"))
nx = ny = 32
op = DistPoisson(nx, ny, mesh)
sig = tuple(chebyshev_shifts(0.0, 8.0, 2))
iters = 50
rows = []

def timeit(fn, *a, reps=2):
    jax.block_until_ready(fn(*a))          # warmup absorbs compile
    t0 = time.perf_counter()
    out = None
    for _ in range(reps):
        out = fn(*a)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps * 1e6

b = jnp.ones((nx, ny))
x0 = jnp.zeros_like(b)
fp = plcg_mesh_sweep(op, l=2, iters=iters, sigma=sig, tol=0.0)
psums = count_primitive_in_scan_bodies(fp, "psum", b, x0, iters)[0]
ppers = count_primitive_in_scan_bodies(fp, "ppermute", b, x0, iters)[0]
rows.append(["dist/plcg_sweep_2x2", timeit(fp, b, x0, iters),
             f"psums_per_iter={psums};ppermutes_per_iter={ppers};"
             f"iters={iters}"])
fc = cg_mesh_sweep(op, iters=iters, tol=0.0)
psums_c = count_primitive_in_scan_bodies(fc, "psum", b, x0)[0]
rows.append(["dist/cg_sweep_2x2", timeit(fc, b, x0),
             f"psums_per_iter={psums_c};iters={iters}"])

fb = plcg_mesh_sweep(op, l=2, iters=iters, sigma=sig, tol=0.0, batched=True)
base = None
for lanes in (1, 4, 8):
    B = jnp.ones((lanes, nx, ny)) * (1.0 + jnp.arange(lanes)[:, None, None])
    psums_b = count_primitive_in_scan_bodies(fb, "psum", B, B * 0, iters)[0]
    us = timeit(fb, B, B * 0, iters)
    if base is None:
        base = us
    rows.append([f"dist/plcg_lanes_{lanes}", us,
                 f"psums_per_iter={psums_b};us_per_lane={us / lanes:.0f};"
                 f"eff_vs_1lane={base * lanes / us:.2f}x"])
print(json.dumps(rows))
"""

_OVERLAP_PAYLOAD = r"""
import json, time
import jax
jax.config.update("jax_enable_x64", True)
import jax.numpy as jnp
from repro.core.shifts import chebyshev_shifts
from repro.distributed import DistPoisson, plcg_mesh_sweep
from repro.kernels.introspect import count_collectives_in_scan_bodies
from repro.launch.mesh import make_mesh_compat

mesh = make_mesh_compat((2, 4), ("data", "model"))
nx = ny = 32
op = DistPoisson(nx, ny, mesh)
l = 5                                # deep enough for the (2,4) ring (4 hops)
sig = tuple(chebyshev_shifts(0.0, 8.0, l))
iters = 50
rows = []

def timeit(fn, *a, reps=2):
    jax.block_until_ready(fn(*a))          # warmup absorbs compile
    t0 = time.perf_counter()
    out = None
    for _ in range(reps):
        out = fn(*a)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps * 1e6

b = jnp.ones((nx, ny))
x0 = jnp.zeros_like(b)
us_blocking = None
for comm in ("blocking", "overlap", "ring"):
    f = plcg_mesh_sweep(op, l=l, iters=iters, sigma=sig, tol=0.0, comm=comm)
    cc = count_collectives_in_scan_bodies(f, b, x0, iters)[0]
    us = timeit(f, b, x0, iters)
    if us_blocking is None:
        us_blocking = us
    detail = (f"psum={cc['psum']};reduce_scatter={cc['reduce_scatter']};"
              f"all_gather={cc['all_gather']};ppermute={cc['ppermute']};"
              f"us_per_iter={us / iters:.1f};"
              f"vs_blocking={us_blocking / us:.2f}x;l={l};iters={iters}")
    rows.append([f"dist/overlap_{comm}_8dev", us, detail])
print(json.dumps(rows))
"""


def _rows_forced(payload: str, ndevices: int) -> list[tuple]:
    """Run ``payload`` in a subprocess on ``ndevices`` forced host devices
    and parse its last stdout line as the row list.

    The child is a CPU rehearsal by design: it is pinned to
    ``JAX_PLATFORMS=cpu`` (a parent that already ran groups on a TPU holds
    the chip, so a child inheriting the platform would fail on the TPU
    lock), and every row it returns names ``platform=cpu``."""
    repo = pathlib.Path(__file__).resolve().parent.parent
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={ndevices}"
    env["PYTHONPATH"] = str(repo / "src")
    out = subprocess.run([sys.executable, "-c", payload], env=env,
                         capture_output=True, text=True, timeout=900)
    if out.returncode != 0:
        raise RuntimeError(
            f"dist bench subprocess failed: {out.stderr[-500:]}")
    rows = json.loads(out.stdout.strip().splitlines()[-1])
    return [(name, us, f"{derived};platform=cpu") for name, us, derived in rows]


def dist_rows():
    """dist/ row family, produced on a host-count-forced 4-device mesh
    (kept at 4 so the rows stay comparable across the BENCH trajectory)."""
    return _rows_forced(_PAYLOAD, 4)


def overlap_rows():
    """dist/overlap_* rows: the comm-policy ladder (blocking | overlap |
    ring) on a forced 8-device (2,4) mesh at depth l=5, same sweep per
    row so the per-iteration wall-clock and collective signature are
    directly comparable."""
    return _rows_forced(_OVERLAP_PAYLOAD, 8)


ALL = [dist_rows, overlap_rows]
SMOKE = [dist_rows, overlap_rows]
