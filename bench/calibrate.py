#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from, for one cell.

    python3 bench/calibrate.py --workload p2d1000.solve --seeds 12 \
        --control-seeds 3 --seconds 10 --first-seed 7000

In one process (set-up is paid once): the cell's timed path, the prepared
solver as ``bench/run.py`` drives it, runs a window of ``--seconds`` on
each of ``--seeds`` seeds and prints the compared numbers of each; then
the control -- the same path with the configuration's ``control`` laid
over it (the nearest precision below the configuration's own, see
``PERF.md``) -- does the same on ``--control-seeds`` other seeds.  One JSON line per seed; the benchmark's own runs never run this.
It needs the chips the cell asks for.
"""
import argparse
import json
import os
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]


def readings(cell, cfg, seed: int, seconds: float) -> dict:
    from bench import harness
    ring = cell.ring(seed)
    win = harness.run_window(cell, ring, seconds)
    expected = win["expected"]
    used = [i for i, _ in win["results"]]
    mesh_size = len(cell.devices) if cell.mesh is not None else 0
    answers = harness.to_host(win["results"])
    verdict = harness.check(cfg, harness.host_ring(ring, used), answers,
                            expected, mesh_size)
    return {"seed": seed, "rhs": len(answers),
            "iters": [int(r.iters) for _, r in win["results"]],
            "correct": verdict["correct"], "checks": verdict["checks"]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--first-seed", type=int, default=7000)
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path.insert(0, str(ROOT))
    from bench import harness
    harness.enable_compile_cache()
    spec = harness.resolve(args.workload)
    cfg, traffic = spec["cfg"], spec["traffic"]
    seeds = [args.first_seed + k for k in range(args.seeds
                                                + args.control_seeds)]
    sides = [("program", cfg, seeds[:args.seeds]),
             ("control", harness.with_control(cfg), seeds[args.seeds:])]
    for side, side_cfg, chosen in sides:
        cell = harness.Cell(side_cfg, traffic)
        for seed in chosen:
            try:
                rec = readings(cell, cfg, seed, args.seconds)
            except Exception as e:          # a control that crashes fails
                rec = {"seed": seed, "correct": False,
                       "error": f"{type(e).__name__}: {e}"[:300]}
            print(json.dumps({"workload": args.workload, "side": side,
                              **rec}), flush=True)
        del cell
    return 0


if __name__ == "__main__":
    sys.exit(main())
