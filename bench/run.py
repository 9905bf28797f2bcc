#!/usr/bin/env python3
"""Run one cell of the on-chip benchmark and print its result line.

    python3 bench/run.py --workload p2d1000.solve --seed 7 --seconds 10 \
        --trace 0

Set-up (imports, the prepared solver, the seeded right-hand sides on the
device, one warm-up step that compiles every shape) is ``setup_s``; then
the cell's closed loop runs for ``--seconds`` and the step that crosses
the end completes.  Every solution of the window is checked against the
plain float64 reference.  The last line of standard output is one JSON
object (``correct``, ``attempted``, ``failed``, ``metrics``, ``device``,
with ``--trace 1`` also ``breakdown``; the compared numbers last, under
``checks``); the same numbers, each beside its limit, are the last lines
of standard error.  ``--trace 0`` reports the cell's end-to-end metrics,
``--trace 1`` traces the window and reports its per-layer metrics.

Without a TPU, or with fewer chips than the cell asks for, it exits 2
and prints no result.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    # the TPU runtime's logs would go to a fixed /tmp path
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path.insert(0, str(ROOT))
    from bench import harness
    chips = harness.resolve(args.workload)["cell"]["chips"]
    import jax
    try:
        devices = jax.devices()
    except RuntimeError as e:
        print(f"bench: JAX found no devices ({e}); nothing was run",
              file=sys.stderr)
        return 2
    if devices[0].platform != "tpu":
        print(f"bench: JAX found no TPU (platform {devices[0].platform!r})"
              "; nothing was run", file=sys.stderr)
        return 2
    if len(devices) < chips:
        print(f"bench: {args.workload} needs {chips} chips, found "
              f"{len(devices)}; nothing was run", file=sys.stderr)
        return 2
    harness.enable_compile_cache()
    out = harness.run(args.workload, args.seed, args.seconds,
                      bool(args.trace), t_start=T_START)
    for name, c in out["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
