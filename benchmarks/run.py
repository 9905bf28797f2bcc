"""Benchmark harness: one function per paper table/figure + kernel timings
+ the unified-front-end groups + the dry-run roofline aggregation.  Prints
``name,us_per_call,derived`` CSV rows.

``--smoke`` runs a fast subset (front-end dispatch, batched engine, kernel
micro-times, the structural Table-1 rows) for the CI benchmark-smoke job:
the rows must *print*, no timing is asserted.

``--json [PATH]`` additionally writes the machine-readable trajectory
file ``{name: us_per_call}`` (plus a ``derived`` map) consumed by the
perf gate: commit one ``BENCH_<rev>.json`` per landed revision so
regressions are diffable across the PR sequence.  Without an explicit
PATH the file is auto-named ``BENCH_<rev>.json`` from
``git rev-parse --short HEAD``, so the provenance can no longer drift
from the checked-out revision.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import subprocess
import sys
import time

# make `python benchmarks/run.py` work from anywhere (not only
# `python -m benchmarks.run` from the repo root)
sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="fast CI subset; asserts nothing about timings")
    ap.add_argument("--json", metavar="PATH", nargs="?", default=None,
                    const="auto",
                    help="also write {name: us_per_call} (+derived) JSON; "
                         "without PATH, auto-names BENCH_<rev>.json from "
                         "`git rev-parse --short HEAD`")
    args = ap.parse_args(argv)
    if args.json == "auto":
        try:
            rev = subprocess.run(
                ["git", "rev-parse", "--short", "HEAD"],
                cwd=pathlib.Path(__file__).resolve().parent.parent,
                capture_output=True, text=True, check=True).stdout.strip()
        except (OSError, subprocess.CalledProcessError) as e:
            rev = "local"
            sys.stderr.write(f"[--json: git rev-parse unavailable ({e}); "
                             "falling back to BENCH_local.json]\n")
        args.json = f"BENCH_{rev}.json"

    from benchmarks import (auto_bench, dist_bench, engine_bench,
                            kernels_bench, mp_bench, paper_figs, prec_bench,
                            roofline, serve_bench, stab_bench, train_bench)
    if args.smoke:
        groups = (list(engine_bench.SMOKE) + list(kernels_bench.ALL)
                  + [paper_figs.table1_cost_model] + list(dist_bench.SMOKE)
                  + list(prec_bench.SMOKE) + list(serve_bench.SMOKE)
                  + list(stab_bench.SMOKE) + list(mp_bench.SMOKE)
                  + list(auto_bench.SMOKE) + list(train_bench.SMOKE))
    else:
        groups = (list(paper_figs.ALL) + list(kernels_bench.ALL)
                  + list(engine_bench.ALL) + list(dist_bench.ALL)
                  + list(prec_bench.ALL) + list(serve_bench.ALL)
                  + list(stab_bench.ALL) + list(mp_bench.ALL)
                  + list(auto_bench.ALL) + list(train_bench.ALL)
                  + list(roofline.ALL))
    print("name,us_per_call,derived")
    failures = 0
    all_rows: list[tuple] = []
    for fn in groups:
        t0 = time.time()
        try:
            rows = fn()
        except Exception as e:  # noqa: BLE001
            print(f"{getattr(fn, '__name__', 'roofline')},0,"
                  f"ERROR:{type(e).__name__}:{str(e)[:120]}")
            failures += 1
            continue
        for name, us, derived in rows:
            print(f"{name},{us:.1f},{derived}")
        all_rows.extend(rows)
        sys.stderr.write(f"[{getattr(fn, '__name__', 'roofline')}: "
                         f"{time.time()-t0:.1f}s]\n")
    # the serving win tracked across PRs: per-call front-end overhead of
    # one-shot solve() over a prepared Solver (a derived row so
    # BENCH_<rev>.json diffs it like any other metric)
    us = {name: v for name, v, _ in all_rows}
    if us.get("serve/prepared"):
        ratio = us["serve/oneshot"] / us["serve/prepared"]
        row = ("serve/overhead_ratio", ratio,
               "oneshot_us_per_call/prepared_us_per_call")
        print(f"{row[0]},{row[1]:.2f},{row[2]}")
        all_rows.append(row)
    # the communication-hiding win tracked across PRs: blocking-psum sweep
    # wall-clock over the split psum_scatter/all_gather sweep on the forced
    # 8-device mesh (>1 means the in-flight reduction paid for itself)
    if us.get("dist/overlap_overlap_8dev"):
        ratio = us["dist/overlap_blocking_8dev"] / us["dist/overlap_overlap_8dev"]
        row = ("dist/overlap_hiding_ratio", ratio,
               f"ratio={ratio:.2f};blocking_us/overlap_us on forced "
               "8-device mesh")
        print(f"{row[0]},{row[1]:.2f},{row[2]}")
        all_rows.append(row)
    # the mixed-precision win tracked across PRs: HBM bytes/iter of the
    # f32 window path over the bf16 one at the deepest benchmarked l
    # (measured from real buffer nbytes in mp_bench.mp_traffic)
    if us.get("mp/traffic_bf16_l5"):
        ratio = us["mp/traffic_f32_l5"] / us["mp/traffic_bf16_l5"]
        row = ("mp/traffic_saving", ratio,
               "f32_bytes_per_iter/bf16_bytes_per_iter at l=5")
        print(f"{row[0]},{row[1]:.2f},{row[2]}")
        all_rows.append(row)
    if args.json:
        payload = {
            "us_per_call": {name: round(us, 1) for name, us, _ in all_rows},
            "derived": {name: derived for name, us, derived in all_rows},
        }
        pathlib.Path(args.json).write_text(json.dumps(payload, indent=1,
                                                      sort_keys=True))
        sys.stderr.write(f"[wrote {len(all_rows)} rows to {args.json}]\n")
    if failures:
        sys.stderr.write(f"{failures} benchmark group(s) failed\n")
        sys.exit(1)


if __name__ == "__main__":
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    main()
