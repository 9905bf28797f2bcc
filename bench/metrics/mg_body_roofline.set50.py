"""HBM roofline share of the p(l)-CG iteration preconditioned by HPCG's
V-cycle: useful iterations times ``bench/roofline_hpcg.py`` bytes at the
chip's peak bandwidth, over device busy time (preconditioner layer)."""
from bench.readers import storage_bytes
from bench.roofline_hpcg import bytes_per_iter


def read(ctx):
    t = ctx.trace
    if (t is None or ctx.peaks is None or t["busy_s"] <= 0
            or not ctx.run["iters"]):
        return None
    cfg = ctx.cfg
    useful = sum(ctx.run["iters"]) * bytes_per_iter(
        cfg["solver"]["l"], cfg["grid"], storage_bytes(cfg))
    return 100.0 * useful / ctx.peaks["hbm_bytes_s"] / t["busy_s"]
