"""Right-hand sides solved to tol over the window time (host clock): an
ensemble's throughput."""


def read(ctx):
    r = ctx.run
    return r["rhs"] / r["window_s"] if r["window_s"] > 0 else None
