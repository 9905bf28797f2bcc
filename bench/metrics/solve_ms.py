"""Window time over the right-hand sides solved in it, in milliseconds
(host clock): the wait for one solution at tol."""


def read(ctx):
    r = ctx.run
    return r["window_s"] / r["rhs"] * 1e3 if r["rhs"] else None
