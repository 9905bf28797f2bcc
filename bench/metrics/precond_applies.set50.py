"""Preconditioner applies (V-cycles) per iteration, from the program's
own ``precond_applies`` counter over the iterations of the window's
solves (preconditioner layer); a program without the counter reads
``None``."""
from bench.program_spans import window_roots


def read(ctx):
    roots = window_roots(ctx)
    iters = sum(ctx.run["iters"])
    if roots is None or iters <= 0:
        return None
    applies = sum(r.counters.get("precond_applies", 0) for r in roots)
    return applies / iters if applies else None
