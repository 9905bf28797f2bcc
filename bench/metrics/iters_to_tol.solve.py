"""Mean iterations to tol per right-hand side, from ``SolveResult.iters``
(solver numerics layer)."""
from bench.readers import iters_to_tol as read  # noqa: F401
