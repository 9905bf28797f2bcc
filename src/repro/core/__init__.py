"""Core solver library: the paper's Krylov methods behind one front-end.

``repro.core.solve(A, b, method=..., l=..., M=...)`` dispatches every
registered solver (``cg``, ``pcg``, ``plcg``, ``plcg_scan``, ``dlanczos``,
``plminres``) through a single signature and a common ``SolveResult``
contract, including the batched multi-RHS ``vmap(scan)`` path and the
mesh execution layer (``mesh=``).  Preconditioning is a first-class
layer (``repro.core.precond``): ``M=`` accepts a structured
:class:`Preconditioner` (``Jacobi`` fuses into the Pallas megakernel,
``BlockJacobi``/``Chebyshev`` run shard-local on a mesh) or any bare
callable, which is promoted via :func:`as_preconditioner`.  For
many-solves serving workloads, :class:`Solver` / :class:`SolverPool`
(``repro.core.session``) prepare a solver once -- validation,
normalization and sweep building out of the per-call path -- and
micro-batch concurrent right-hand sides into one batched sweep;
``solve()`` itself is the one-shot wrapper around that session API.
On a mesh, ``comm=`` (``repro.core.comm.CommPolicy``) selects how the
per-iteration reduction runs: blocking psum, split psum_scatter +
delayed all_gather genuinely overlapped with compute, or a staged
ppermute ring.  ``precision=`` (``repro.core.precision.PrecisionPolicy``)
splits window *storage* dtype from scalar *compute* dtype -- bf16 window
arrays halve the dominant HBM traffic while recurrences, collective
payloads and convergence tests stay f32/f64.  ``l="auto"`` /
``comm="auto"`` (``repro.core.autotune``) calibrate the pipeline depth
and reduction policy from measured on-device latencies, clamped so the
storage-precision residual-gap floor never misses the requested ``tol``.
Individual algorithm modules (``cg.py``, ``plcg.py``, ``plcg_scan.py``,
...) stay importable directly for research use.
"""
from .autotune import (AutoDecision, clear_calibration_events, decide,
                       depth_budget, override_latencies, resolve_auto)
from .comm import CommPolicy, as_comm_policy
from .engine import (as_operator, clear_batch_trace, describe_methods,
                     get_method, methods, methods_supporting, register,
                     solve)
from .linop import (BindableOperator, LinearOperator, dense_operator,
                    identity_preconditioner, is_bindable)
from .precision import (PRECISION_MODES, PrecisionPolicy,
                        as_precision_policy)
from .precond import (BlockJacobi, Chebyshev, Identity, Jacobi, Multigrid,
                      Preconditioner, as_preconditioner, residual_gap)
from .results import SolveResult
from .session import SolveHandle, Solver, SolverPool
from .solver_cache import clear_solver_cache

__all__ = [
    "AutoDecision",
    "BindableOperator",
    "BlockJacobi",
    "Chebyshev",
    "CommPolicy",
    "Identity",
    "Jacobi",
    "LinearOperator",
    "Multigrid",
    "PRECISION_MODES",
    "PrecisionPolicy",
    "Preconditioner",
    "SolveHandle",
    "SolveResult",
    "Solver",
    "SolverPool",
    "as_comm_policy",
    "as_operator",
    "as_precision_policy",
    "as_preconditioner",
    "clear_batch_trace",
    "clear_calibration_events",
    "clear_solver_cache",
    "decide",
    "dense_operator",
    "depth_budget",
    "describe_methods",
    "get_method",
    "identity_preconditioner",
    "is_bindable",
    "methods",
    "methods_supporting",
    "override_latencies",
    "register",
    "residual_gap",
    "resolve_auto",
    "solve",
]
