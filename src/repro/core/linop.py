"""Linear operator abstraction shared by every solver in the library.

A :class:`LinearOperator` is a thin, array-library-agnostic wrapper around a
``matvec`` callable.  The same object drives the numpy reference solvers, the
jitted JAX production solvers, and (through duck typing) the distributed
shard_map path -- the solvers only ever call ``A @ v`` / ``A.matvec(v)``.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional

Array = Any  # numpy or jax array


@dataclasses.dataclass(frozen=True)
class LinearOperator:
    """Matrix-free symmetric linear operator ``v -> A v``.

    Attributes:
      matvec: the operator application.
      n: problem dimension (vectors have shape ``(n,)``).
      diag: optional diagonal of A (used by Jacobi-type preconditioners).
      name: human-readable tag used in benchmark tables.
      stencil2d: optional (H, W) grid shape when the operator IS the
        unscaled 5-point Dirichlet Poisson stencil on that grid -- the
        structural hint that lets the ``backend="fused"`` scan engine fold
        the SPMV into its per-iteration Pallas megakernel.
      stencil27: optional ``(nx, ny, nz)`` when the operator is HPCG's
        27-point stencil on that grid (diagonal 26, the 26 neighbours -1,
        zero Dirichlet; vectors in C order) -- the hint from which
        ``repro.core.precond.Multigrid`` builds its V-cycle.
    """

    matvec: Callable[[Array], Array]
    n: int
    diag: Optional[Array] = None
    name: str = "A"
    stencil2d: Optional[tuple] = None
    stencil27: Optional[tuple] = None

    def __matmul__(self, v: Array) -> Array:
        return self.matvec(v)

    def __call__(self, v: Array) -> Array:
        return self.matvec(v)


@dataclasses.dataclass(eq=False)
class BindableOperator:
    """Matrix-free SPD operator whose matvec closes over a *rebindable*
    context pytree: ``matvec(v) = matvec_ctx(context, v)``.

    The point is zero-retrace outer loops (Newton–CG training): a plain
    ``LinearOperator`` closure would bake its captured arrays into the
    compiled sweep as trace-time constants, forcing a retrace whenever the
    operator data changes (new parameters, new batch).  Here the engine
    threads ``context`` through every prepared sweep as a TRACED leading
    operand and keys its compile caches on the *stable* ``matvec_ctx``
    callable, so ``bind()``-ing fresh same-shape data between solves reuses
    the one compiled program.

    ``matvec_ctx`` must be a stable callable (an instance attribute or
    module-level function, not a per-call lambda) with signature
    ``(context, v) -> Av``; ``context`` may be any pytree of arrays.

    ``eq=False`` keeps identity hashing -- instances are weak-cache keys.
    """

    matvec_ctx: Callable[[Any, Array], Array]
    n: int
    context: Any
    diag: Optional[Array] = None
    name: str = "A"
    stencil2d: Optional[tuple] = None

    def bind(self, context: Any) -> "BindableOperator":
        """Swap in fresh operator data (same pytree structure/shapes)."""
        self.context = context
        return self

    def matvec(self, v: Array) -> Array:
        return self.matvec_ctx(self.context, v)

    def __matmul__(self, v: Array) -> Array:
        return self.matvec(v)

    def __call__(self, v: Array) -> Array:
        return self.matvec(v)


def is_bindable(A: Any) -> bool:
    """True when ``A`` carries a rebindable ``(context, v)`` matvec."""
    return callable(getattr(A, "matvec_ctx", None)) and hasattr(A, "context")


@dataclasses.dataclass(frozen=True)
class Preconditioner:
    """SPD preconditioner; ``apply`` computes ``M^{-1} v``.

    Only the *inverse* application is ever required by the algorithms in this
    repo (the paper's preconditioned p(l)-CG never applies ``M`` itself --
    the unpreconditioned auxiliary basis removes that need, Sec. 2.3).
    """

    apply: Callable[[Array], Array]
    name: str = "M"

    def __call__(self, v: Array) -> Array:
        return self.apply(v)


def dense_operator(A: Array, name: str = "dense") -> LinearOperator:
    """Wrap a dense (n, n) symmetric matrix as a LinearOperator."""
    n = A.shape[0]
    if A.shape != (n, n):
        raise ValueError(f"dense_operator expects a square matrix, got {A.shape}")
    diag = A.diagonal()
    return LinearOperator(matvec=lambda v: A @ v, n=n, diag=diag, name=name)


def identity_preconditioner() -> Preconditioner:
    return Preconditioner(apply=lambda v: v, name="I")
