"""Unified solver engine: one front-end for every Krylov method in the repo.

``solve(A, b, method=..., l=..., M=...)`` dispatches through a method
registry that every solver registers into with a common
:class:`~repro.core.results.SolveResult` contract:

  =============  ========================================================
  ``cg``         classic Hestenes-Stiefel CG (paper Alg. 4)
  ``pcg``        Ghysels-Vanroose pipelined CG, depth 1 (paper Alg. 5)
  ``plcg``       deep-pipelined p(l)-CG, python reference (paper Alg. 2)
  ``plcg_scan``  jitted p(l)-CG production engine (Alg. 3), early exit
  ``dlanczos``   direct Lanczos (exact-arithmetic oracle, Remark 7)
  ``plminres``   deep-pipelined MINRES (paper Remark 6; indefinite OK)
  =============  ========================================================

Batched multi-RHS: a 2-D right-hand side ``B`` of shape ``(nrhs, n)``
solves all systems at once.  For the scan-engine methods (``plcg``,
``plcg_scan``) the batch runs as **one jitted loop over the ``vmap`` of
the engine body** -- a single XLA compilation, a single fused program
in which every per-iteration reduction covers all right-hand sides.
Per-RHS convergence is masked inside the loop: a converged column's
state is frozen through the ``jnp.where``/``lax.select`` commit gate of
the engine body (under ``vmap`` that gate batches into a per-lane
``select``), mirroring how the paper's pipeline keeps all lanes busy
while individual systems finish at different iterations; the loop
itself stops after the body in which the last lane is done.  Methods
without a batched engine fall back to a loop of single-RHS solves.

The ``backend`` switch ("fused" | "pallas" | "ref" | "auto" | None)
selects the kernel tier used inside the scan engine's hot path (see
``plcg_scan``); it is threaded through both the single-RHS and the
batched paths, together with the operator's ``stencil2d`` structural
hint that lets ``backend="fused"`` fold the SPMV into its single
per-iteration Pallas launch.  Under the batched path the lane-major
``(n, window)`` state means every kernel batches to ONE
``(B, n, window)`` launch rather than B replays.
"""
from __future__ import annotations

import dataclasses
import functools
import weakref
from typing import Any, Callable, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from . import solver_cache, telemetry
from .cg import classic_cg
from .dlanczos import d_lanczos
from .linop import LinearOperator, dense_operator, is_bindable
from .pcg import ghysels_pcg
from .plcg import plcg
from .precision import as_precision_policy
from .precond import Multigrid, as_preconditioner
from .plcg_scan import plcg_solve, read_batched, resolve_backend
from .plcg_scan import plcg_scan as _plcg_scan_engine
from .plminres import plminres
from .results import SolveResult
from .shifts import chebyshev_shifts

Array = Any

_REGISTRY: dict[str, "MethodSpec"] = {}

#: Trace-time log of the batched engine: one entry is appended
#: each time XLA *traces* (= compiles) the batched engine (single-device
#: and mesh-aware), so tests can assert that a batched ``solve(A, B)``
#: compiles exactly once.
BATCH_TRACE_EVENTS: list[tuple] = []


def clear_batch_trace() -> None:
    """Reset :data:`BATCH_TRACE_EVENTS` (test helper).

    The mesh engine and the single-device batched engine both append to
    this exact list object, so it must be cleared in place -- rebinding
    the module attribute would silently detach their appends.  This
    helper is the one supported way to reset it.
    """
    BATCH_TRACE_EVENTS.clear()


@dataclasses.dataclass(frozen=True)
class MethodSpec:
    """Registry entry for one solver method.

    ``fn(A, b, x0, *, tol, maxiter, M, l, sigma, spectrum, backend, **opts)``
    must return a :class:`SolveResult`.  ``batched`` is ``"vmap"`` when the
    method is backed by the jittable scan engine (batch solves run as one
    jitted loop over the ``vmap`` of its body) and ``"loop"`` otherwise.  ``supports_M`` /
    ``supports_mesh`` are the capability flags :func:`solve` checks up
    front -- the single source of truth replacing per-adapter
    ``ValueError``s, so every method rejects an unsupported ``M=`` /
    ``mesh=`` with the same documented message.  ``options`` declares the
    method-specific ``**options`` keys the adapter accepts: unknown keys
    are rejected by :func:`solve` / :class:`~repro.core.session.Solver`
    with a uniform error instead of leaking into the method body (where
    they used to surface as an adapter-dependent ``TypeError`` or be
    swallowed silently).  ``supports_comm`` marks methods whose mesh
    execution honors a ``comm=`` communication policy (split-phase /
    ring reductions; see ``repro.core.comm``).  ``mesh_options`` is the
    subset of ``options`` the mesh execution path honors -- the single
    place that restriction lives (checked by ``_prepare_mesh_options``;
    the mesh adapters no longer carry their own allow-lists).
    ``supports_restart`` marks methods whose scan engine can re-seed
    broken lanes in-trace (``restart=`` / ``residual_replacement=``, see
    ``plcg_scan``); only those accept the stability knob pair.
    ``supports_precision`` marks methods whose engine splits window
    *storage* dtype from scalar *compute* dtype (``precision=``, see
    ``repro.core.precision``); only those accept non-default policies.
    """

    name: str
    fn: Callable[..., SolveResult]
    batched: str = "loop"
    description: str = ""
    supports_M: bool = True
    supports_mesh: bool = False
    supports_comm: bool = False
    supports_restart: bool = False
    supports_precision: bool = False
    uses_sigma: bool = False
    options: frozenset = frozenset()
    mesh_options: frozenset = frozenset()


def register(name: str, *, batched: str = "loop", description: str = "",
             supports_M: bool = True, supports_mesh: bool = False,
             supports_comm: bool = False, supports_restart: bool = False,
             supports_precision: bool = False, uses_sigma: bool = False,
             options: Sequence[str] = (), mesh_options: Sequence[str] = ()):
    """Decorator registering a solver adapter under ``name``.

    ``uses_sigma`` marks pipelined methods that consume the auxiliary-
    basis shifts -- only those trigger the (possibly costly) default
    shift-interval derivation from ``M.precond_spectrum``.  ``options``
    is the closed set of method-specific ``**options`` keys the adapter
    accepts; ``mesh_options`` (must be a subset) is what survives on the
    mesh execution path (execution paths may restrict the sets further,
    never widen them).
    """
    if batched not in ("loop", "vmap"):
        raise ValueError(f"batched must be 'loop' or 'vmap', got {batched!r}")
    if set(mesh_options) - set(options):
        raise ValueError(
            f"mesh_options {sorted(set(mesh_options) - set(options))} of "
            f"method {name!r} are not declared in options")
    if supports_comm and not supports_mesh:
        raise ValueError(
            f"method {name!r} declares supports_comm without supports_mesh; "
            "communication policies only select the mesh reduction")

    def deco(fn):
        _REGISTRY[name] = MethodSpec(name=name, fn=fn, batched=batched,
                                     description=description,
                                     supports_M=supports_M,
                                     supports_mesh=supports_mesh,
                                     supports_comm=supports_comm,
                                     supports_restart=supports_restart,
                                     supports_precision=supports_precision,
                                     uses_sigma=uses_sigma,
                                     options=frozenset(options),
                                     mesh_options=frozenset(mesh_options))
        return fn

    return deco


def methods() -> tuple[str, ...]:
    """Registered method names, sorted."""
    return tuple(sorted(_REGISTRY))


#: The cross-cutting solve knobs -- the keyword-only group every entry
#: point (:func:`solve`, :class:`~repro.core.session.Solver`,
#: ``prepare_on_mesh``) accepts on top of the per-method ``**options``.
#: ONE validation table: each knob maps to the ``MethodSpec`` capability
#: flag that gates it (None = accepted by every method) and the execution
#: path it selects; the ``_prepare_*`` helper named in the third column
#: normalizes it exactly once per prepared solver (never per call).
#:
#:   knob        capability flag   normalized by                path
#:   ----------  ----------------  ---------------------------  -----------
#:   ``M=``      ``supports_M``    ``_prepare_preconditioner``  all
#:   ``mesh=``   ``supports_mesh`` ``_prepare_mesh_check``      mesh only
#:   ``backend=``  --              ``plcg_scan`` BACKENDS       single-dev
#:                                 (warned + ignored on a mesh)
#:   ``comm=``   ``supports_comm`` ``_prepare_comm``            mesh only
#:                                 (rejected off-mesh up front;
#:                                 ``"auto"`` = calibrated pick)
#:   ``l=``      ``uses_sigma``    ``_prepare_depth``           pipelined
#:                                 (``"auto"`` = calibrated pick,
#:                                 resolved at session construction
#:                                 via ``repro.core.autotune``)
#:   ``restart=``            ``supports_restart``
#:                                 ``_prepare_restart``         all
#:   ``residual_replacement=``  ``supports_restart``
#:                                 ``_prepare_restart``         all
#:   ``precision=``  ``supports_precision``
#:                                 ``_prepare_precision``       all
_KNOB_TABLE = {
    "M": "supports_M",
    "mesh": "supports_mesh",
    "backend": None,
    "l": "uses_sigma",
    "comm": "supports_comm",
    "restart": "supports_restart",
    "residual_replacement": "supports_restart",
    "precision": "supports_precision",
}


def methods_supporting(capability: str) -> tuple[str, ...]:
    """Registered method names carrying a capability flag
    ("M" | "mesh" | "comm" | "restart" | "precision") -- derived from
    :data:`_KNOB_TABLE`."""
    flag = _KNOB_TABLE[capability]
    if flag is None:
        return methods()
    return tuple(m for m in methods() if getattr(_REGISTRY[m], flag))


def describe_methods() -> dict[str, str]:
    """name -> one-line description for every registered method."""
    return {k: _REGISTRY[k].description for k in methods()}


def get_method(name: str) -> MethodSpec:
    try:
        return _REGISTRY[name]
    except KeyError:
        raise ValueError(
            f"unknown method {name!r}; registered methods: "
            f"{', '.join(methods())}") from None


def as_operator(A, b=None) -> LinearOperator:
    """Coerce ``A`` (LinearOperator | BindableOperator | dense square array
    | matvec callable) into an operator the engine can run."""
    if isinstance(A, LinearOperator):
        return A
    if is_bindable(A):
        # rebindable-context operator: pass through as-is -- the engine
        # threads A.context into the jitted sweeps as a traced operand
        # and keys its caches on the stable A.matvec_ctx callable
        return A
    if hasattr(A, "ndim") and getattr(A, "ndim") == 2:
        if A.shape[0] != A.shape[1]:
            raise ValueError(f"dense operator must be square, got {A.shape}")
        return dense_operator(A)
    if callable(A):
        if b is None:
            raise ValueError("a matvec callable needs b to infer the "
                             "problem dimension")
        n = b.shape[-1]
        return LinearOperator(matvec=A, n=n, name="matvec")
    raise TypeError(f"cannot interpret {type(A).__name__} as a linear "
                    "operator")


#: Modules whose frames count as "inside the engine" for warning
#: attribution: the front-end itself and the prepared-solver session layer
#: it delegates to.
_INTERNAL_MODULES = (__name__, __name__.rsplit(".", 1)[0] + ".session")


def _stacklevel_outside_engine() -> int:
    """``warnings.warn`` stacklevel of the first frame outside the engine
    (this module and the session layer).

    Used so engine warnings point at the *caller of* :func:`solve` /
    :class:`~repro.core.session.Solver` regardless of how many internal
    dispatch frames sit in between (the depth differs between the
    batched, loop, mesh and prepared-session paths and would otherwise
    silently drift on refactors).
    """
    import sys
    level = 1
    frame = sys._getframe(1)
    while (frame is not None
           and frame.f_globals.get("__name__") in _INTERNAL_MODULES):
        level += 1
        frame = frame.f_back
    return level


def _is_mesh_operator(A) -> bool:
    """Duck-typed DistributedOperator check (no distributed import)."""
    return hasattr(A, "matvec_local") and hasattr(A, "mesh")


def _resolve_sigma(sigma, spectrum, l: int) -> list[float]:
    if sigma is not None:
        sig = [float(s) for s in sigma]
        if len(sig) != l:
            raise ValueError(f"need exactly l={l} shifts, got {len(sig)}")
        return sig
    lmin, lmax = spectrum if spectrum is not None else (0.0, 8.0)
    return chebyshev_shifts(lmin, lmax, l)


# --------------------------------------------------------------------------
# one-time preparation helpers (shared by solve() and session.Solver)
# --------------------------------------------------------------------------
#
# These are the pieces of the old monolithic solve() body that must run
# exactly ONCE per prepared solver but used to run on every call: method
# lookup, option validation, preconditioner normalization and the
# shift-interval defaulting.  solve() composes them per call (one-shot
# semantics unchanged); session.Solver composes them at construction.

def _prepare_method(method: str) -> MethodSpec:
    """Registry lookup (raises the uniform unknown-method error)."""
    return get_method(method)


def _prepare_options(spec: MethodSpec, options: dict) -> None:
    """Reject ``**options`` keys outside the method's declared set.

    Before this gate, unknown keys leaked into the adapter bodies where
    they surfaced as an adapter-dependent ``TypeError`` (or were silently
    swallowed by a ``**kw`` sink); now every method raises one uniform
    error naming its accepted keys.  Execution paths (batched vmap, mesh)
    may restrict the set further at dispatch time -- they can never widen
    it.
    """
    unknown = set(options) - spec.options
    if unknown:
        accepted = (", ".join(sorted(spec.options)) if spec.options
                    else "none")
        raise ValueError(
            f"method {spec.name!r} does not accept options "
            f"{sorted(unknown)}; accepted options for {spec.name!r}: "
            f"{accepted}")


#: preconditioners a configuration can name (``M="mg"``), each built from
#: the operator at preparation
NAMED_PRECONDITIONERS = {"mg": Multigrid}


def _prepare_preconditioner(spec: MethodSpec, M, A=None):
    """Normalize ``M`` once: a name in :data:`NAMED_PRECONDITIONERS` is
    built from the operator ``A``, bare callables promote to the
    Preconditioner protocol, Identity collapses to the cheaper
    unpreconditioned pipeline, and methods without the capability flag
    reject it up front -- every downstream layer sees either None or a
    structured Preconditioner, never a raw closure."""
    named = isinstance(M, str)
    if named and M not in NAMED_PRECONDITIONERS:
        raise TypeError(
            f"unknown preconditioner name {M!r}; known names: "
            f"{', '.join(sorted(NAMED_PRECONDITIONERS))}")
    if not named:
        M = as_preconditioner(M).runtime()
    if M is not None and not spec.supports_M:
        raise ValueError(
            f"method {spec.name!r} does not support preconditioning (M=); "
            f"methods with M= support: {', '.join(methods_supporting('M'))}")
    return NAMED_PRECONDITIONERS[M](A) if named else M


def _prepare_spectrum(spec: MethodSpec, M, sigma, spectrum):
    """Default the auxiliary-basis shift interval from the preconditioned
    spectrum when the preconditioner knows it (only for shift-consuming
    pipelined methods -- BlockJacobi's estimate runs a power iteration,
    which cg/pcg would discard)."""
    if (M is not None and sigma is None and spectrum is None
            and spec.uses_sigma):
        return M.precond_spectrum((0.0, 8.0))
    return spectrum


def _prepare_mesh_check(spec: MethodSpec, backend) -> None:
    """Mesh-capability gate + the backend-ignored warning (the injected
    local-partial dots bypass every kernel tier by construction)."""
    if not spec.supports_mesh:
        raise ValueError(
            f"method {spec.name!r} has no mesh-aware execution path; "
            f"methods available on a mesh: "
            f"{', '.join(methods_supporting('mesh'))}")
    if backend is not None:
        import warnings
        warnings.warn(
            f"backend={backend!r} is ignored on the mesh path: the "
            "injected local-partial dots bypass every kernel tier by "
            "construction (the distributed hot path is the "
            "halo-exchange stencil plus the collective schedule)",
            stacklevel=_stacklevel_outside_engine())


def _prepare_depth(spec: MethodSpec, l):
    """Normalize the pipeline depth ``l`` once: a positive int, or the
    ``"auto"`` sentinel selecting measured-latency calibration
    (``repro.core.autotune``).  ``"auto"`` is resolved where the operator
    is known -- session construction (``Solver`` / ``prepare_on_mesh``)
    -- so this helper only validates; methods that do not consume a
    pipeline depth (``uses_sigma`` is the capability that moves with it)
    reject the sentinel up front with the uniform knob style."""
    if l == "auto":
        if not spec.uses_sigma:
            raise ValueError(
                f"method {spec.name!r} has no pipeline depth to tune "
                "(l='auto' calibrates the depth of the pipelined "
                "methods); methods with a depth knob: "
                f"{', '.join(m for m in methods() if _REGISTRY[m].uses_sigma)}")
        return "auto"
    l = int(l)
    if l < 1:
        raise ValueError(f"pipeline depth l must be >= 1 (or 'auto'), "
                         f"got {l}")
    return l


def _prepare_comm(spec: MethodSpec, comm, on_mesh: bool):
    """Normalize ``comm=`` once (string -> ``CommPolicy``) and gate it on
    the capability flag and the execution path -- non-blocking policies
    select the *mesh* reduction schedule, so off-mesh uses are rejected
    up front with the same uniform style as ``M=`` / ``mesh=``.

    ``comm="auto"`` is a *sentinel*, not a policy mode: on a mesh with a
    ``supports_comm`` method it passes through as the string for the
    session layer to resolve against measured reduction latencies
    (``repro.core.autotune``); anywhere else only the blocking reduction
    exists, so auto degrades to it silently (asking for "the fastest
    available schedule" where exactly one is available is not an error).
    """
    from .comm import as_comm_policy
    if comm == "auto":
        if on_mesh and spec.supports_comm:
            return "auto"
        from .comm import CommPolicy
        return CommPolicy()
    policy = as_comm_policy(comm)
    if policy.is_blocking:
        return policy
    if not spec.supports_comm:
        raise ValueError(
            f"method {spec.name!r} does not support communication "
            f"policies (comm=); methods with comm= support: "
            f"{', '.join(methods_supporting('comm'))}")
    if not on_mesh:
        raise ValueError(
            f"comm={policy.mode!r} selects the mesh reduction schedule "
            "and has no single-device execution path; pass mesh=... (or "
            "a DistributedOperator) or drop comm=")
    return policy


def _prepare_restart(spec: MethodSpec, restart, residual_replacement,
                     options: dict):
    """Normalize the stability knob pair (``restart=`` /
    ``residual_replacement=``) once per prepared solver.

    ``restart`` is ``"auto" | int | None``: an int caps the number of
    in-scan per-lane re-seeds on square-root breakdown; ``None`` disables
    them (a single-RHS solve then falls back to the deprecated host
    restart loop when the legacy ``max_restarts`` option asks for it).
    ``"auto"`` (the default) lets the engine pick: it resolves to 5 when
    ``residual_replacement`` already put the sweep in stability mode
    (recovery is then free) and to ``None`` otherwise -- the stability
    machinery widens the reduction payload by one slot and un-fuses the
    stencil megakernel, so it stays opt-in on the default path.

    ``residual_replacement`` is a period in committed updates (int >= 1)
    for the in-scan true-residual recompute ``r = b - A x``, or ``None``.

    Returns the normalized ``(restart, residual_replacement)`` pair of
    ``Optional[int]``s.  Explicit use of either knob on a method without
    the ``supports_restart`` capability raises up front; combining an
    explicit ``restart=`` int with the legacy ``max_restarts`` option
    raises (two restart caps, ONE semantics).
    """
    rr = residual_replacement
    if rr is not None:
        rr = int(rr)
        if rr < 1:
            raise ValueError(
                f"residual_replacement must be a period >= 1 (committed "
                f"updates between true-residual recomputes), got "
                f"{residual_replacement!r}")
    if restart == "auto":
        restart = 5 if (spec.supports_restart and rr is not None) else None
    elif restart is not None:
        restart = int(restart)
        if restart < 0:
            raise ValueError(f"restart must be >= 0, got {restart!r}")
        if "max_restarts" in options:
            raise ValueError(
                "restart= (in-scan recovery) and the legacy max_restarts "
                "option (host restart loop) are mutually exclusive; drop "
                "max_restarts -- restart= is the one restart semantics")
    if (restart is not None or rr is not None) and not spec.supports_restart:
        raise ValueError(
            f"method {spec.name!r} does not support in-scan restarts / "
            f"residual replacement (restart= / residual_replacement=); "
            f"methods with restart support: "
            f"{', '.join(methods_supporting('restart'))}")
    return restart, rr


def _prepare_precision(spec: MethodSpec, precision):
    """Normalize ``precision=`` once (string/dtype -> ``PrecisionPolicy``)
    and gate it on the capability flag: the storage/compute dtype split
    lives in the scan engine's window handling, so methods without it
    reject non-default policies up front with the uniform style of the
    other knobs.  The default policy (None) is accepted everywhere -- it
    resolves to the legacy uniform-precision graphs bit-identically."""
    policy = as_precision_policy(precision)
    if not policy.is_default and not spec.supports_precision:
        raise ValueError(
            f"method {spec.name!r} does not support precision policies "
            f"(precision=); methods with precision= support: "
            f"{', '.join(methods_supporting('precision'))}")
    return policy


def _prepare_mesh_options(spec: MethodSpec, options: dict) -> None:
    """Reject declared method options the mesh execution path does not
    honor (``MethodSpec.mesh_options``) -- the single validation table
    replacing the allow-lists the mesh adapters used to hard-code."""
    unsupported = set(options) - spec.mesh_options
    if unsupported:
        supported = (f"; mesh-supported options for {spec.name!r}: "
                     f"{', '.join(sorted(spec.mesh_options))}"
                     if spec.mesh_options else "")
        raise ValueError(
            f"options {sorted(unsupported)} are not supported by the "
            f"mesh-aware {spec.name} path{supported}")


def _prepare_knobs(spec: MethodSpec, *, M, backend, mesh, comm,
                   precision=None, on_mesh: Optional[bool] = None, A=None):
    """One-stop validation of the cross-cutting knob group (M= / mesh= /
    backend= / comm= / precision= -- see :data:`_KNOB_TABLE`): runs each
    knob's ``_prepare_*`` helper in table order and returns the
    normalized ``(M, comm, precision)`` triple.  ``on_mesh`` may be
    forced when the mesh path is selected by an operator rather than an
    explicit ``mesh=``; ``A`` is the operator a named ``M`` is built
    from."""
    on_mesh = (mesh is not None) if on_mesh is None else on_mesh
    M = _prepare_preconditioner(spec, M, A)
    if on_mesh:
        _prepare_mesh_check(spec, backend)
    comm = _prepare_comm(spec, comm, on_mesh)
    precision = _prepare_precision(spec, precision)
    return M, comm, precision


# --------------------------------------------------------------------------
# the front-end
# --------------------------------------------------------------------------

def solve(
    A,
    b,
    method: str = "plcg",
    *,
    x0=None,
    tol: float = 1e-8,
    maxiter: int = 1000,
    M: Optional[Callable] = None,
    l=1,
    sigma: Optional[Sequence[float]] = None,
    spectrum: Optional[tuple] = None,
    backend: Optional[str] = None,
    mesh=None,
    comm=None,
    restart="auto",
    residual_replacement: Optional[int] = None,
    precision=None,
    **options,
) -> SolveResult:
    """Solve ``A x = b`` (or a stacked batch ``A X[j] = B[j]``).

    Args:
      A: :class:`LinearOperator`, dense square array, or matvec callable;
        with ``mesh=`` also a ``repro.distributed.DistributedOperator``
        (a ``LinearOperator`` with a ``stencil2d`` hint is auto-promoted
        to ``DistPoisson``).
      b: right-hand side ``(n,)``, or ``(nrhs, n)`` for a batched solve;
        on a mesh, the global field ``op.global_shape`` (e.g.
        ``(nx, ny)``) or a stacked batch ``(nrhs, nx, ny)``.
      method: one of :func:`methods` (default the paper's p(l)-CG).
      x0: initial guess, same shape as ``b`` (default zeros).
      tol: relative residual tolerance (``0`` disables early stopping).
      maxiter: solution-update budget.
      M: SPD preconditioner: a structured
        :class:`repro.core.precond.Preconditioner` (``Jacobi`` fuses into
        the Pallas megakernel via its ``inv_diag`` hint; ``BlockJacobi``
        / ``Chebyshev`` / constant-diagonal ``Jacobi`` run shard-local on
        a mesh) or any bare callable applying ``M^{-1} v`` (promoted via
        :func:`repro.core.precond.as_preconditioner`), or a name in
        :data:`NAMED_PRECONDITIONERS` built from ``A`` (``"mg"``: HPCG's
        multigrid V-cycle, :class:`repro.core.precond.Multigrid`, for an
        operator with the ``stencil27`` hint).  ``Identity``
        collapses to the unpreconditioned pipeline.  Methods without the
        ``supports_M`` capability flag reject it up front.
      l: pipeline depth (pipelined methods only), or ``"auto"`` to pick
        it from on-device calibration: the session layer measures one
        local SPMV, one stacked reduction per ``comm=`` mode and the
        per-depth sweep cost, then solves the paper's latency model
        ``t_iter ~ max(glred/l, spmv)`` for the fastest depth whose
        storage-precision residual-gap floor still reaches ``tol`` (see
        ``repro.core.autotune``; the decision and the measured
        latencies are reported in ``SolveResult.info["auto"]``).
        Passing a manual int pins the depth and bypasses calibration.
      sigma: l auxiliary-basis shifts; default Chebyshev roots on
        ``spectrum`` (itself defaulting to the Poisson interval (0, 8)).
      backend: kernel tier for the scan engine
        ("fused" | "pallas" | "ref" | "auto" | None), ignored by
        reference methods and by the distributed injected-dot path.
      mesh: a 2-axis ``jax.sharding.Mesh`` -- dispatches the method onto
        the mesh execution layer: domain decomposition inside
        (``shard_map`` + halo ``ppermute``), RHS batching outside
        (``vmap``), ONE fused psum per iteration carrying all lanes'
        ``(nrhs, 2l+1)`` payloads (``cg`` is the two-psum baseline).
        Methods without the ``supports_mesh`` registry capability raise;
        shard-local preconditioning composes (``M=BlockJacobi(...)``,
        ``Jacobi`` with a constant diagonal, ``Chebyshev``) and keeps the
        one-psum contract.
      comm: communication policy for the mesh reduction -- ``"blocking"``
        (default, one fused psum per iteration), ``"overlap"`` (split
        psum_scatter + delayed all_gather carried in the scan-state
        queue; genuinely in flight across d iterations of local
        compute), ``"ring"`` (circulate-accumulate ppermute hops staged
        across iterations; needs ``l >= hops + 1``), or a
        :class:`repro.core.comm.CommPolicy` (e.g. with an explicit
        overlap ``depth``).  ``"auto"`` picks the policy from measured
        reduction latencies on the live mesh (``repro.core.autotune``;
        off-mesh it degrades to blocking, the only schedule there).
        Methods without the ``supports_comm`` capability, and non-mesh
        calls, reject non-blocking policies up front.  See the
        ``M=``/``mesh=``/``backend=``/``comm=`` knob table in this
        module (``_KNOB_TABLE``).
      restart: in-scan breakdown recovery -- ``"auto" | int | None``.
        An int caps how many times each lane may re-seed its Krylov
        window from the current iterate after a square-root breakdown,
        *inside* the compiled sweep (per lane under batched ``vmap``,
        per shard group on a mesh, zero host round-trips; shifts are
        Ritz-refreshed from the committed tridiagonal).  ``None``
        disables in-scan recovery (legacy behavior; single-RHS solves
        may still use the deprecated host loop via ``max_restarts``).
        ``"auto"`` (default) enables cap 5 when ``residual_replacement``
        is set and resolves to ``None`` otherwise (see
        ``_prepare_restart``).  Methods without the ``supports_restart``
        capability reject explicit values up front.
      residual_replacement: period (committed updates) of the in-scan
        true-residual recompute ``r = b - A x`` countering the residual
        drift of deep pipelines (paper Sec. 4; arXiv:1706.05988), or
        ``None`` (default, off).  Compatible with every ``comm=`` policy
        (the replacement rides the existing per-iteration reduction,
        widened by one slot).
      precision: storage/compute precision policy for the scan engine --
        ``None`` (default: windows and scalars both in ``b.dtype``,
        bit-identical to the pre-policy engine), a storage dtype name
        (``"bf16"`` stores the ``Vw``/``Zw``/``Zhw`` window arrays and
        the SPMV stream in bfloat16 while every scalar recurrence, dot
        payload, collective buffer and convergence test stays in
        ``promote_types(b.dtype, float32)``), an explicit compound like
        ``"bf16x64"`` pinning the compute side, or a
        :class:`repro.core.precision.PrecisionPolicy`.  Methods without
        the ``supports_precision`` capability reject non-default
        policies up front.  See ``repro.core.precision`` and
        ``benchmarks/mp_bench.py`` for the measured traffic/accuracy
        ladder.
      **options: method-specific extras (``trace_gaps``, ``record_G``,
        ``max_restarts``, ``exploit_symmetry``, ...); keys outside the
        method's declared option set raise a uniform error naming the
        accepted keys.

    Returns:
      :class:`SolveResult`; for batched input, ``x`` has shape
      ``(nrhs, n)`` (``(nrhs, nx, ny)`` on a mesh), ``resnorms`` is a
      per-RHS list of traces, and ``info["per_rhs_converged"]`` /
      ``info["per_rhs_iters"]`` hold the per-system outcomes.

    This is the one-shot convenience wrapper around the prepared-solver
    session API: it builds a :class:`repro.core.session.Solver` (all
    validation / normalization / defaulting, once) and runs it on ``b``.
    Callers issuing many solves against one operator should hold the
    :class:`Solver` (or a :class:`repro.core.session.SolverPool`)
    themselves and skip the per-call setup entirely.
    """
    from .session import Solver
    with telemetry.span("solver.solve") as root:
        with telemetry.span("plcg.prepare"):
            # validate options before the keyword passthrough: session-only
            # constructor keywords (n=) must not absorb a same-named
            # unknown option key and dodge the uniform rejection
            _prepare_options(get_method(method), options)
            solver = Solver(A, method=method, tol=tol, maxiter=maxiter, M=M,
                            l=l, sigma=sigma, spectrum=spectrum,
                            backend=backend, mesh=mesh, comm=comm,
                            restart=restart,
                            residual_replacement=residual_replacement,
                            precision=precision, **options)
        r = solver._solve(b, x0=x0)
        root.requests = telemetry.request_ids(r.info.get("nrhs", 1))
    return r


# --------------------------------------------------------------------------
# batched multi-RHS paths
# --------------------------------------------------------------------------

def _solve_batched(spec: MethodSpec, A: LinearOperator, B, *, x0, tol,
                   maxiter, M, l, sigma, spectrum, backend,
                   restart=None, rr_period=None, precision=None,
                   get_engine=None, lanes=None, **options) -> SolveResult:
    nrhs = B.shape[0]
    if spec.batched == "vmap":
        return _solve_batched_vmap(spec, A, B, x0=x0, tol=tol,
                                   maxiter=maxiter, M=M, l=l, sigma=sigma,
                                   spectrum=spectrum, backend=backend,
                                   restart=restart, rr_period=rr_period,
                                   precision=precision,
                                   get_engine=get_engine, lanes=lanes,
                                   **options)
    outs = [
        spec.fn(A, B[j], None if x0 is None else x0[j], tol=tol,
                maxiter=maxiter, M=M, l=l, sigma=sigma, spectrum=spectrum,
                backend=backend, **options)
        for j in range(nrhs)
    ]
    return SolveResult(
        x=np.stack([np.asarray(r.x) for r in outs]),
        resnorms=[r.resnorms for r in outs],
        iters=max(r.iters for r in outs),
        converged=all(r.converged for r in outs),
        breakdowns=sum(r.breakdowns for r in outs),
        restarts=sum(r.restarts for r in outs),
        replacements=sum(r.replacements for r in outs),
        info={"method": spec.name, "batched": "loop", "nrhs": nrhs,
              "per_rhs_converged": [r.converged for r in outs],
              "per_rhs_iters": [r.iters for r in outs]},
    )


#: Jitted batched engines, keyed weakly on the operator/preconditioner
#: callables (see solver_cache; cleared by ``clear_solver_cache``).
_BATCH_CACHE = solver_cache.WeakCallableCache(maxsize=16)


def _batched_engine(method_name: str, matvec, l: int, iters: int, sigma,
                    tol: float, prec, exploit_symmetry: bool, unroll: int,
                    backend, stencil_hw, restart=None, rr_period=None,
                    ritz_refresh: bool = True, k_budget=None,
                    precision=None, bindable: bool = False):
    """Jitted batched engine, cached per configuration so repeated
    batched solves with the same operator/settings compile only once.

    Keyed on ``matvec``/``prec`` object identity through weak references:
    pass a long-lived ``LinearOperator`` (rather than a fresh dense array
    each call, which ``as_operator`` wraps in a new closure) to benefit
    from the cache.  Entries of dead closures are evicted eagerly, so the
    cache no longer pins operators the caller has dropped.

    ``bindable=True`` interprets ``matvec`` as ``matvec_ctx(context, v)``
    and the returned engine takes ``(context, B, X0)``: the context is a
    traced operand shared by every lane, so
    rebinding operator data between batched solves reuses the compiled
    program."""

    def build():
        mv = solver_cache.weakly_callable(matvec)
        kwargs = dict(
            l=l, iters=iters, sigma=sigma, tol=tol,
            prec=solver_cache.weakly_callable(prec),
            # diag fusion hint of a structured Preconditioner: captured as
            # an array constant (does not pin the preconditioner object)
            prec_diag=getattr(prec, "inv_diag", None),
            exploit_symmetry=exploit_symmetry, unroll=unroll,
            backend=backend, stencil_hw=stencil_hw,
            restart=restart, rr_period=rr_period,
            ritz_refresh=ritz_refresh, k_budget=k_budget,
            precision=precision)

        # the engine takes the stacked (nrhs, n) lanes itself: it vmaps
        # its body inside one loop that stops when every lane is done
        if bindable:
            def _batched_ctx(ctx, Bb, Xb):
                if len(BATCH_TRACE_EVENTS) < 4096:
                    BATCH_TRACE_EVENTS.append(
                        (method_name, tuple(Bb.shape), l))
                return _plcg_scan_engine(lambda v: mv(ctx, v), Bb, Xb,
                                         **kwargs)

            return jax.jit(_batched_ctx)

        engine = functools.partial(_plcg_scan_engine, mv, **kwargs)

        def _batched(Bb, Xb):
            # trace-time side effect: fires once per XLA compilation, so
            # the test suite can assert the batch compiles exactly once
            if len(BATCH_TRACE_EVENTS) < 4096:  # bounded in long processes
                BATCH_TRACE_EVENTS.append((method_name, tuple(Bb.shape), l))
            return engine(Bb, Xb)

        return jax.jit(_batched)

    return _BATCH_CACHE.get_or_build(
        (matvec, prec),
        (method_name, l, iters, sigma, tol, exploit_symmetry, unroll,
         backend, stencil_hw, restart, rr_period, ritz_refresh, k_budget,
         as_precision_policy(precision), bindable),
        build)


#: Batched engines that have warned about their ``tol`` (once each).
_TOL_WARNED = weakref.WeakSet()


def _batched_program(spec: MethodSpec, A: LinearOperator, B, *, x0, tol,
                     maxiter, M, l, sigma, spectrum, backend,
                     restart=None, rr_period=None, precision=None,
                     exploit_symmetry: bool = True, unroll: int = 1,
                     ritz_refresh: bool = True,
                     get_engine=None, **options):
    """The jitted batched engine and its operands for one stacked RHS:
    ``(fn, args, sigma, stab)`` with ``fn(*args)`` the one call that
    :func:`_solve_batched_vmap` runs (and ``fn.lower(*args)`` the program
    a prepared session reports, see ``session.Solver.lower``)."""
    if options:
        # don't silently drop flags the single-RHS call would honor
        # (trace_gaps, record_G, max_restarts, ...)
        raise ValueError(
            f"options {sorted(options)} are not supported by the batched "
            "engine; solve each RHS individually (1-D b) or "
            "use a loop-batched method (cg, pcg, dlanczos, plminres)")
    sig = tuple(_resolve_sigma(sigma, spectrum, l))
    Bj = jnp.asarray(B)
    precision = as_precision_policy(precision)
    X0 = jnp.zeros_like(Bj) if x0 is None else jnp.asarray(x0)
    from .plcg_scan import stab_iter_slack
    stab = restart is not None or rr_period is not None
    iters = maxiter + l + 1 + stab_iter_slack(l, restart, rr_period, maxiter)
    build = get_engine if get_engine is not None else _batched_engine
    # the stability slack bodies are pipeline re-fill, not extra updates:
    # an explicit k_budget freezes every lane at maxiter committed updates
    # (without stab, iters itself caps the count -- keep the graph as-is)
    bind = is_bindable(A)
    fn = build(spec.name, A.matvec_ctx if bind else A.matvec, l, iters,
               sig, tol, M, exploit_symmetry, unroll, backend,
               getattr(A, "stencil2d", None), restart, rr_period,
               ritz_refresh, maxiter if stab else None, precision, bind)
    args = (A.context, Bj, X0) if bind else (Bj, X0)
    # below the modeled residual-gap floor of the depth-l pipeline in its
    # storage dtype the lanes still converge on their recursive residual,
    # but the true residual b - A x misses tol
    from .autotune import attainable_floor
    sdt, _ = precision.resolve(Bj.dtype)
    floor = attainable_floor(l, sdt)
    if tol and tol < floor and fn not in _TOL_WARNED:
        import warnings
        _TOL_WARNED.add(fn)           # once per built engine
        # attribute the warning to the caller of solve(), not to a frame
        # inside this module: count the contiguous run of engine frames
        # above us instead of hard-coding the internal call-chain depth
        warnings.warn(
            f"tol={tol:g} is below the attainable floor {floor:.2g} of a "
            f"depth-{l} pipeline with {sdt} storage; lanes may report "
            "convergence while the true residual misses tol -- enable "
            "jax_enable_x64 or relax tol",
            stacklevel=_stacklevel_outside_engine())
    return fn, args, sig, stab


def _solve_batched_vmap(spec: MethodSpec, A: LinearOperator, B, *, x0, tol,
                        maxiter, M, l, sigma, spectrum, backend,
                        restart=None, rr_period=None, precision=None,
                        exploit_symmetry: bool = True, unroll: int = 1,
                        ritz_refresh: bool = True,
                        get_engine=None, lanes=None,
                        **options) -> SolveResult:
    """One jitted batched engine over the stacked RHS.

    A single XLA compilation covers all ``nrhs`` systems; converged lanes
    freeze via the engine's per-lane commit select while the remaining
    lanes keep iterating, and the loop stops once every lane is done.  Runs ONE sweep always: with ``restart=`` /
    ``rr_period=`` (normalized by ``_prepare_restart``) each lane
    re-seeds itself in-trace on breakdown / on the replacement period --
    recovery is per lane, inside the same compiled program, never a
    second sweep.

    ``get_engine`` (internal) lets a prepared :class:`session.Solver`
    inject its strongly-held jitted engine in place of the weak-key cache
    lookup; it receives exactly :func:`_batched_engine`'s arguments.
    Lanes past the first ``lanes`` (default: none) are padding: they
    count in the ``bodies`` telemetry counter, not in ``useful``.
    """
    with telemetry.span("plcg.prepare"):
        fn, args, sig, stab = _batched_program(
            spec, A, B, x0=x0, tol=tol, maxiter=maxiter, M=M, l=l,
            sigma=sigma, spectrum=spectrum, backend=backend,
            restart=restart, rr_period=rr_period, precision=precision,
            exploit_symmetry=exploit_symmetry, unroll=unroll,
            ritz_refresh=ritz_refresh, get_engine=get_engine, **options)
        precision = as_precision_policy(precision)
    out = telemetry.dispatch(fn, *args)
    telemetry.wait(out)
    (resnorms, conv, brk, k_done, restarts_pl, repl_pl) = read_batched(
        (out.resnorms, out.converged, out.breakdown, out.k_done,
         out.committed, out.restarts, out.replacements, out.trips),
        l=l, stab=stab, lanes=lanes, prec=M is not None)
    return SolveResult(
        x=out.x,
        resnorms=resnorms,
        iters=int(k_done.max()) + 1,
        converged=bool(conv.all()),
        breakdowns=int(brk.sum()) + int(restarts_pl.sum()),
        restarts=int(restarts_pl.sum()),
        replacements=int(repl_pl.sum()),
        info={"method": f"p({l})-CG[scan,vmap]", "l": l,
              "sigma": list(sig), "backend": resolve_backend(backend),
              "batched": "vmap",
              "prec": getattr(M, "name", None) if M is not None else None,
              "nrhs": int(conv.shape[0]),
              "restart": restart, "residual_replacement": rr_period,
              "precision": None if precision.is_default else precision,
              "per_rhs_converged": conv,
              "per_rhs_iters": k_done + 1,
              "per_rhs_breakdown": brk,
              "per_rhs_restarts": restarts_pl,
              "per_rhs_replacements": repl_pl},
    )


# --------------------------------------------------------------------------
# registered method adapters
# --------------------------------------------------------------------------

@register("cg", supports_mesh=True, options=("trace_true_residual",),
          description="classic Hestenes-Stiefel CG (paper Alg. 4)")
def _method_cg(A, b, x0=None, *, tol=1e-8, maxiter=1000, M=None, l=1,
               sigma=None, spectrum=None, backend=None, **kw):
    return classic_cg(A, b, x0, tol=tol, maxiter=maxiter, M=M, **kw)


@register("pcg", options=("trace_true_residual",),
          description="Ghysels-Vanroose pipelined CG, depth 1 (Alg. 5)")
def _method_pcg(A, b, x0=None, *, tol=1e-8, maxiter=1000, M=None, l=1,
                sigma=None, spectrum=None, backend=None, **kw):
    return ghysels_pcg(A, b, x0, tol=tol, maxiter=maxiter, M=M, **kw)


@register("dlanczos",
          description="direct Lanczos, exact-arithmetic oracle (Remark 7)")
def _method_dlanczos(A, b, x0=None, *, tol=1e-8, maxiter=1000, M=None, l=1,
                     sigma=None, spectrum=None, backend=None, **kw):
    return d_lanczos(A, b, x0, tol=tol, maxiter=maxiter, M=M, **kw)


@register("plcg", batched="vmap", supports_mesh=True, supports_comm=True,
          uses_sigma=True,
          options=("exploit_symmetry", "record_G", "trace_gaps", "prune",
                   "max_restarts"),
          mesh_options=("exploit_symmetry", "max_restarts"),
          description="deep-pipelined p(l)-CG reference (paper Alg. 2)")
def _method_plcg(A, b, x0=None, *, tol=1e-8, maxiter=1000, M=None, l=1,
                 sigma=None, spectrum=None, backend=None, **kw):
    return plcg(A, b, x0, l=l, tol=tol, maxiter=maxiter, M=M, sigma=sigma,
                spectrum=spectrum, **kw)


def _run_plcg_scan(A, b, x0, *, tol, maxiter, M, l, sigma, spectrum,
                   backend, sweep=None, restart=None,
                   residual_replacement=None, precision=None,
                   **kw) -> SolveResult:
    """Scan-engine single-RHS run + SolveResult packaging.

    Shared by the one-shot adapter below and the prepared session path:
    ``sweep`` (internal) is a pre-built jitted ``(b, x0, k_budget)``
    sweep a :class:`session.Solver` holds strongly -- when given,
    ``plcg_solve`` skips its weak-key cache lookup entirely.
    ``restart``/``residual_replacement`` arrive normalized (see
    ``_prepare_restart``); either being set selects the in-scan
    stability path of ``plcg_solve``.
    """
    sig = _resolve_sigma(sigma, spectrum, l)
    pp = as_precision_policy(precision)
    bj = jnp.asarray(b)
    x0j = None if x0 is None else jnp.asarray(x0)
    bind = is_bindable(A)
    x, resnorms, info = plcg_solve(A.matvec_ctx if bind else A.matvec,
                                   bj, x0j, l=l, sigma=sig,
                                   tol=tol, maxiter=maxiter, prec=M,
                                   backend=backend,
                                   stencil_hw=getattr(A, "stencil2d", None),
                                   sweep=sweep, restart=restart,
                                   residual_replacement=residual_replacement,
                                   precision=precision,
                                   context=A.context if bind else None,
                                   **kw)
    return SolveResult(
        x=x, resnorms=resnorms, iters=info["iterations"],
        converged=info["converged"], breakdowns=info["breakdowns"],
        restarts=info["restarts"],
        replacements=info.get("replacements", 0),
        info={"method": f"p({l})-CG[scan]", "l": l, "sigma": sig,
              "backend": resolve_backend(backend),
              "restart": restart,
              "residual_replacement": residual_replacement,
              "precision": (None if pp.is_default else pp),
              "prec": getattr(M, "name", None) if M is not None else None},
    )


@register("plcg_scan", batched="vmap", supports_mesh=True,
          supports_comm=True, supports_restart=True,
          supports_precision=True, uses_sigma=True,
          options=("exploit_symmetry", "max_restarts", "unroll",
                   "ritz_refresh"),
          mesh_options=("exploit_symmetry", "max_restarts", "ritz_refresh"),
          description="jitted p(l)-CG production engine (Alg. 3)")
def _method_plcg_scan(A, b, x0=None, *, tol=1e-8, maxiter=1000, M=None, l=1,
                      sigma=None, spectrum=None, backend=None, **kw):
    return _run_plcg_scan(A, b, x0, tol=tol, maxiter=maxiter, M=M, l=l,
                          sigma=sigma, spectrum=spectrum, backend=backend,
                          **kw)


@register("plminres", supports_M=False, uses_sigma=True,
          description="deep-pipelined MINRES (Remark 6; indefinite OK)")
def _method_plminres(A, b, x0=None, *, tol=1e-8, maxiter=1000, M=None, l=1,
                     sigma=None, spectrum=None, backend=None, **kw):
    # solve() enforces supports_M up front with the uniform message;
    # this guard covers direct registry invocation (get_method().fn) so
    # a passed M is never silently dropped
    if as_preconditioner(M).runtime() is not None:
        raise ValueError(
            "plminres does not support preconditioning (M=); see "
            "repro.core.methods_supporting('M')")
    r = plminres(A, b, x0, l=l, m=min(maxiter, A.n), sigma=sigma,
                 spectrum=spectrum, **kw)
    # plgmres runs a fixed m iterations; grade convergence on the true
    # residual with the same convention as the other methods (relative to
    # ||b||, and tol=0 means "never early-converged")
    x = np.asarray(r.x)
    bn = float(np.linalg.norm(np.asarray(b)))
    res = float(np.linalg.norm(np.asarray(b) - np.asarray(A @ x)))
    r.converged = bool(res <= tol * (bn if bn > 0 else 1.0))
    r.info["true_resnorm"] = res
    return r
