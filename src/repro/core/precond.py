"""Preconditioning as a first-class layer (paper Sec. 6, Alg. 4).

The flagship variant of the paper is *preconditioned* p(l)-CG, yet a bare
``M=`` callable tells the execution layers nothing: the fused Pallas tier
cannot fold an opaque closure into its megakernel, and the mesh layer
cannot know whether an apply is shard-local (no communication), neighbor-
local (halo ``ppermute`` only) or global (forbidden -- it would add a
reduction to the paper's single ``psum`` per iteration).

:class:`Preconditioner` makes those properties structural:

  * ``apply(v)``        -- the full-vector ``M^{-1} v`` (single device);
  * ``inv_diag``        -- optional diagonal hint: when set, ``M^{-1}`` IS
    an elementwise multiply, so ``backend="fused"`` folds the apply (and
    the zhat window recurrence) into its single per-iteration Pallas
    launch instead of splitting the body;
  * ``local_apply(op)`` -- optional shard-local apply bound to a
    :class:`~repro.distributed.operator.DistributedOperator`; returning a
    callable declares "no global communication inside", which is what
    lets the mesh engine run preconditioned p(l)-CG with still exactly
    ONE stacked psum per iteration;
  * ``precond_spectrum(base)`` -- optional inclusion interval for the
    spectrum of ``M^{-1} A``, used to default the auxiliary-basis shifts
    (``core.shifts.chebyshev_shifts``) of the preconditioned pipeline;
  * ``residual_gap`` diagnostics (module function): the attainable-
    accuracy gap ``(b - A x_k) - zeta_k v_k`` of arXiv:1804.02962 for any
    finished solve, preconditioned or not.

Concrete implementations: :class:`Identity` (the collapsed
unpreconditioned case), :class:`Jacobi` (diagonal; fuses into the
megakernel; shard-local when the diagonal is constant),
:class:`BlockJacobi` (block-local Chebyshev approximate inverse of the
Poisson stencil -- the paper's natural mesh preconditioner: zero
communication by construction) and :class:`Chebyshev` (polynomial in the
full operator, built on the SAME Chebyshev-root machinery as the basis
shifts; neighbor-halo traffic only on a mesh) and :class:`Multigrid`
(HPCG's V-cycle for the 27-point operator, one device; ``M="mg"``).

``as_preconditioner`` promotes bare callables (and the legacy
``linop.Preconditioner`` dataclass) so the public ``M=`` API is
unchanged.
"""
from __future__ import annotations

import math
from typing import Any, Callable, Optional, Sequence

import numpy as np

from .shifts import chebyshev_shifts

Array = Any


# --------------------------------------------------------------------------
# shared polynomial machinery (reuses the shift roots of core.shifts)
# --------------------------------------------------------------------------

def chebyshev_inverse_apply(matvec: Callable, v: Array,
                            shifts: Sequence[float]) -> Array:
    """``p(A) v`` with ``p(t) = (1 - prod_i (1 - t/sigma_i)) / t``.

    The ``sigma_i`` are the degree-m Chebyshev roots on ``[lmin, lmax]``
    (``core.shifts.chebyshev_shifts``), so ``1 - t p(t)`` is the scaled
    Chebyshev residual polynomial: ``|1 - t p(t)| <= 1/T_m(theta/delta)``
    on the interval, and ``p(t) > 0`` for every ``0 < t <= lmax`` -- i.e.
    ``p(A)`` is SPD whenever ``spec(A) \\subset (0, lmax]``.  Uses
    ``len(shifts) - 1`` operator applications.
    """
    # factored update: x_{k+1} = x_k + r_k / s_{k+1}, r_{k+1} = (I - A/s) r_k
    x = v * 0
    r = v
    for j, s in enumerate(shifts):
        x = x + r / s
        if j + 1 < len(shifts):            # last residual is never read
            r = r - matvec(r) / s
    return x


def _cheb_tp_range(lmin: float, lmax: float, degree: int,
                   tmax: float) -> tuple:
    """Numerical range of ``t * p(t)`` (= spectrum map of ``p(A) A``) over
    ``(0, tmax]`` for the degree-``degree`` Chebyshev inverse polynomial
    on ``[lmin, lmax]``."""
    sig = np.asarray(chebyshev_shifts(lmin, lmax, degree))
    t = np.linspace(tmax / 4096.0, tmax, 4096)
    r = np.ones_like(t)
    for s in sig:
        r *= 1.0 - t / s
    tp = 1.0 - r
    return float(tp.min()), float(tp.max())


# --------------------------------------------------------------------------
# the protocol
# --------------------------------------------------------------------------

class Preconditioner:
    """Base class / structural protocol for SPD preconditioners.

    Only the *inverse* application ``M^{-1} v`` is ever required (the
    paper's preconditioned p(l)-CG never applies ``M`` itself, Sec. 2.3).
    Subclasses override :meth:`apply`; everything else has safe defaults
    (no hints, no mesh path).
    """

    name: str = "M"

    def apply(self, v: Array) -> Array:
        raise NotImplementedError

    def __call__(self, v: Array) -> Array:
        return self.apply(v)

    # ---- structural hints ------------------------------------------------

    @property
    def is_identity(self) -> bool:
        """True when ``apply`` is the identity -- the engines then run the
        cheaper unpreconditioned pipeline (3l+2 instead of 3l+5 vectors).
        ``M=None`` and ``M=Identity()`` are the same solve."""
        return False

    @property
    def inv_diag(self):
        """Inverse diagonal when ``M^{-1}`` is an elementwise multiply
        (scalar or ``(n,)`` array), else None.  Set => ``backend="fused"``
        folds the apply into its single per-iteration Pallas launch."""
        return None

    def local_apply(self, op) -> Optional[Callable]:
        """Shard-local apply bound to a DistributedOperator, or None.

        The returned callable maps one *local flat block* to its
        preconditioned block inside ``shard_map`` and must not perform any
        global collective (neighbor ``ppermute`` halos are fine) -- that
        contract is what keeps the preconditioned mesh sweep at exactly
        ONE psum per iteration.
        """
        return None

    def precond_spectrum(self, base: tuple = (0.0, 8.0)) -> Optional[tuple]:
        """Inclusion interval for ``spec(M^{-1} A)`` given an interval
        ``base`` for ``spec(A)``, or None when unknown.  Drives the
        default auxiliary-basis shifts of the preconditioned pipeline."""
        return None

    def runtime(self) -> Optional["Preconditioner"]:
        """Self, or None for the identity -- the single place where the
        unpreconditioned code path collapses into ``M=Identity``."""
        return None if self.is_identity else self


class Identity(Preconditioner):
    """The trivial preconditioner: every unpreconditioned solve is the
    ``M=Identity`` case of the preconditioned pipeline."""

    name = "I"

    def apply(self, v):
        return v

    @property
    def is_identity(self):
        return True

    @property
    def inv_diag(self):
        return 1.0

    def local_apply(self, op):
        return lambda v: v

    def precond_spectrum(self, base=(0.0, 8.0)):
        return tuple(base)


class Jacobi(Preconditioner):
    """Diagonal preconditioner ``M = diag(d)``; ``apply`` multiplies by
    ``1/d``.  Carries the ``inv_diag`` fusion hint, so the fused scan
    backend keeps ONE Pallas launch per steady-state body.  Mesh-capable
    either way: a constant (scalar) diagonal is trivially shard-local,
    and a full ``(n,)`` diagonal is shard-split through the operator's
    2-D processor grid (each shard slices its own block of the inverse
    diagonal by mesh axis index -- an elementwise multiply, zero
    communication, so the preconditioned mesh sweep keeps exactly ONE
    psum per iteration).
    """

    def __init__(self, diag, name: str = "jacobi"):
        self.name = name
        d = np.asarray(diag, dtype=float)
        if d.ndim == 0 or (d.size and np.all(d == d.reshape(-1)[0])):
            self._inv = float(1.0 / (d if d.ndim == 0 else d.reshape(-1)[0]))
            self._scalar = True
        else:
            self._inv = 1.0 / d
            self._scalar = False

    @classmethod
    def from_operator(cls, A) -> "Jacobi":
        if getattr(A, "diag", None) is None:
            raise ValueError("operator exposes no diagonal")
        return cls(A.diag, name=f"jacobi({getattr(A, 'name', 'A')})")

    def apply(self, v):
        return v * self._inv

    @property
    def inv_diag(self):
        return self._inv

    def local_apply(self, op):
        if self._scalar:                # constant: trivially shard-local
            inv = self._inv
            return lambda v: v * inv
        # full (n,) diagonal: shard-split through the operator's 2-D
        # decomposition.  The global inverse diagonal rides the traced
        # program as a replicated constant; each shard dynamic-slices its
        # own (H, W) block by mesh axis index -- no collective, keeping
        # the one-psum-per-iteration gate of the mesh sweep.
        gshape = tuple(getattr(op, "global_shape", ()) or ())
        lshape = tuple(getattr(op, "local_shape", ()) or ())
        axes = getattr(op, "axes", None)
        if (len(gshape) != 2 or len(lshape) != 2 or axes is None
                or np.size(self._inv) != gshape[0] * gshape[1]):
            return None
        inv2d = np.asarray(self._inv).reshape(gshape)
        row_axis, col_axis = tuple(axes)[:2]

        def apply_local(vflat):
            import jax
            import jax.numpy as jnp
            H, W = lshape
            i = jax.lax.axis_index(row_axis)
            j = jax.lax.axis_index(col_axis)
            blk = jax.lax.dynamic_slice(
                jnp.asarray(inv2d, dtype=vflat.dtype),
                (i * H, j * W), (H, W))
            return (vflat.reshape(H, W) * blk).reshape(-1)

        return apply_local

    def precond_spectrum(self, base=(0.0, 8.0)):
        lo, hi = base
        if self._scalar:
            return (lo * self._inv, hi * self._inv)
        imin, imax = float(np.min(self._inv)), float(np.max(self._inv))
        return (lo * imin, hi * imax)


def _block_stencil5(g):
    """Zero-Dirichlet 5-point stencil on one 2-D block (no halos): the
    block-diagonal part of the Poisson operator.  jnp so it traces under
    jit/vmap/shard_map; identical math on a shard and on a vmapped block,
    which is what makes mesh vs single-device BlockJacobi bit-comparable.
    """
    import jax.numpy as jnp
    g = jnp.asarray(g)
    out = 4.0 * g
    out = out.at[1:, :].add(-g[:-1, :])
    out = out.at[:-1, :].add(-g[1:, :])
    out = out.at[:, 1:].add(-g[:, :-1])
    out = out.at[:, :-1].add(-g[:, 1:])
    return out


class BlockJacobi(Preconditioner):
    """Block-Jacobi for the 2-D Poisson stencil: each ``(nx/px, ny/py)``
    block is approximately inverted by a degree-``degree`` Chebyshev
    polynomial of the *block-local* zero-Dirichlet stencil.

    This is the paper's natural mesh preconditioner (Fig. 5 uses block
    Jacobi): the block grid is the processor grid, so ``local_apply`` is
    literally the one-block apply on the shard -- zero communication, and
    the preconditioned mesh sweep keeps its single psum per iteration.
    The polynomial local solve replaces the paper's ILU block solve,
    whose sequential triangular sweeps map poorly onto the TPU VPU; a
    positive Chebyshev polynomial of an SPD block is SPD by construction.

    On a single device ``apply`` partitions the global field into the
    SAME ``(px, py)`` blocks (one ``vmap`` over blocks), so mesh and
    single-device preconditioned solves agree to roundoff.
    """

    def __init__(self, stencil2d: tuple, blocks: tuple = (1, 1),
                 degree: int = 4, spectrum: tuple = (0.5, 8.0),
                 power_iters: int = 32, name: Optional[str] = None):
        nx, ny = stencil2d
        px, py = blocks
        if nx % px or ny % py:
            raise ValueError(f"grid {stencil2d} must divide blocks {blocks}")
        if not 0 < spectrum[0] < spectrum[1]:
            raise ValueError(f"need 0 < lmin < lmax, got {spectrum}")
        self.stencil2d = (int(nx), int(ny))
        self.blocks = (int(px), int(py))
        self.degree = int(degree)
        self.spectrum = (float(spectrum[0]), float(spectrum[1]))
        self.power_iters = int(power_iters)
        self._shifts = tuple(chebyshev_shifts(*self.spectrum, degree))
        self._pspec: Optional[tuple] = None     # lazy precond_spectrum
        self.name = name or f"block-jacobi{self.blocks}-cheb{degree}"

    @classmethod
    def for_mesh(cls, A, mesh, *, degree: int = 4,
                 spectrum: tuple = (0.5, 8.0), **kw) -> "BlockJacobi":
        """Blocks = the processor grid of ``mesh`` (first two axes), grid
        from the operator's ``stencil2d`` hint."""
        hint = getattr(A, "stencil2d", None) or getattr(A, "global_shape",
                                                        None)
        if hint is None:
            raise ValueError("BlockJacobi.for_mesh needs an operator with "
                             "a stencil2d hint (repro.operators.poisson2d)")
        names = tuple(mesh.axis_names)[:2]
        return cls(tuple(hint), (mesh.shape[names[0]], mesh.shape[names[1]]),
                   degree=degree, spectrum=spectrum, **kw)

    def _local2d(self, gb):
        """Chebyshev approximate inverse of one zero-Dirichlet block."""
        return chebyshev_inverse_apply(_block_stencil5, gb, self._shifts)

    def apply(self, v):
        import jax
        import jax.numpy as jnp
        v = jnp.asarray(v)
        nx, ny = self.stencil2d
        px, py = self.blocks
        bx, by = nx // px, ny // py
        g = (v.reshape(nx, ny).reshape(px, bx, py, by)
             .transpose(0, 2, 1, 3).reshape(px * py, bx, by))
        out = jax.vmap(self._local2d)(g)
        out = (out.reshape(px, py, bx, by).transpose(0, 2, 1, 3)
               .reshape(nx, ny))
        return out.reshape(v.shape)

    def local_apply(self, op):
        gshape = tuple(getattr(op, "global_shape", ()) or ())
        lshape = tuple(getattr(op, "local_shape", ()) or ())
        if gshape != self.stencil2d or len(lshape) != 2:
            return None
        nx, ny = self.stencil2d
        if (nx // lshape[0], ny // lshape[1]) != self.blocks:
            raise ValueError(
                f"BlockJacobi blocks {self.blocks} do not match the "
                f"operator's processor grid "
                f"{(nx // lshape[0], ny // lshape[1])}; build the "
                "preconditioner with BlockJacobi.for_mesh(A, mesh)")
        return lambda vflat: self._local2d(
            vflat.reshape(lshape)).reshape(-1)

    def precond_spectrum(self, base=(0.0, 8.0)):
        # a TIGHT interval matters here: a slack upper bound misplaces
        # the auxiliary-basis shifts, which degrades the conditioning of
        # G and triggers square-root breakdowns near the accuracy floor
        # (paper Sec. 4).  The stencil2d hint IS the global operator (the
        # zero-Dirichlet 5-point stencil on the full grid), so estimate
        # lam_max(M^{-1} A) directly by power iteration at first use;
        # power_iters=0 falls back to the analytic split bound
        # max t*p(t) + ||p||_inf * ||A - A_blk||_2  (cut coupling <= 2).
        if self._pspec is not None:
            return self._pspec
        lo, hi = self.spectrum
        if self.power_iters > 0:
            import jax.numpy as jnp
            nx, ny = self.stencil2d
            v = jnp.asarray(np.random.default_rng(7)
                            .standard_normal(nx * ny))
            lam = hi
            for _ in range(self.power_iters):
                w = self.apply(_block_stencil5(
                    v.reshape(nx, ny)).reshape(-1))
                lam = float(jnp.vdot(v, w) / jnp.vdot(v, v))
                v = w / jnp.linalg.norm(w)
            self._pspec = (0.0, 1.05 * lam)
            return self._pspec
        tmax = float(base[1])
        tp_max = _cheb_tp_range(lo, hi, self.degree, tmax)[1]
        theta = 0.5 * (hi + lo)
        delta = 0.5 * (hi - lo)
        s = theta / delta
        m = self.degree
        tm = math.cosh(m * math.acosh(s))
        tmp = m * math.sinh(m * math.acosh(s)) / math.sinh(math.acosh(s))
        p0 = tmp / (delta * tm)
        self._pspec = (0.0, tp_max + 2.0 * p0)
        return self._pspec


class Chebyshev(Preconditioner):
    """Polynomial preconditioner ``M^{-1} = p(A)`` with ``p`` the
    degree-``degree`` Chebyshev approximation of ``1/t`` on ``spectrum``
    -- the same root machinery (``core.shifts.chebyshev_shifts``) that
    generates the auxiliary-basis shifts.

    SPD whenever ``spec(A) \\subset (0, lmax]`` (the residual polynomial
    satisfies ``1 - t p(t) < 1`` there).  On a mesh, ``local_apply``
    applies the polynomial through the operator's ``matvec_local`` --
    ``degree - 1`` extra halo exchanges per iteration, neighbor traffic
    only, still zero extra global reductions.
    """

    def __init__(self, A=None, *, spectrum: tuple = (0.5, 8.0),
                 degree: int = 3, matvec: Optional[Callable] = None,
                 name: Optional[str] = None):
        if matvec is None:
            if A is None:
                raise ValueError("Chebyshev needs A (operator) or matvec=")
            if hasattr(A, "matvec"):
                matvec = A.matvec
            elif callable(A):
                matvec = A
            elif hasattr(A, "matvec_local"):
                matvec = None       # mesh-only: apply via local_apply(op)
            else:
                raise TypeError(f"cannot take a matvec from "
                                f"{type(A).__name__}")
        if not 0 < spectrum[0] < spectrum[1]:
            raise ValueError(f"need 0 < lmin < lmax, got {spectrum}")
        self._matvec = matvec
        self.degree = int(degree)
        self.spectrum = (float(spectrum[0]), float(spectrum[1]))
        self._shifts = tuple(chebyshev_shifts(*self.spectrum, degree))
        self.name = name or f"chebyshev-{degree}"

    def apply(self, v):
        if self._matvec is None:
            raise ValueError(
                "this Chebyshev preconditioner was built from a "
                "DistributedOperator and is mesh-local only; construct it "
                "from a LinearOperator/matvec for single-device applies")
        return chebyshev_inverse_apply(self._matvec, v, self._shifts)

    def local_apply(self, op):
        mv = getattr(op, "matvec_local", None)
        if mv is None:
            return None
        shifts = self._shifts
        return lambda vflat: chebyshev_inverse_apply(mv, vflat, shifts)

    def precond_spectrum(self, base=(0.0, 8.0)):
        lo, hi = self.spectrum
        tpmin, tpmax = _cheb_tp_range(lo, hi, self.degree, float(base[1]))
        return (0.0, tpmax)


#: the 8 colours of a 3-D grid: colour ``4a + 2b + c`` holds the points
#: whose (i, j, k) have parities (a, b, c); no two points of one colour
#: are 27-point neighbours
_COLOURS = tuple((a, b, c) for a in (0, 1) for b in (0, 1) for c in (0, 1))


def _split(u):
    """A ``(nx, ny, nz)`` field as its 8 colour sub-lattices, each
    ``(nx/2, ny/2, nz/2)`` in C order; sub-lattice 0 (the even points) is
    the next coarser grid."""
    import jax
    return [jax.lax.slice(u, col, u.shape, (2, 2, 2)) for col in _COLOURS]


def _merge(parts):
    """Inverse of :func:`_split`."""
    import jax.numpy as jnp
    mx, my, mz = parts[0].shape
    u = jnp.stack(parts).reshape(2, 2, 2, mx, my, mz)
    return u.transpose(3, 0, 4, 1, 5, 2).reshape(2 * mx, 2 * my, 2 * mz)


def _shift(x, axis: int, d: int):
    """``out[i] = x[i - d]`` along ``axis`` (d = +-1), zero where
    ``i - d`` leaves the grid."""
    import jax
    import jax.numpy as jnp
    cfg = [(0, 0, 0)] * x.ndim
    cfg[axis] = (1, -1, 0) if d > 0 else (-1, 1, 0)
    return jax.lax.pad(x, jnp.zeros((), x.dtype), cfg)


def _line(even, odd, parity: int, axis: int):
    """3-point sums along ``axis`` of the line whose even points are
    ``even`` and odd points ``odd``, at the points of ``parity``:
    ``u[2i-1] + u[2i] + u[2i+1]`` or ``u[2i] + u[2i+1] + u[2i+2]``."""
    if parity == 0:
        return _shift(odd, axis, 1) + even + odd
    return even + odd + _shift(even, axis, -1)


def _neighbour_sum(x: list, col: int):
    """Sum of the 26 neighbours of every point of colour ``col``, from the
    8 sub-lattices ``x`` (the separable 3 x 3 x 3 box sum with the point
    itself left out, so that the result does not read colour ``col``)."""
    import jax.numpy as jnp
    a, b, c = _COLOURS[col]
    x = list(x)
    x[col] = jnp.zeros_like(x[col])
    s0 = {(bb, cc): _line(x[2 * bb + cc], x[4 + 2 * bb + cc], a, 0)
          for bb in (0, 1) for cc in (0, 1)}
    s1 = [_line(s0[(0, cc)], s0[(1, cc)], b, 1) for cc in (0, 1)]
    return _line(s1[0], s1[1], c, 2)


def _symgs(r: list, x: list) -> list:
    """One symmetric Gauss-Seidel sweep of HPCG's 27-point ``A x = r``
    from ``x``, 8-coloured: forward over colours 7..0, backward 0..7, each
    colour set at once to ``(r + neighbours) / 26``.  The backward pass's
    first update (colour 0) reads only the neighbours the forward pass's
    last update read, so it would reproduce it exactly and is skipped.

    The coarse points (colour 0) must not be the last updated: a colour
    just updated has zero residual, so a sweep ending on them would leave
    the injected residual, and so the coarse correction, at rounding
    level (the V-cycle would be two sweeps and no multigrid)."""
    x = list(x)
    for col in (7, 6, 5, 4, 3, 2, 1, 0, 1, 2, 3, 4, 5, 6, 7):
        x[col] = (r[col] + _neighbour_sum(x, col)) / 26.0
    return x


class Multigrid(Preconditioner):
    """HPCG's multigrid V-cycle (reference ``ComputeMG``) for an operator
    carrying the ``stencil27`` hint (``repro.operators.hpcg27``), on one
    device.

    Four levels (HPCG's), each coarsened by 2 in every dimension.  On
    each level but the coarsest: one symmetric Gauss-Seidel sweep from
    zero, the residual ``r - A x`` injected at the even points
    (``r_c = (r_f - A_f x_f)[f2c]``), the V-cycle of the coarser level on
    it, its correction added at the same points (``x_f[f2c] += x_c``),
    and one more sweep; on the coarsest one sweep.  The coarse operator
    is the 27-point stencil on the coarse grid (not a Galerkin product).
    The sweep is 8-coloured (:func:`_symgs`) where HPCG's reference sweep
    is lexicographic; the backward pass reverses the forward pass's colour
    order, which keeps ``M`` symmetric, as p(l)-CG requires.

    A field lives as its 8 colour sub-lattices (:func:`_split`), so every
    colour update is one vectorised update of a stride-2 sub-lattice and
    the injection is sub-lattice 0.  Arithmetic runs in
    ``promote_types(dtype, float32)`` and the result is cast back.

    ``precond_spectrum`` is an interval for ``spec(M^{-1} A)``: ``(0,
    1.05 * lam)`` with ``lam`` from :data:`POWER_ITERS` power iterations
    on ``M^{-1} A`` (jitted, from a fixed seed, with the operator's own
    ``matvec``), estimated at construction in the span ``mg.setup``.
    Scopes ``mg.smooth`` / ``.residual`` /
    ``.restrict`` / ``.prolong`` / ``.coarse`` name every op of an apply
    (``.restrict`` includes the split of the input, ``.prolong`` the merge
    of the output).
    """

    name = "mg"
    #: HPCG's hierarchy: the fine grid and three coarser ones
    LEVELS = 4
    #: power iterations of the spectrum estimate
    POWER_ITERS = 20

    def __init__(self, A):
        from . import telemetry
        grid = getattr(A, "stencil27", None)
        if grid is None:
            raise ValueError(
                "the multigrid preconditioner (M='mg') is HPCG's V-cycle "
                "for the 27-point stencil: it needs an operator with the "
                "stencil27 hint (repro.operators.hpcg27); got "
                f"{getattr(A, 'name', type(A).__name__)}")
        grid = tuple(int(g) for g in grid)
        step = 2 ** self.LEVELS
        if len(grid) != 3 or any(g % step for g in grid):
            raise ValueError(
                f"Multigrid needs a 3-D grid whose every dimension splits "
                f"into 8 colours on each of its {self.LEVELS} levels "
                f"(divisible by 2**levels = {step}); got {grid}")
        self.grid = grid
        with telemetry.span("mg.setup", grid=grid):
            self._pspec = (0.0, 1.05 * self._estimate_lmax(A.matvec))

    def _vcycle(self, r: list, level: int) -> list:
        import jax
        import jax.numpy as jnp
        zero = [jnp.zeros_like(p) for p in r]
        if level == self.LEVELS - 1:
            with jax.named_scope("mg.coarse"):
                return _symgs(r, zero)
        with jax.named_scope("mg.smooth"):
            x = _symgs(r, zero)
        with jax.named_scope("mg.residual"):
            rc = r[0] - (26.0 * x[0] - _neighbour_sum(x, 0))
        with jax.named_scope("mg.restrict"):
            rc = _split(rc)
        xc = self._vcycle(rc, level + 1)
        with jax.named_scope("mg.prolong"):
            x[0] = x[0] + _merge(xc)
        with jax.named_scope("mg.smooth"):
            return _symgs(r, x)

    def apply(self, v):
        import jax
        import jax.numpy as jnp
        v = jnp.asarray(v)
        cdt = jnp.promote_types(v.dtype, jnp.float32)
        with jax.named_scope("mg.restrict"):
            r = _split(v.astype(cdt).reshape(self.grid))
        x = self._vcycle(r, 0)
        with jax.named_scope("mg.prolong"):
            out = _merge(x).reshape(v.shape).astype(v.dtype)
        # XLA would otherwise fuse the last sweep and the merge into every
        # consumer of M^-1 v in the engine's body and run them once for
        # each: the compiler's own count of the bytes a v5e sweep program
        # at 256^3 accesses falls from 134 GB to 51 GB with the barrier
        return jax.lax.optimization_barrier(out)

    def _estimate_lmax(self, A) -> float:
        """Largest eigenvalue of ``M^{-1} A`` by power iteration on the
        matvec ``A``, each step's estimate the Rayleigh quotient in the A
        inner product."""
        import jax
        import jax.numpy as jnp

        def step(_, carry):
            v, _ = carry
            av = A(v)
            w = self.apply(av)
            lam = jnp.vdot(av, w) / jnp.vdot(v, av)
            return w / jnp.linalg.norm(w), lam

        @jax.jit
        def run(key):
            v = jax.random.normal(key, (math.prod(self.grid),))
            v = v / jnp.linalg.norm(v)
            return jax.lax.fori_loop(0, self.POWER_ITERS, step,
                                     (v, jnp.zeros((), v.dtype)))[1]

        return float(run(jax.random.PRNGKey(0)))

    def local_apply(self, op):
        raise ValueError(
            "the multigrid preconditioner runs on one device: its V-cycle "
            "needs the whole grid, and there is no shard-local form; solve "
            "without mesh= (or use a shard-local preconditioner such as "
            "BlockJacobi on a mesh)")

    def precond_spectrum(self, base=(0.0, 8.0)):
        return self._pspec


class _CallablePreconditioner(Preconditioner):
    """Promotion of a bare ``M=`` callable (incl. the legacy
    ``linop.Preconditioner`` dataclass): full-vector apply only -- no
    fusion hint, no shard-local form."""

    def __init__(self, fn: Callable, name: str = "M"):
        self._fn = fn
        self.name = name

    def apply(self, v):
        return self._fn(v)


def as_preconditioner(M) -> Preconditioner:
    """Coerce ``M`` (None | Preconditioner | callable) to the protocol.

    ``None`` becomes :class:`Identity` -- downstream code then handles
    exactly one shape of object and collapses the identity back to the
    cheap unpreconditioned pipeline via :meth:`Preconditioner.runtime`.
    """
    if M is None:
        return _IDENTITY
    if isinstance(M, Preconditioner):
        return M
    if callable(M):
        return _CallablePreconditioner(M, name=getattr(M, "name", "M"))
    raise TypeError(f"cannot interpret {type(M).__name__} as a "
                    "preconditioner (need a callable applying M^{-1} v)")


_IDENTITY = Identity()


# --------------------------------------------------------------------------
# attainable-accuracy diagnostics (paper Sec. 4 / arXiv:1804.02962)
# --------------------------------------------------------------------------

def residual_gap(A, b, result, lane: Optional[int] = None) -> dict:
    """Residual-gap report for a finished solve.

    The pipelined recurrences drift: the *implicit* residual norm
    ``|zeta_k|`` (what the stopping test sees) and the *true* residual
    ``||b - A x_k||`` separate by the gap that bounds attainable accuracy
    (paper eq. 41/42, arXiv:1804.02962).  For a batched result pass
    ``lane`` (and that lane's ``b``).  Returns ``{"true_resnorm",
    "implicit_resnorm", "gap", "rel_gap"}``; with a preconditioner the
    implicit norm is the M-inner-product residual, so the gap is the
    honest cross-metric drift the caller should monitor.
    """
    x = np.asarray(result.x)
    bb = np.asarray(b)
    traces = result.resnorms
    if x.size != bb.size:
        if lane is None:
            raise ValueError(
                "batched result: pass lane= (and that lane's b) to "
                "residual_gap")
        x = x[lane]
        traces = traces[lane]
    elif lane is not None:
        traces = traces[lane]
    true = float(np.linalg.norm((bb.reshape(-1)
                                 - np.asarray(A @ x.reshape(-1)))
                                .reshape(-1)))
    last = traces[-1] if len(traces) else 0.0
    while isinstance(last, (list, tuple, np.ndarray)):
        last = last[-1] if len(last) else 0.0
    implicit = float(last)
    bnorm = float(np.linalg.norm(bb.reshape(-1))) or 1.0
    return {
        "true_resnorm": true,
        "implicit_resnorm": implicit,
        "gap": abs(true - implicit),
        "rel_gap": abs(true - implicit) / bnorm,
    }
