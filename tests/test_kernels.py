"""Per-kernel allclose vs the pure-jnp oracles (interpret mode executes the
TPU kernel bodies exactly), swept over shapes and dtypes.  Window kernels
take lane-major (n, window) operands."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels import ops as kops
from repro.kernels.fused_body import fused_body
from repro.kernels.multidot import multidot
from repro.kernels.stencil2d import stencil2d, stencil2d_batched
from repro.kernels.window_axpy import window_axpy

KEY = jax.random.PRNGKey(7)


@pytest.mark.parametrize("shape", [(32, 128), (64, 128), (128, 256), (40, 128),
                                   (20, 36)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("bh", [8, 16])
def test_stencil2d(shape, dtype, bh):
    H, W = shape
    x = jax.random.normal(KEY, (H, W), jnp.float32).astype(dtype)
    hn = jax.random.normal(jax.random.PRNGKey(1), (W,), jnp.float32).astype(dtype)
    hs = jax.random.normal(jax.random.PRNGKey(2), (W,), jnp.float32).astype(dtype)
    hw = jax.random.normal(jax.random.PRNGKey(3), (H,), jnp.float32).astype(dtype)
    he = jax.random.normal(jax.random.PRNGKey(4), (H,), jnp.float32).astype(dtype)
    out = stencil2d(x, hn, hs, hw, he, bh=bh, interpret=True)
    want = ref.stencil2d_ref(x, hn, hs, hw, he)
    tol = 1e-5 if dtype == jnp.float32 else 8e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), atol=tol)


def test_stencil2d_matches_poisson_operator():
    """With zero halos the kernel IS the paper's Poisson operator."""
    from repro.operators import poisson2d
    H = W = 128
    A = poisson2d(H, W)
    x = np.random.default_rng(0).standard_normal(H * W).astype(np.float32)
    z = jnp.zeros
    out = stencil2d(jnp.asarray(x.reshape(H, W)), z(W), z(W), z(H), z(H),
                    interpret=True)
    np.testing.assert_allclose(np.asarray(out).reshape(-1), A @ x,
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("m,n", [(3, 1024), (5, 4096), (9, 2048), (7, 1536),
                                 (7, 1001)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_multidot(m, n, dtype):
    W = jax.random.normal(KEY, (n, m), jnp.float32).astype(dtype)
    z = jax.random.normal(jax.random.PRNGKey(9), (n,), jnp.float32).astype(dtype)
    out = multidot(W, z, bn=512, interpret=True)
    want = ref.multidot_ref(W, z)
    rel = np.max(np.abs(np.asarray(out) - np.asarray(want))) / (
        np.max(np.abs(np.asarray(want))) + 1e-9)
    assert rel < (1e-5 if dtype == jnp.float32 else 3e-2)


def test_multidot_preserves_f64():
    """x64 accumulation stays f64 (the tight-parity requirement of the
    backend ladder)."""
    old = jax.config.jax_enable_x64
    jax.config.update("jax_enable_x64", True)
    try:
        W = jax.random.normal(KEY, (2048, 5), jnp.float64)
        z = jax.random.normal(jax.random.PRNGKey(9), (2048,), jnp.float64)
        out = multidot(W, z, bn=512, interpret=True)
        assert out.dtype == jnp.float64
        np.testing.assert_allclose(np.asarray(out),
                                   np.asarray(ref.multidot_ref(W, z)),
                                   rtol=1e-14)
    finally:
        jax.config.update("jax_enable_x64", old)


@pytest.mark.parametrize("m,n", [(2, 1024), (6, 4096), (10, 2048), (6, 1001)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_window_axpy(m, n, dtype):
    V = jax.random.normal(KEY, (n, m), jnp.float32).astype(dtype)
    z = jax.random.normal(jax.random.PRNGKey(2), (n,), jnp.float32).astype(dtype)
    g = jax.random.normal(jax.random.PRNGKey(3), (m,), jnp.float32)
    out = window_axpy(V, z, g, 1.25, bn=512, interpret=True)
    want = ref.window_axpy_ref(V, z, g, 1.25)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               atol=1e-4 if dtype == jnp.float32 else 1e-1)


@pytest.mark.parametrize("B", [1, 3, 8])
@pytest.mark.parametrize("bh", [8, 16])
def test_stencil2d_batched_matches_per_lane(B, bh):
    """The lane-leading (B, H, W) batched kernel is bit-identical to B
    single-lane applications."""
    H, W = 32, 128
    ks = [jax.random.PRNGKey(i) for i in range(5)]
    x = jax.random.normal(ks[0], (B, H, W), jnp.float32)
    hn = jax.random.normal(ks[1], (B, W), jnp.float32)
    hs = jax.random.normal(ks[2], (B, W), jnp.float32)
    hw = jax.random.normal(ks[3], (B, H), jnp.float32)
    he = jax.random.normal(ks[4], (B, H), jnp.float32)
    out = stencil2d_batched(x, hn, hs, hw, he, bh=bh, interpret=True)
    want = jnp.stack([ref.stencil2d_ref(x[i], hn[i], hs[i], hw[i], he[i])
                      for i in range(B)])
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=1e-6)
    np.testing.assert_allclose(
        np.asarray(ref.stencil2d_batched_ref(x, hn, hs, hw, he)),
        np.asarray(want), atol=0)


def test_stencil2d_apply_vmaps_to_one_launch():
    """jax.vmap of the halo stencil (the mesh engine's multi-RHS SPMV)
    lowers to ONE pallas_call streaming the whole lane batch -- the
    custom_vmap rule installs stencil2d_batched."""
    from repro.kernels.introspect import count_pallas_calls
    B, H, W = 4, 16, 128
    x = jax.random.normal(KEY, (B, H, W), jnp.float32)
    hn = jnp.zeros((B, W))
    hw = jnp.zeros((B, H))

    def one(xx, a, b, c, d):
        return kops.stencil2d_apply(xx, a, b, c, d, use_pallas=True)

    assert count_pallas_calls(jax.vmap(one), x, hn, hn, hw, hw) == 1
    got = jax.vmap(one)(x, hn, hn, hw, hw)
    want = ref.stencil2d_batched_ref(x, hn, hn, hw, hw)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-6)
    # the jnp-oracle path batches through the same custom_vmap rule
    got_ref = jax.vmap(lambda *a: kops.stencil2d_apply(*a,
                                                       use_pallas=False))(
        x, hn, hn, hw, hw)
    np.testing.assert_allclose(np.asarray(got_ref), np.asarray(want), atol=0)


# ---------------------- fused iteration megakernel ------------------------

def _fused_inputs(l, n, dtype, prec=False):
    m = 2 * l + 1
    Vw = jax.random.normal(KEY, (n, m), jnp.float32).astype(dtype)
    Zw = jax.random.normal(jax.random.PRNGKey(1), (n, l + 1),
                           jnp.float32).astype(dtype)
    Zhw = (jax.random.normal(jax.random.PRNGKey(2), (n, 3),
                             jnp.float32).astype(dtype) if prec else None)
    t = jax.random.normal(jax.random.PRNGKey(3), (n,),
                          jnp.float32).astype(dtype)
    th = (jax.random.normal(jax.random.PRNGKey(4), (n,),
                            jnp.float32).astype(dtype) if prec else None)
    g = jax.random.normal(jax.random.PRNGKey(5), (2 * l,),
                          jnp.float32).astype(dtype)
    scalars = dict(s_warm=jnp.asarray(0.7, dtype), gam=jnp.asarray(1.3, dtype),
                   dlt=jnp.asarray(0.9, dtype), dsub=jnp.asarray(0.4, dtype),
                   gcc=jnp.asarray(1.1, dtype), g=g)
    return Vw, Zw, Zhw, t, th, scalars


def _pack_scal(steady, scalars, l, dtype, invd_s=0.0):
    # layout must match fused_body.N_FIXED_SCALARS (incl. the scalar
    # inverse-diagonal slot of the fused preconditioner apply)
    return jnp.concatenate([
        jnp.stack([jnp.asarray(1.0 if steady else 0.0, dtype),
                   scalars["s_warm"], scalars["gam"], scalars["dlt"],
                   scalars["dsub"], scalars["gcc"],
                   jnp.asarray(invd_s, dtype)]),
        scalars["g"]]).reshape(1, 7 + 2 * l).astype(dtype)


@pytest.mark.parametrize("l", [1, 2, 4])
@pytest.mark.parametrize("steady", [True, False])
@pytest.mark.parametrize("prec", [False, True])
def test_fused_body_matches_oracle(l, steady, prec):
    n, dtype = 2048, jnp.float32
    Vw, Zw, Zhw, t, th, scalars = _fused_inputs(l, n, dtype, prec=prec)
    scal = _pack_scal(steady, scalars, l, dtype)
    got = fused_body(Vw, Zw, scal, Zhw, t, th, l=l, bn=512, interpret=True)
    want = ref.fused_body_ref(Vw, Zw, Zhw, t, th, l=l,
                              steady=jnp.bool_(steady), **scalars)
    labels = ("Vw2", "Zw2", "Zhw2", "dots")
    for lab, a, b in zip(labels, got, want):
        if a is None and b is None:
            continue
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), atol=2e-4,
                                   err_msg=lab)


@pytest.mark.parametrize("hw", [(16, 128), (32, 128), (24, 256), (12, 30),
                                (10, 36)])
def test_fused_body_in_kernel_stencil(hw):
    """t=None folds the 5-point Dirichlet SPMV into the kernel; must match
    the oracle that applies stencil2d_ref to Zw[:, 0].  Widths that are
    not a multiple of 8 give blocks that straddle grid rows and a partial
    last block."""
    H, W = hw
    l, n, dtype = 2, H * W, jnp.float32
    Vw, Zw, _, _, _, scalars = _fused_inputs(l, n, dtype)
    scal = _pack_scal(True, scalars, l, dtype)
    got = fused_body(Vw, Zw, scal, None, None, None, l=l,
                     stencil_hw=(H, W), bn=8 * W, interpret=True)
    want = ref.fused_body_ref(Vw, Zw, None, None, None, l=l,
                              steady=jnp.bool_(True), stencil_hw=(H, W),
                              **scalars)
    for a, b in zip(got, want):
        if a is None and b is None:
            continue
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), atol=2e-4)


@pytest.mark.parametrize("l", [1, 3])
def test_fused_body_partial_last_block(l):
    """An n with no 8-aligned divisor runs a partial last block whose
    out-of-range rows stay out of the payload dots."""
    n, dtype = 1001, jnp.float32
    Vw, Zw, _, t, _, scalars = _fused_inputs(l, n, dtype)
    scal = _pack_scal(True, scalars, l, dtype)
    got = fused_body(Vw, Zw, scal, None, t, None, l=l, bn=256,
                     interpret=True)
    want = ref.fused_body_ref(Vw, Zw, None, t, None, l=l,
                              steady=jnp.bool_(True), **scalars)
    for lab, a, b in zip(("Vw2", "Zw2", "Zhw2", "dots"), got, want):
        if a is None and b is None:
            continue
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), atol=2e-4,
                                   err_msg=lab)


@pytest.mark.parametrize("mode", ["scalar", "vector"])
@pytest.mark.parametrize("stencil", [False, True])
def test_fused_body_diag_preconditioner(mode, stencil):
    """The in-kernel diagonal preconditioner apply (scalar slot or (n, 1)
    operand), with and without the fused stencil SPMV, matches the oracle
    that applies t = invd * t_hat."""
    H, W = 16, 128
    l, n, dtype = 2, H * W, jnp.float32
    Vw, Zw, Zhw, t, th, scalars = _fused_inputs(l, n, dtype, prec=True)
    if mode == "scalar":
        invd = jnp.asarray(0.25, dtype)
        scal = _pack_scal(True, scalars, l, dtype, invd_s=0.25)
        vec = None
    else:
        invd = 1.0 / jnp.linspace(3.5, 4.5, n).astype(dtype)
        scal = _pack_scal(True, scalars, l, dtype)
        vec = invd.reshape(n, 1)
    if stencil:
        got = fused_body(Vw, Zw, scal, Zhw, None, None, vec, l=l,
                         stencil_hw=(H, W), diag=mode, bn=4 * W,
                         interpret=True)
        want = ref.fused_body_ref(Vw, Zw, Zhw, None, None, l=l,
                                  steady=jnp.bool_(True), invd=invd,
                                  stencil_hw=(H, W), **scalars)
    else:
        got = fused_body(Vw, Zw, scal, Zhw, None, th, vec, l=l,
                         diag=mode, bn=512, interpret=True)
        want = ref.fused_body_ref(Vw, Zw, Zhw, None, th, l=l,
                                  steady=jnp.bool_(True), invd=invd,
                                  **scalars)
    for lab, a, b in zip(("Vw2", "Zw2", "Zhw2", "dots"), got, want):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), atol=2e-4,
                                   err_msg=lab)


def test_fused_body_batches_to_one_launch():
    """vmap over the megakernel (the batched multi-RHS engine) must lower
    to ONE pallas_call handling the whole (B, n, window) batch."""
    from repro.kernels.introspect import count_pallas_calls
    l, n, B, dtype = 2, 1024, 3, jnp.float32
    Vw, Zw, _, t, _, scalars = _fused_inputs(l, n, dtype)
    scal = _pack_scal(True, scalars, l, dtype)
    stack = lambda a: jnp.stack([a] * B)  # noqa: E731
    fn = jax.vmap(lambda V, Z, s, tt: fused_body(V, Z, s, None, tt, None,
                                                 l=l, bn=512, interpret=True))
    assert count_pallas_calls(fn, stack(Vw), stack(Zw), stack(scal),
                              stack(t)) == 1
    out = fn(stack(Vw), stack(Zw), stack(scal), stack(t))
    want = ref.fused_body_ref(Vw, Zw, None, t, None, l=l,
                              steady=jnp.bool_(True), **scalars)
    np.testing.assert_allclose(np.asarray(out[0][1]), np.asarray(want[0]),
                               atol=2e-4)


def test_kernels_drive_a_full_solve():
    """The fused kernels plugged into the reference solver reproduce it."""
    from repro.core.plcg import plcg
    from repro.operators import poisson2d
    A = poisson2d(16, 16)
    b = A @ np.ones(A.n)
    r = plcg(A, b, l=2, tol=1e-9, maxiter=200, spectrum=(0, 8))
    assert r.converged
