"""The Pallas kernels compile for a TPU v5e chip, at the paper's sizes.

Nothing runs: each case compiles one kernel for a v5e chip that is
described but not attached (``jax.experimental.topologies``), with
``interpret=False``, so the chip's compiler refuses here what it would
refuse on the chip -- a block not aligned to the (8, 128) tiling, a
lane-to-sublane relayout Mosaic cannot do, more VMEM than a core has.
Interpret-mode tests (``test_kernels.py``) cannot see any of that.

The topology is described inside a module fixture (never at import), so
every pytest-xdist worker collects the same tests and only the worker
that runs this file loads the TPU library.  The persistent compilation
cache is off around the compiles: a deviceless compile is written to it
but cannot be read back without a chip.
"""
import os

import jax
import jax.numpy as jnp
import pytest

from repro.kernels.fused_body import N_FIXED_SCALARS, fused_body
from repro.kernels.multidot import multidot
from repro.kernels.stencil2d import stencil2d, stencil2d_batched
from repro.kernels.window_axpy import window_axpy


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    return topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.experimental.compilation_cache import compilation_cache as cc
    from jax.sharding import SingleDeviceSharding
    old = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", old)
    cc.reset_cache()


def _compile(one_chip, fn, *shapes) -> int:
    """Compile ``fn`` for the described chip; returns its kernel count."""
    args = [jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip)
            for s in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    return text.count('custom_call_target="tpu_custom_call"')


@pytest.mark.parametrize("H", [500, 875, 1024])
def test_stencil2d_compiles(one_chip, H):
    """The local SPMV of the paper's grids on a 2x2 mesh (1000^2 -> 500^2,
    1750^2 -> 875^2) and an aligned block."""
    fn = lambda x, a, b, c, d: stencil2d(x, a, b, c, d,  # noqa: E731
                                         interpret=False)
    assert _compile(one_chip, fn, (H, H), (H,), (H,), (H,), (H,)) == 1


@pytest.mark.parametrize("H", [256, 500])
def test_stencil2d_batched_compiles(one_chip, H):
    B = 8
    fn = lambda x, a, b, c, d: stencil2d_batched(  # noqa: E731
        x, a, b, c, d, interpret=False)
    assert _compile(one_chip, fn, (B, H, H), (B, H), (B, H), (B, H),
                    (B, H)) == 1


@pytest.mark.parametrize("n", [10 ** 6, 10 ** 6 + 1])
def test_multidot_compiles(one_chip, n):
    fn = lambda W, z: multidot(W, z, interpret=False)  # noqa: E731
    assert _compile(one_chip, fn, (n, 7), (n,)) == 1


@pytest.mark.parametrize("n", [10 ** 6, 10 ** 6 + 1])
def test_window_axpy_compiles(one_chip, n):
    fn = lambda V, z, g, gcc: window_axpy(V, z, g, gcc,  # noqa: E731
                                          interpret=False)
    assert _compile(one_chip, fn, (n, 6), (n,), (6,), ()) == 1


@pytest.mark.parametrize("l", [1, 3, 5])
def test_fused_body_streamed_compiles(one_chip, l):
    n = 10 ** 6
    fn = lambda V, Z, s, t: fused_body(V, Z, s, None, t, l=l,  # noqa: E731
                                       interpret=False)
    assert _compile(one_chip, fn, (n, 2 * l + 1), (n, l + 1),
                    (1, N_FIXED_SCALARS + 2 * l), (n,)) == 1


@pytest.mark.parametrize("grid", [1000, 875])
def test_fused_body_in_kernel_stencil_compiles(one_chip, grid):
    """The single-launch body with the SPMV in-kernel, at the paper's
    1000^2 grid and at a width that is not a multiple of 8."""
    l, n = 3, grid * grid
    fn = lambda V, Z, s: fused_body(V, Z, s, l=l,  # noqa: E731
                                    stencil_hw=(grid, grid),
                                    interpret=False)
    assert _compile(one_chip, fn, (n, 2 * l + 1), (n, l + 1),
                    (1, N_FIXED_SCALARS + 2 * l)) == 1
