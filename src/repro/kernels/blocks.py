"""Row-block sizing shared by the Pallas kernels.

The TPU compiler takes a block whose second-minor dimension is a multiple
of 8 (f32 sublanes) or the whole array extent.  Every kernel here tiles
one row axis of extent ``n`` with :func:`row_block` and runs
``pl.cdiv(n, block)`` grid steps: when the block does not divide ``n`` the
last step is partial, its out-of-range rows read unspecified values and
its out-of-range writes are dropped, so a kernel that reduces across rows
masks them by :func:`block_rows` ``< n``.  Any ``n`` therefore compiles, with
no halving loop that could end at a 1-row block.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

SUBLANES = 8


def row_block(n: int, target: int) -> int:
    """Rows per block: ``n`` itself when it fits in ``target``, else the
    largest multiple of 8 not above ``target`` (at least 8)."""
    if n <= target:
        return n
    return max(target // SUBLANES, 1) * SUBLANES


def block_rows(i, block: int):
    """Global row index of each row of grid step ``i``, as a
    ``(block, 1)`` column."""
    return i * block + jax.lax.broadcasted_iota(jnp.int32, (block, 1), 0)
