"""HPCG's solution vector, scaled: ``x_hat = s * 1`` with ``s = +-2^k``,
``k`` drawn from the key in [-4, 4] and the sign from the key too.

HPCG solves ``A x = b`` with ``b = A 1``.  A power of two scales every
float32 operation of the solve exactly, so every right-hand side of every
seed makes the solver do bit for bit the same work, up to that scale,
while consecutive solves still see different ``b``."""
import jax
import jax.numpy as jnp


def x_hat(key, grid, dtype):
    k_key, s_key = jax.random.split(key)
    k = jax.random.randint(k_key, (), -4, 5)
    sign = jnp.where(jax.random.bernoulli(s_key), 1.0, -1.0)
    return jnp.full(grid, jnp.ldexp(sign, k), dtype)
