"""A rough solution vector: ``x_hat ~ N(0, 1)`` at every grid point, so
that ``b = A x_hat`` is dominated by the operator's high frequencies."""
import jax


def x_hat(key, grid, dtype):
    return jax.random.normal(key, grid, dtype)
