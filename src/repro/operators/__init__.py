from .hpcg import hpcg27
from .poisson import poisson2d, poisson3d, poisson2d_dense, poisson_eig_interval
from .spd import random_spd_dense, spd_with_spectrum
from .precond import jacobi, block_jacobi_ssor

__all__ = [
    "hpcg27",
    "poisson2d", "poisson3d", "poisson2d_dense", "poisson_eig_interval",
    "random_spd_dense", "spd_with_spectrum",
    "jacobi", "block_jacobi_ssor",
]
