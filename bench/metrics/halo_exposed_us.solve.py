"""Halo collective-permutes running with no compute op, per useful
iteration, averaged over the mesh's devices (mesh layer)."""
from bench.readers import halo_exposed_us as read  # noqa: F401
