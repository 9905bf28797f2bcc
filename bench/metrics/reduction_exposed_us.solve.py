"""Reduction collectives running with no compute op, per useful
iteration, averaged over the mesh's devices (mesh layer)."""
from bench.readers import reduction_exposed_us as read  # noqa: F401
