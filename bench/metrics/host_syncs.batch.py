"""Blocking device-to-host reads per right-hand side, from the program's
own ``syncs`` counter (front end layer)."""
from bench.program_spans import host_syncs as read  # noqa: F401
