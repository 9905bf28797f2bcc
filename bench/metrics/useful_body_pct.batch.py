"""Scan bodies up to each lane's last committed update, as a share of
the bodies the engine ran, from the program's own counters (engine and
kernels layer)."""
from bench.program_spans import useful_body_pct as read  # noqa: F401
