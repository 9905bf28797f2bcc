"""Front-end milliseconds per right-hand side: the solver's root spans
less the time their ``plcg.wait`` children cover (front end layer)."""
from bench.program_spans import front_end_ms as read  # noqa: F401
