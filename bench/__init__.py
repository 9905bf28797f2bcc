"""The on-chip benchmark of the p(l)-CG solver (see PERF.md)."""
