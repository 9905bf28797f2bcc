#!/usr/bin/env python3
"""Record ``cpu_solve.xplane.pb``: a CPU profile of two tiny traced solves.

    JAX_PLATFORMS=cpu python3 bench/testdata/make_cpu_trace.py

The solves run inside ``solve`` host spans, as the benchmark's window
does, so ``tests/bench/test_trace.py`` checks ``bench/trace.py`` on a
recorded trace without a chip.
"""
import pathlib
import shutil
import sys
import tempfile

HERE = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parents[1] / "src"))


def main() -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core import Solver
    from repro.operators import poisson2d
    A = poisson2d(8)
    solver = Solver(A, method="plcg_scan", l=2, tol=1e-5, maxiter=20,
                    spectrum=(0.0, 8.0))
    b = jnp.asarray(np.random.default_rng(0).standard_normal(A.n),
                    jnp.float32)
    jax.block_until_ready(solver.solve(b).x)
    d = tempfile.mkdtemp()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(d, profiler_options=opts)
    for _ in range(2):
        with jax.profiler.TraceAnnotation("solve"):
            jax.block_until_ready(solver.solve(b).x)
    jax.profiler.stop_trace()
    src = next(pathlib.Path(d).rglob("*.xplane.pb"))
    shutil.copy(src, HERE / "cpu_solve.xplane.pb")
    shutil.rmtree(d)


if __name__ == "__main__":
    main()
