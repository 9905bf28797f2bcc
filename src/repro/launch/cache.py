"""JAX's persistent compilation cache, placed from outside the program.

The entry points (``repro.launch.solve``, ``repro.launch.train``,
``benchmarks/run.py`` and ``chip_smoke.py``) call
:func:`enable_compile_cache` once, before their first compile; importing
the library never turns the cache on.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, that directory is the cache
and no other is set.  Otherwise the cache lives at the fixed path
``<repo>/.jax_cache`` (listed in ``.gitignore``): the directory is part
of what a later run must find again, so it is never derived from a
temporary name, a process id or the time.
"""
from __future__ import annotations

import os
import pathlib

#: ``<repo>/.jax_cache`` of the checkout this module belongs to
#: (src/repro/launch/cache.py)
DEFAULT_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its
    directory."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        DEFAULT_CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_enable_compilation_cache", True)
    return path
