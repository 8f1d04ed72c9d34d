"""JAX's persistent compilation cache for the command-line entry points.

Every ``main()`` that users run (``launch/sim.py``, ``launch/sim_serve.py``,
``benchmarks/run.py``, ``chip_smoke.py``) calls :func:`use_compile_cache`
first. Nothing calls it at import, so library callers and the test suite
stay uncached.

The cache is placed from outside: when ``JAX_COMPILATION_CACHE_DIR`` is set,
JAX reads it itself and nothing is set here. Otherwise the cache lives at
the fixed ``<checkout>/.jax_cache/``; the path is part of each entry's key,
so it never carries a process id, a temp name or a timestamp.
"""
from __future__ import annotations

import os

import jax

CHECKOUT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
)


def use_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and
    return that directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if path:
        return path
    path = os.path.join(CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
