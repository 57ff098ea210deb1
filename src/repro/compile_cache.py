"""JAX's persistent compilation cache, kept at one fixed place.

Entry points (the examples, ``repro.launch.train``, ``benchmarks/run.py``
and ``chip_smoke.py``) call :func:`enable` first thing in ``main()``, so a
second run of the same program loads its compiled executables instead of
compiling again. It is never called at import, and never from tests.
"""
from __future__ import annotations

import os
import pathlib

# The cache key includes the directory, so it never moves: <repo>/.jax_cache.
CACHE_DIR = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def enable() -> str:
    """Turn the persistent cache on; returns the directory in use.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing else is set here.
    """
    import jax
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
