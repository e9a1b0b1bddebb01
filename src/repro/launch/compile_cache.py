"""Placement of JAX's persistent compilation cache for the entry points.

The cache key includes the directory, so the directory must not move from
run to run: it is either the one ``JAX_COMPILATION_CACHE_DIR`` names (JAX
reads that variable itself) or the fixed ``<repo>/.jax_cache``.  Call
``setup_compile_cache()`` at the start of a ``main()``, never at import.
"""
from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def setup_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already uses it and
    nothing else is set here.  Otherwise the cache goes to the repository's
    ``.jax_cache``.  Besides speeding up a rerun, the cache lets two engines
    of one process that trace the same program share one compile.
    """
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
