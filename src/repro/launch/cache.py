"""JAX's persistent compilation cache for the launchers.

Entry points (``chip_smoke.py``, ``serve.py``, ``daemon.py``) call
``enable_compile_cache`` once at start-up, before anything compiles.
Library code and tests never do: a test run keeps JAX's defaults.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

# <checkout>/.jax_cache: this file is <checkout>/src/repro/launch/cache.py.
# A fixed path, because the path is part of the cache's key.
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    no path is set here.  Otherwise the cache lives in
    ``<checkout>/.jax_cache``.  Every program is cached, not only those
    over JAX's default one second of compile time: the service compiles
    a few hundred small programs, and a restart should find them all.
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
