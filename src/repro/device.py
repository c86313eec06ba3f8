"""Where the program runs: the accelerator test and the compile cache.

``on_tpu`` is the one platform test behind every backend choice
(Pallas kernel vs jnp, interpret mode or not).  It does not swallow
errors: a backend that fails to initialise fails the caller.

``enable_compile_cache`` points JAX's persistent compilation cache at
``$JAX_COMPILATION_CACHE_DIR`` when that is set, and otherwise at the fixed,
git-ignored ``<checkout>/.jax_cache``.  The path is part of the cache key,
so it never depends on a temporary name, a pid or the time.  Entry points
call it before their first compile.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def on_tpu() -> bool:
    """True when JAX's default backend is a TPU."""
    return jax.default_backend() == "tpu"


def enable_compile_cache() -> str:
    """Point the persistent compilation cache at its directory; returns it.

    The cache's on/off switch (``jax_enable_compilation_cache``, on by
    default) is left as the caller set it."""
    env = os.environ.get(CACHE_ENV)
    if env:
        # JAX reads the variable itself; setting no other directory keeps
        # what this call caches where the next call will look.
        return env
    path = str(DEFAULT_CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
