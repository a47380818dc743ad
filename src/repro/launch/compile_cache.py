"""JAX's persistent compilation cache, placed from outside the program.

The cache key includes the cache directory, so the directory must be the
same on every run: a temporary, per-process or time-stamped path never hits.
Entry points call :func:`enable_compile_cache` from their ``main()``; nothing
calls it at import.
"""

from __future__ import annotations

import os

# <checkout>/src/repro/launch/compile_cache.py -> <checkout>
CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))
DEFAULT_DIR = os.path.join(CHECKOUT, ".jax_cache")


def enable_compile_cache() -> str:
    """Return the directory JAX keeps compiled programs in.

    ``JAX_COMPILATION_CACHE_DIR``, when set, wins: JAX reads it itself and
    this sets nothing.  Otherwise the cache goes to ``<checkout>/.jax_cache``.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
