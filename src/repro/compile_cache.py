"""JAX's persistent compilation cache, kept at one fixed place per checkout.

The cache key includes the cache directory, so a directory that moves
between runs (a temporary, a process id, a timestamp) never hits.
"""
from __future__ import annotations

import os
from pathlib import Path

#: the checkout root (``src/repro/compile_cache.py`` -> ``../../..``)
CHECKOUT = Path(__file__).resolve().parents[2]


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; -> its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is JAX's own setting: it is
    used as it is and nothing else is set here.  Otherwise the cache goes
    to ``<checkout>/.jax_cache``.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
