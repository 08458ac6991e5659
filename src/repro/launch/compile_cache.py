"""JAX's persistent compilation cache, at one fixed place.

The cache key includes the directory, so a directory that moves between
runs (a tempdir, a pid, a timestamp) never hits.  Entry points call
:func:`enable_compile_cache` before their first compile.
"""

from __future__ import annotations

import os
from pathlib import Path

#: ``<checkout>/.jax_cache`` — src/repro/launch/ is three levels below.
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory in use.

    ``$JAX_COMPILATION_CACHE_DIR``, when set, is JAX's own setting and is
    left alone; otherwise the cache goes to :data:`DEFAULT_CACHE_DIR`.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)
