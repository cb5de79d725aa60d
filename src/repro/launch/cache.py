"""Where JAX keeps its persistent compilation cache.

The cache key includes the directory, so the directory must not move from
one run to the next: a temp name, a process id or a timestamp never hits.
"""

from __future__ import annotations

import os
from pathlib import Path

# <checkout>/.jax_cache (listed in .gitignore): src/repro/launch/cache.py
# is three levels below the checkout root
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory.

    Where `JAX_COMPILATION_CACHE_DIR` is set, JAX reads it itself and this
    sets nothing; otherwise the cache goes to the fixed `DEFAULT_CACHE_DIR`
    inside the checkout. Call before the first compile."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)
