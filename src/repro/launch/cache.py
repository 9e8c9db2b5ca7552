"""Where a checkout keeps what it builds at run time.

JAX's persistent compilation cache and the fused-hop autotune table both
live under the git-ignored ``<checkout>/.cache`` unless the environment
places them elsewhere, so a run reads and writes nothing outside its
checkout.  The path is fixed (never built from a temporary name, a pid
or the time): JAX keys its cache on the directory, so a cache that moves
never hits.
"""
from __future__ import annotations

import os
from pathlib import Path

CHECKOUT = Path(__file__).resolve().parents[3]
CACHE_DIR = CHECKOUT / ".cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache for an entry point and
    return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is read by JAX itself and
    wins; otherwise the cache goes to ``<checkout>/.cache/jax``.  Kernel
    compiles take well under a second, so the minimum compile time worth
    caching drops to zero.  Call once, before the first compile; tests
    never call it.
    """
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR / "jax"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return jax.config.jax_compilation_cache_dir
