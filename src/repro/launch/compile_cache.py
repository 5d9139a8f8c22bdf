"""JAX's persistent compilation cache for programs run from a checkout."""
from __future__ import annotations

import os

import jax

# Kernels of a second or two are worth caching; JAX's default floor is 1 s.
MIN_COMPILE_SECS = 0.1


def enable_compile_cache(checkout: str) -> str:
    """Turn on the persistent compilation cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already keeps its cache
    there and this sets no other.  Otherwise the cache lives at the fixed
    ``<checkout>/.jax_cache``, never a per-run temporary directory, whose
    entries the next run could not find.  Call it at the
    start of a program, before the first compile — never on import.
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(os.path.abspath(checkout), ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", MIN_COMPILE_SECS)
    return path
