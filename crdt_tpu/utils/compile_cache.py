"""JAX's persistent compilation cache, placed in one way for every entry
point (tests, ``benchmark/run.py``, the static checks).

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and no
other path is set here. Otherwise the cache lives at ``<checkout>/.jax_cache``:
a fixed path, because the path is part of the cache key, so a directory
named after a pid, a temporary name or the time never hits.
"""

from __future__ import annotations

import os

CHECKOUT_CACHE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache",
)


def enable_compile_cache() -> str:
    """Turn the persistent cache on and return its directory. The env
    vars are exported too, so worker processes share the same cache.
    Programs that compile in 0.2 s or more are cached (the test suite is
    hundreds of such programs)."""
    import jax

    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0.2")
    jax.config.update(
        "jax_persistent_cache_min_compile_time_secs",
        float(os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"]),
    )
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = CHECKOUT_CACHE
        os.environ["JAX_COMPILATION_CACHE_DIR"] = path
        jax.config.update("jax_compilation_cache_dir", path)
    return path
