"""JAX's persistent compilation cache for the repo's entry points.

`enable_compile_cache()` is called by `chip_smoke.py` and the benchmark
entry points before their first compile — never when the library is
imported, and never by the tests.  Where `JAX_COMPILATION_CACHE_DIR` is
set, JAX reads that directory itself and nothing else is set here.
Otherwise the cache goes to `.jax_cache/` at the root of the checkout:
a fixed path, because the path is part of what a cache entry matches.
"""
from __future__ import annotations

import os

__all__ = ["enable_compile_cache"]

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))


def enable_compile_cache() -> str:
    """Turn the persistent compile cache on; returns its directory."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(_CHECKOUT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path
