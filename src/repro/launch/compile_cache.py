"""Where JAX keeps its persistent compilation cache.

Called from a program's entry point, never at import: importing a
module must not change JAX's configuration for its importer.
"""
from __future__ import annotations

import os

# <checkout>/src/repro/launch/compile_cache.py → <checkout>
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    Where JAX_COMPILATION_CACHE_DIR is set, JAX reads it itself and this
    sets nothing.  Otherwise the cache goes to `<checkout>/.jax_cache`:
    a fixed path, so a later run of the same checkout finds what an
    earlier one compiled (the path is part of the cache key)."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if path:
        return path
    import jax

    path = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
