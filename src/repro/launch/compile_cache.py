"""JAX's persistent compilation cache, placed from outside the program.

A full-width program takes seconds to tens of seconds to compile, and the
continuous engine compiles one per shape key, so entry points turn the
cache on before their first compile.  Nothing here runs on import.
"""

from __future__ import annotations

import os

import jax

#: checkout-relative default.  The cache directory is part of every entry's
#: lookup, so it must be the same path run after run: never a temp name,
#: a pid or a time stamp
DEFAULT_DIR = os.path.abspath(os.path.join(
    os.path.dirname(__file__), "..", "..", "..", ".jax_cache"))


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is JAX's own setting and is left
    exactly as it is; otherwise the cache lives in ``.jax_cache/`` at the
    root of the checkout."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = DEFAULT_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    return path
