"""JAX persistent compilation cache for the entry points.

Called from ``main()`` of each entry point, never at import: a library
user keeps control of JAX's configuration.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["CHECKOUT_CACHE", "use_compile_cache"]

# <checkout>/.jax_cache: a fixed path, since the path is part of the key.
CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn on the persistent compile cache and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is used as it is and nothing
    else is set; otherwise the cache lives in ``<checkout>/.jax_cache``.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
