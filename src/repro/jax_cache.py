"""Where JAX keeps its persistent compilation cache.

A run on a fresh machine compiles every round variant from scratch; the
persistent cache lets later processes on the same checkout skip that. The
cache path is part of the cache's key, so it must never move: it is either
what ``JAX_COMPILATION_CACHE_DIR`` says (JAX reads that variable itself)
or one fixed, git-ignored directory inside the checkout.
"""
from __future__ import annotations

import os
from pathlib import Path

CHECKOUT_CACHE = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory.

    Leaves ``JAX_COMPILATION_CACHE_DIR`` alone when it is set; otherwise
    points JAX at ``<checkout>/.jax_cache``. Call it from an entry point,
    never at import.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
