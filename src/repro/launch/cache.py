"""The persistent compilation cache every driver uses.

JAX keys a cached program partly by the cache's path, so the directory must
not move between runs: where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads
it itself and nothing here overrides it; otherwise the cache lives at the
fixed ``<checkout>/.jax_cache`` (ignored by git).
"""

from __future__ import annotations

import os

import jax

__all__ = ["CHECKOUT_CACHE_DIR", "use_compile_cache"]

#: ``<checkout>/.jax_cache`` — this file is ``<checkout>/src/repro/launch/cache.py``
CHECKOUT_CACHE_DIR = os.path.normpath(
    os.path.join(os.path.dirname(os.path.abspath(__file__)), "..", "..", "..", ".jax_cache")
)


def use_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", CHECKOUT_CACHE_DIR)
    return CHECKOUT_CACHE_DIR
