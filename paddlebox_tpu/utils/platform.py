"""Platform helpers: where compiled programs are cached.

`JAX_PLATFORMS` alone selects the backend; nothing here overrides it.
"""

from __future__ import annotations

import os

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

#: the in-checkout persistent compile cache (git-ignored). A FIXED path:
#: the directory is part of the cache key, so one that moves never hits.
DEFAULT_COMPILE_CACHE = os.path.join(_CHECKOUT, ".jax_cache")


def ensure_compile_cache() -> str:
    """Point jax's persistent compilation cache somewhere, once.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set the caller placed the
    cache from outside (jax reads the variable itself) and nothing is
    set here; a directory the caller already gave ``jax.config`` is left
    alone too. Only an unset ``jax_compilation_cache_dir`` becomes
    ``<checkout>/.jax_cache``. Idempotent and cheap: every
    ``instrument_jit`` construction calls it, so any process that jits
    through the package caches its compiles. Returns the directory in
    effect."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    if not jax.config.jax_compilation_cache_dir:
        jax.config.update("jax_compilation_cache_dir", DEFAULT_COMPILE_CACHE)
    return jax.config.jax_compilation_cache_dir
