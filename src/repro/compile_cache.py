"""Placement of JAX's persistent compilation cache for the entry points.

Command-line mains call :func:`place_compile_cache` before their first
compile; importing this module changes nothing, so tests that import the
program never write a cache.
"""
from __future__ import annotations

import os
import pathlib

#: the checkout this module was loaded from (``<checkout>/src/repro/``).
CHECKOUT = pathlib.Path(__file__).resolve().parents[2]


def place_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is left to JAX.  Otherwise the
    cache lives in ``<checkout>/.jax_cache``: a fixed path, since the path
    is part of what a later run must find again.  Every program is cached,
    however fast it compiled, because the device tier compiles many small
    impls.
    """
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
