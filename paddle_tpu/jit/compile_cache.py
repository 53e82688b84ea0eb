"""Where JAX's persistent compilation cache lives for this checkout.

The directory is part of every cache key, so it must not move between
runs: either the environment names it (``JAX_COMPILATION_CACHE_DIR``, which
JAX reads by itself) or it is ``<checkout>/.jax_cache``, derived from this
file's own location. Entry points (``chip_smoke.py``, ``bench.py``) call
:func:`enable_compile_cache` before their first compile.
"""

from __future__ import annotations

import os

import jax

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def enable_compile_cache() -> str:
    """Returns the directory compiled programs are kept in."""
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    path = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
