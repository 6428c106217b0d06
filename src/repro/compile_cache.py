"""JAX's persistent compilation cache for this repository's entry points.

Scripts (``chip_smoke.py``, ``examples/``, ``benchmarks/``) call
:func:`enable` before they compile anything; library code never does, so
importing ``repro`` changes no JAX setting.
"""
from __future__ import annotations

import os

#: fixed, so a compile cached by one run is found by the next: the
#: directory is part of the cache key, and it is listed in .gitignore
CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
        __file__)))), ".jax_cache")


def enable() -> str:
    """Turn the persistent cache on and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is left to JAX.  Otherwise
    the cache goes to ``<checkout>/.jax_cache``; the variable is exported
    too, so spawned rank processes share the directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if path:
        return path
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    import jax
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    return CACHE_DIR
