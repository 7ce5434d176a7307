"""JAX's persistent compilation cache for this repo's scripts.

Called from a script's ``main()`` (``chip_smoke.py``, the benchmarks, the
examples), never at library import.  A machine that sets
``JAX_COMPILATION_CACHE_DIR`` keeps its cache there and JAX reads the
variable itself.  Otherwise the cache lives at ``<checkout>/.jax_cache``:
a fixed path, because the path is part of the cache key, so a directory
that moved between runs would never hit.
"""

from __future__ import annotations

import os
import pathlib

import jax

CHECKOUT = pathlib.Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
