"""JAX's persistent compilation cache, placed from outside or in the
checkout.

Every entry point that compiles for the chip (``chip_smoke.py``,
``launch/train.py``, ``launch/serve.py``, the benchmarks) calls
``enable_compile_cache()`` before its first compile.  Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it as the cache
directory on its own, and nothing else is set.  Otherwise the cache
lives at one fixed path inside the checkout (``.jax_cache/``, listed in
``.gitignore``): the path is part of what makes a later run find the
entries, so it is never derived from a temp directory, a pid or a time.
"""

from __future__ import annotations

import os
import pathlib

import jax

CHECKOUT_CACHE = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; return its directory."""
    outside = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if outside:
        return outside
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
