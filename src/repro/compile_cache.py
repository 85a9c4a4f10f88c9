"""JAX's persistent compilation cache, placed from outside.

Entry points (``chip_smoke.py``, the examples, the benchmarks) call
``enable_compile_cache()`` once at start-up; importing the package never
turns the cache on, and neither do the tests. Where the environment sets
``JAX_COMPILATION_CACHE_DIR``, JAX already reads it and this sets no other
directory. Otherwise the cache lives at one fixed path inside the checkout
(``<repo>/.jax_cache``): the directory is part of every entry's key, so a
path that moved between runs would never hit.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory in use. The
    compile-time floor drops to zero so the many small kernel and
    shape-class compiles are cached too."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(CHECKOUT_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
