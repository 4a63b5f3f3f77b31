"""JAX persistent compilation cache for the repository's entry points.

Entry points (``chip_smoke.py``, the benchmarks) call
:func:`enable_compile_cache` once before their first compile; importing a
module never turns the cache on.
"""

from __future__ import annotations

import os
from pathlib import Path

#: the cache directory when ``JAX_COMPILATION_CACHE_DIR`` is not set: one
#: fixed path inside the checkout, since the path is part of the cache key
DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, names the directory (JAX reads
    it itself, and no other directory is set here); otherwise the cache is
    :data:`DEFAULT_DIR`.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
