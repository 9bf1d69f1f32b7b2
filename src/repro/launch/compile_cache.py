"""JAX's persistent compilation cache, placed from outside the program.

Entry points (``launch.train``, ``launch.serve``, ``benchmarks.run``,
``chip_smoke.py``) call :func:`enable_compile_cache` first thing; nothing
calls it at import time, so test processes stay uncached. Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and no other path
is set here. Otherwise the cache lives at the fixed ``<checkout>/.jax_cache``:
the directory is part of the cache key, so a path built from a temp name, a
pid or the time would never hit. ``<checkout>`` is found from this file's
place in ``src/repro/launch``, so the default assumes the package runs from
a source tree or an editable install; a non-editable install should set
``JAX_COMPILATION_CACHE_DIR``.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: The cache directory used when ``JAX_COMPILATION_CACHE_DIR`` is unset.
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on before the first compile; returns its
    directory. Idempotent."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
