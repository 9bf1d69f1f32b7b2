"""Process start-up shared by the benchmark's entry points.

Called before anything imports JAX. The compile cache is the program's own
(``repro.launch.compile_cache.enable_compile_cache``), placed at the fixed
``<checkout>/.jax_cache``: the path is part of the cache's key, and two
checkouts must not share one. Every program is cached, however fast it
compiled, so that a warm run finds them all.
"""
from __future__ import annotations

import os
import sys
from pathlib import Path


def start(root: Path) -> str:
    """Place the cache, keep libtpu's logs out of ``/tmp`` and import paths
    in order; returns the cache directory."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(root / ".jax_cache")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    for path in (str(root), str(root / "src")):
        if path not in sys.path:
            sys.path.insert(0, path)
    import jax  # noqa: PLC0415

    from repro.launch.compile_cache import enable_compile_cache  # noqa: PLC0415

    path = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path
