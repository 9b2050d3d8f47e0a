"""JAX's persistent compilation cache, placed from outside.

``enable_compile_cache()`` is called from the ``main`` of each entry
point that compiles for the chip (``chip_smoke.py``, ``bench.py``,
``serve/loadgen.py``) and never at import time, so tests and AOT
compiles never touch it.  Where ``JAX_COMPILATION_CACHE_DIR`` is set,
that directory is the cache and no other is set.  Otherwise the cache
is ``<checkout>/.jax_cache/`` (git-ignored): a fixed path, because the
path is part of the cache key and a directory that moves never hits.
"""
from __future__ import annotations

import os

CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DEFAULT_DIR = os.path.join(CHECKOUT, ".jax_cache")


def enable_compile_cache() -> str:
    """Point JAX's persistent cache at its directory; returns the path."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or DEFAULT_DIR
    jax.config.update("jax_compilation_cache_dir", path)
    return path
