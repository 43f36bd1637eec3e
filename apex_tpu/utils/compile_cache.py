"""Placement of JAX's persistent compilation cache.

The cache directory is part of what a caller outside the program decides
(a machine may come with ``JAX_COMPILATION_CACHE_DIR`` set so that one
run's compiles are found again by the next), so the rule is:

- ``JAX_COMPILATION_CACHE_DIR`` set: do nothing — JAX reads it, and no
  code here sets another directory;
- unset: ``<checkout>/.jax_cache``, a fixed path (the path is part of
  the cache key: a directory named after a pid, a time or a temp dir
  never hits).

Entry points call :func:`enable_compile_cache` first thing
(``chip_smoke.py``, ``bench.py``'s ``__main__``).  The test suite and the
examples it runs as subprocesses stay cache-free.
"""

from __future__ import annotations

import os

import jax

_ENV = "JAX_COMPILATION_CACHE_DIR"


def enable_compile_cache(checkout: str) -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    placed = os.environ.get(_ENV)
    if placed:
        return placed
    path = os.path.join(os.path.abspath(checkout), ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
