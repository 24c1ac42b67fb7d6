"""Where JAX keeps its persistent compilation cache for this repository.

Entry points that compile for the chip (``chip_smoke.py``,
``benchmarks/run.py``) call :func:`enable_compile_cache` once, before
their first compile.  Importing ``repro`` never touches the cache.

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself, and nothing
  here sets another directory.
* Otherwise the cache lives at ``<checkout>/.jax_cache`` (gitignored).
  The path is part of each entry's key, so it is fixed: never built from
  a temp name, a pid or the time.
"""

from __future__ import annotations

import os
import pathlib

CACHE_DIR = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and
    return that directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
