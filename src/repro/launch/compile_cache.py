"""JAX's persistent compilation cache, placed before the first compile.

Entry points (``chip_smoke.py``, ``python -m repro.launch.serve``, the
benchmark scripts) call ``enable_compile_cache()`` before they compile
anything, so a second run of the same checkout reads its programs back
instead of compiling them again.

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself, and nothing
  here changes it.
* Otherwise the cache lives in ``<checkout>/.jax_cache``, a fixed path
  (the path is part of the cache key, so it never holds a temp name, a
  PID or a time) that ``.gitignore`` lists.
"""

from __future__ import annotations

import os
import pathlib

import jax

DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
