"""JAX's persistent compilation cache, configured once for every entry point.

``serve.py``, ``train.py``, ``python -m repro.engine.autotune`` and
``chip_smoke.py`` call :func:`enable_compile_cache` before they compile
anything, so a second run of any of them reuses the executables the first
one wrote.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing
is overridden.  Otherwise the cache lives at a fixed ``<repo>/.jax_cache``:
the directory is part of what a later run must find again, so it is never a
temporary, per-process or time-stamped path.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

#: Cache directory used when ``JAX_COMPILATION_CACHE_DIR`` is unset.
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)
