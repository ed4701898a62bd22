"""JAX's persistent compilation cache, kept at one fixed place.

Entry points (``chip_smoke.py``, ``python -m repro.launch.serve``, the
benchmark scripts) call :func:`use_compile_cache` before their first
compile, so repeated runs from one checkout reuse each other's programs.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: ``.jax_cache/`` at the root of the checkout (listed in ``.gitignore``)
CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn on the persistent compilation cache; return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, where set, is left alone (JAX reads it
    itself).  Otherwise the cache goes to :data:`CACHE_DIR`, a fixed path:
    a directory named after a temp file, a pid or the time would never be
    found again by the next run.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
