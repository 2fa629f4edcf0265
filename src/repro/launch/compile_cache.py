"""Where JAX keeps its persistent compilation cache.

Programs call ``enable_compile_cache()`` at the start of their ``main``
(never at import): ``chip_smoke.py``, ``repro.launch.serve`` and
``benchmarks/run.py``.
"""
from __future__ import annotations

import os
import pathlib

import jax

#: the checkout's root (this file is src/repro/launch/compile_cache.py)
REPO_ROOT = pathlib.Path(__file__).resolve().parents[3]

#: the cache when ``JAX_COMPILATION_CACHE_DIR`` is not set: a fixed path, so
#: one run's compiled programs are found by the next run in this checkout
DEFAULT_CACHE_DIR = REPO_ROOT / "artifacts" / "jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it, and no
    other directory is set here; otherwise the cache is
    ``DEFAULT_CACHE_DIR``."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(DEFAULT_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
