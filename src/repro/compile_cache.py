"""JAX persistent compilation cache, kept at one fixed place.

A compiled program is found again only under the same cache directory, so
the directory must not move between runs: it is either the one the
``JAX_COMPILATION_CACHE_DIR`` environment variable names (JAX reads that
itself, and nothing here overrides it) or ``<checkout>/.jax_cache``.

Entry points (``chip_smoke.py``, ``repro.launch.serve``, the benchmark
harness) call :func:`enable_compile_cache` once at start-up; importing this
module sets nothing.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
# <checkout>/src/repro/compile_cache.py -> <checkout>/.jax_cache
DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory."""
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
