"""JAX persistent compilation cache for the command-line entry points.

Every per-box program is bucketed to a power-of-two shape, so a run
compiles a few dozen programs; the persistent cache lets the next process
on the same machine reuse them. The cache key includes the directory, so
the directory is fixed: ``JAX_COMPILATION_CACHE_DIR`` when it is set
(JAX reads that variable itself and nothing else is set in code),
otherwise ``<checkout>/.jax_cache`` (listed in ``.gitignore``).
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on for every program, however quick to
    compile; returns the directory it writes to."""
    path = os.environ.get(ENV_VAR) or str(DEFAULT_DIR)
    if not os.environ.get(ENV_VAR):
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_enable_compilation_cache", True)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path
