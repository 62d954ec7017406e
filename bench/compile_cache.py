"""JAX's persistent compilation cache for benchmark runs.

Copied from the program's ``launch/compile_cache.py``. The cache key
includes the directory, so the directory is fixed:
``JAX_COMPILATION_CACHE_DIR`` when it is set (JAX reads that variable
itself), otherwise ``<checkout>/.jax_cache``. Only the first run of a
cell in a checkout compiles; later runs load every program from here.
"""

from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[1] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on for every program, however quick to
    compile; returns the directory it writes to."""
    import jax

    path = os.environ.get(ENV_VAR) or str(DEFAULT_DIR)
    if not os.environ.get(ENV_VAR):
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_enable_compilation_cache", True)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path
