"""JAX's persistent compilation cache for the repo's entry points.

``chip_smoke.py`` and ``python -m repro.launch.serve`` call
:func:`enable_compile_cache` before they compile anything, so a second
run on the same machine loads its kernels and jits instead of compiling
them again.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

# <checkout>/.jax_cache (listed in .gitignore).  The path is part of the
# cache key, so it is fixed rather than derived from a temp directory.
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is the only directory used;
    otherwise the cache lives at :data:`DEFAULT_CACHE_DIR`.  Every
    compilation is cached, however quick: the Pallas kernels compile in
    well under JAX's default one-second threshold.
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        DEFAULT_CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
