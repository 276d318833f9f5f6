"""JAX's persistent compilation cache, at one fixed place.

Entry points (``chip_smoke.py``, ``benchmarks/run.py``) call
:func:`enable_compile_cache` from their ``main``; importing the library
never touches the cache.  The sparse-ladder engine compiles one program
per rung, so a cold process can spend much of its time compiling, and a
cache is found again only under the same path: the path is part of the
key, so it is fixed, never a temporary name.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

# <repo>/.jax_cache (listed in .gitignore)
REPO_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads the
    directory from it and no other is set here.  Otherwise the cache goes
    to ``<repo>/.jax_cache``.  Programs are cached however quickly they
    compile: on small graphs the engine's many rung programs each compile
    in well under JAX's default one-second floor.
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(REPO_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path
