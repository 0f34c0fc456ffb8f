"""JAX's persistent compilation cache, placed where a run can find it again.

The launchers and ``chip_smoke.py`` call :func:`enable_compile_cache` before
their first compile; the tests never do.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

# A fixed path in the checkout: the cache directory is part of what a later
# run must find, so it is never made from a temp name, a pid or the time.
REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and no
    directory is set here.  Otherwise the cache goes to ``<checkout>/
    .jax_cache`` (git-ignored).
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
