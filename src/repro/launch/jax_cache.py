"""Where JAX keeps its persistent compilation cache.

Called once at the start of each entry point (``fl_run``, ``serve_fl``,
``chip_smoke.py``), never at import.  The cache key includes the
directory, so the path is fixed: no temp name, pid or time in it.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_persistent_cache() -> None:
    """With ``JAX_COMPILATION_CACHE_DIR`` set, JAX already caches there and
    nothing is set here; otherwise cache in ``.jax_cache/`` at the repo
    root."""
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
