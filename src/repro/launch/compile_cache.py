"""Where JAX keeps its persistent compilation cache.

Entry points call :func:`use_compile_cache` from ``main()``, never at
import.  The cache key includes the directory, so the directory must not
move between runs: it is either the one ``JAX_COMPILATION_CACHE_DIR``
names (JAX reads that variable itself, so nothing is set in code) or the
fixed ``<repo>/.jax_cache``.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: the repository's own cache directory (listed in .gitignore)
REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Place the persistent compilation cache; return the directory in use."""
    from_env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if from_env:
        return from_env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
