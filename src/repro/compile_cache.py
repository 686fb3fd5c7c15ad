"""Where JAX keeps its persistent compilation cache.

Every entry point that compiles for the chip calls :func:`use_compile_cache`
before its first compile.  A cached program is found again only under the
same directory, so the directory is fixed: the one the environment names
in ``JAX_COMPILATION_CACHE_DIR`` (which JAX reads itself, so nothing is set
here), otherwise ``.jax_cache`` at the root of this repository.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["REPO_CACHE_DIR", "use_compile_cache"]

REPO_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def use_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its fixed directory;
    returns that directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
