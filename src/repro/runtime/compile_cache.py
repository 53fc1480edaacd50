"""Where JAX's persistent compilation cache lives.

A run on a fresh machine compiles every program from cold; the
persistent cache lets later processes on the same disk skip that.  The
cache directory is part of what a later run must find again, so it is
either the one the environment names or a fixed path inside the
checkout — never one derived from a temporary name, a pid or the time.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["CACHE_ENV", "REPO_CACHE_DIR", "enable_compile_cache"]

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and
    return that directory.

    ``$JAX_COMPILATION_CACHE_DIR`` wins when set (JAX reads it at import;
    it is applied again here for a variable set after import); otherwise
    ``<repo>/.jax_cache``.  Entry points call this before compiling.
    """
    path = os.environ.get(CACHE_ENV) or str(REPO_CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
