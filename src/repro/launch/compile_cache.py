"""JAX's persistent compilation cache, in one fixed place.

``JAX_COMPILATION_CACHE_DIR`` wins when it is set; otherwise the cache
lives at ``<repo>/.jax_cache`` (gitignored).  The path is part of the
cache key, so it never depends on a temporary name, a process id or
the time: a second run of the same program finds what the first one
compiled.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

#: the checkout's root (src/repro/launch/ is three levels below it)
REPO_ROOT = Path(__file__).resolve().parents[3]
DEFAULT_DIR = REPO_ROOT / ".jax_cache"


def cache_dir() -> str:
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(DEFAULT_DIR)


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at :func:`cache_dir`
    and return that directory."""
    path = cache_dir()
    jax.config.update("jax_compilation_cache_dir", path)
    return path
