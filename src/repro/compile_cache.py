"""Where JAX's persistent compilation cache lives: one rule for every entry point.

A secure round program takes tens of seconds to compile; the persistent
cache lets a later process reuse it. The cache directory is part of what a
later process must find again, so it is a fixed path and never a temp name.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
REPO_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; return its directory.

    `$JAX_COMPILATION_CACHE_DIR`, when set, is JAX's own setting and is left
    alone. Otherwise the cache goes to `.jax_cache/` at the repository root.
    Called by scripts and benchmarks at start-up, never on import.
    """
    path = os.environ.get(CACHE_ENV)
    if path:
        return path
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
