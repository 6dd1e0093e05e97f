"""Where JAX's persistent compilation cache lives.

``JAX_COMPILATION_CACHE_DIR``, when set, is the cache and nothing else is
configured. Otherwise the cache is the fixed ``.jax_cache/`` at the root
of this checkout (gitignored): the path is part of what a later run must
find again, so it never depends on a temp dir, a pid or the clock.

Entry points call :func:`enable_compile_cache` before their first
compile; importing this module changes nothing.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and
    return the path."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(CHECKOUT_CACHE)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
