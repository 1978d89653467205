"""JAX's persistent compilation cache, kept at one fixed place.

A cold run on the chip compiles every session program; the persistent
cache lets later processes on the same machine load them instead.  The
cache directory is part of each entry's key, so it must not move between
runs: where ``JAX_COMPILATION_CACHE_DIR`` is set JAX uses it and nothing
else is set here; otherwise the cache lives at ``<checkout>/.jax_cache``
(gitignored), never at a path derived from a temp name, a pid or the time.

Entry points call :func:`enable_compile_cache` once before their first
compile; importing this module changes nothing.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env  # JAX reads the variable itself
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
