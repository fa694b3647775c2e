"""JAX's persistent compilation cache, shared by every entry point.

A cold call on the chip compiles every program it runs; the full-width
serving step alone takes seconds.  The cache keeps the compiled programs on
disk, and its key includes the directory, so the directory must not move
between runs: where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it
itself and nothing is set here; otherwise the cache lives at a fixed
``.jax_cache/`` in the checkout root (git-ignored).  CPU runs (tests, the
Pallas interpreter) compile in seconds and are left uncached.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str | None:
    """Turn the persistent cache on before the first compile; returns the
    directory in use (None: no cache on a CPU backend)."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    if jax.default_backend() == "cpu":
        return None
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
