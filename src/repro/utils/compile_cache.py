"""JAX's persistent compilation cache, placed from outside the program.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads that directory
itself and nothing here names another. Otherwise the cache lives at one
fixed path inside the checkout, ``<repo>/.jax_cache/`` (git ignores it):
the path is part of what the cache is found by, so it never depends on
a temp directory, a process id or the time."""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: The checkout's own cache directory, used when the environment names none.
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn JAX's persistent compilation cache on; return its directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(CHECKOUT_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_enable_compilation_cache", True)
    return path
