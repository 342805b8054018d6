"""Where the entry points keep JAX's persistent compilation cache.

A cold process compiles every GA pass and every serving bucket from
scratch; the persistent cache lets the next process on the same machine
load them instead. JAX reads ``JAX_COMPILATION_CACHE_DIR`` itself, so when
that variable is set nothing is set here. Otherwise the cache goes to one
fixed directory inside the checkout, ``<repo>/.jax_cache`` (listed in
``.gitignore``): a path made from a temp name, a pid or the time would
never be found again.
"""
from __future__ import annotations

import os
from pathlib import Path

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory (see the
    module docstring) and return that directory. Call before the first
    compile."""
    env = os.environ.get(CACHE_ENV)
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)
