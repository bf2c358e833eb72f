"""JAX's persistent compilation cache for the program's entry points.

A cold process compiles every jitted program it runs (a 32-layer decode
step, the chunked-prefill step, the Pallas kernels); the persistent
cache lets the next process load them instead.  The cache directory is
part of what makes an entry findable again, so it never holds a temp
name, a pid or a time: ``JAX_COMPILATION_CACHE_DIR`` when the
environment sets it, else the fixed ``<checkout>/.jax_cache``
(gitignored).

Entry points call ``enable_compile_cache()`` from their ``main``;
nothing calls it at import, and tests leave the cache off.
"""
from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def compile_cache_dir() -> str:
    """Where the persistent compilation cache lives."""
    return os.environ.get(ENV_VAR) or str(DEFAULT_DIR)


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at ``compile_cache_dir()``
    and return that directory."""
    import jax

    path = compile_cache_dir()
    jax.config.update("jax_compilation_cache_dir", path)
    return path
