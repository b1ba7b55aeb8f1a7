"""JAX persistent compilation cache setup for entry points.

Compiling the full-width perception step and its parameter init takes tens
of seconds; a persistent cache lets the next process on the same machine
load them instead.  JAX keys cache entries by directory, so the directory
is fixed: ``JAX_COMPILATION_CACHE_DIR`` when the environment sets it (JAX
reads that itself), else ``<repo>/.jax_cache``.

Entry points (``chip_smoke.py``, ``benchmarks/run.py``) call
:func:`enable_compile_cache` before their first compilation.  Nothing
calls it on import, and tests never call it.
"""

from __future__ import annotations

import os

#: environment variable JAX reads for its cache directory
CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"

#: fallback cache directory: fixed, at the repository root
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; return its directory."""
    env = os.environ.get(CACHE_ENV)
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    return DEFAULT_CACHE_DIR


__all__ = ["CACHE_ENV", "DEFAULT_CACHE_DIR", "enable_compile_cache"]
