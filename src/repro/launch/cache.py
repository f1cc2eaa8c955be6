"""JAX's persistent compilation cache, kept at one fixed place.

Entry points (``chip_smoke.py``, ``benchmarks``, ``repro.launch``) call
:func:`enable_compile_cache` once, before their first compile. Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and nothing is set
in code; otherwise the cache lives in ``<checkout>/.jax_cache`` (git-ignored).
The path is part of every cache key, so it never depends on a temporary
directory, a process id or the time.
"""
from __future__ import annotations

import os
from pathlib import Path

ENV = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    path = os.environ.get(ENV)
    if path:
        return path
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
