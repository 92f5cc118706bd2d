"""Where JAX keeps its persistent compilation cache.

Every entry point (``chip_smoke.py``, ``repro.launch.train``,
``repro.launch.serve``, ``benchmarks/run.py``) calls
:func:`use_compile_cache` once before it compiles.  The cache key includes
the directory, so the directory is fixed: ``$JAX_COMPILATION_CACHE_DIR``
when the environment sets it (JAX reads that variable itself, and nothing
here overrides it), else ``<checkout>/.jax_cache`` (git-ignored).
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# src/repro/launch/cache.py -> the checkout root
CHECKOUT = Path(__file__).resolve().parents[3]


def use_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its one directory and
    return that directory.  Idempotent; a directory already chosen by the
    environment or by an earlier call is left as it is."""
    chosen = os.environ.get("JAX_COMPILATION_CACHE_DIR") \
        or jax.config.jax_compilation_cache_dir
    if chosen:
        return chosen
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
