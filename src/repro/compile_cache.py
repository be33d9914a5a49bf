"""JAX's persistent compilation cache, at one fixed place per checkout.

Every entry point (``chip_smoke.py``, ``python -m repro.launch.serve``,
``python -m repro.runtime.worker``, the ``benchmarks/`` scripts) calls
:func:`enable_compile_cache` before it compiles, so a second run on the
same machine, or a worker started after its supervisor, finds the
programs the first one compiled.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# <checkout>/.jax_cache (listed in .gitignore).  The cache path is part of
# JAX's cache key, so it must be fixed: never a temp, pid- or time-based
# directory, which would never hit.
CHECKOUT_CACHE = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing else is set here.  Otherwise the cache goes to
    ``<checkout>/.jax_cache``.
    """
    configured = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if configured:
        return configured
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
