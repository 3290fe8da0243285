"""Where compiled programs persist between runs of an entry point.

Call :func:`enable_compile_cache` from an entry point's ``main()``, never at
import.  Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
the location stays with it; otherwise the cache lives at the fixed
``<checkout>/.jax_cache`` (gitignored).  The path is fixed on purpose: a
directory that moves between runs never hits.
"""
from __future__ import annotations

import os
from pathlib import Path

CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory."""
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return jax.config.jax_compilation_cache_dir
