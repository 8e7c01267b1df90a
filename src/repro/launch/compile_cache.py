"""JAX's persistent compilation cache, kept at one fixed place.

A program that compiles the Full-HD render pays a minute or more per
executable; the persistent cache lets the next process load it instead.
JAX keys cache entries partly by the directory's path, so the directory
must not move between runs: no temporary, pid- or time-derived names.

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()       # before the first compile
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
# <checkout>/.jax_cache — this file is <checkout>/src/repro/launch/.
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory:
    `JAX_COMPILATION_CACHE_DIR` where it is set, else `CHECKOUT_CACHE_DIR`
    (inside the checkout, and ignored by git)."""
    path = os.environ.get(ENV_VAR) or str(CHECKOUT_CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
