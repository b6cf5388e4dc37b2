"""The persistent XLA compilation cache, in one place.

Entry points (`chip_smoke.py`, `launch/train.py`) call
`enable_compile_cache()` from their `main`; importing this module
changes nothing.  When `JAX_COMPILATION_CACHE_DIR` is set, JAX already
reads it and this helper sets no other directory.  Otherwise the cache
lives at the fixed `<checkout>/.jax_cache` (listed in `.gitignore`):
the directory is part of the cache key, so a temp, pid or time path
would never hit.
"""
from __future__ import annotations

import os
from pathlib import Path

ENV = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT = Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    import jax
    path = os.environ.get(ENV)
    if not path:
        path = str(CHECKOUT / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path
