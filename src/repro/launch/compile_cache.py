"""Where JAX keeps its persistent compilation cache.

Entry points (``chip_smoke.py``, ``benchmarks/run.py``) call
``use_compile_cache`` once before their first compile; nothing calls it at
import.  ``JAX_COMPILATION_CACHE_DIR``, when set, is JAX's own setting and
wins untouched.  Otherwise the cache goes to ``<checkout>/.jax_cache``: a
fixed path, so a later run from the same checkout finds what an earlier
one compiled (the path is part of the cache key; a temp or per-process
name would never hit).
"""

from __future__ import annotations

import os

CACHE_DIRNAME = ".jax_cache"


def use_compile_cache(checkout: str) -> str:
    """Turn on JAX's persistent compilation cache; return its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    path = os.path.join(os.path.abspath(checkout), CACHE_DIRNAME)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
