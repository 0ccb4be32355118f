"""JAX's persistent compilation cache, in one place.

Every entry point (``chip_smoke.py`` and the ``repro.launch`` mains) calls
``enable_compile_cache`` before its first compile.  Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing here
overrides it; otherwise the cache goes to ``<checkout>/.jax_cache``.  The
path is part of the cache key, so it is fixed: never a temporary,
process-id or time-based directory, which would never hit again.
"""
from __future__ import annotations

import os
import pathlib
from typing import Mapping, Optional

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
# src/repro/launch/compile_cache.py -> the checkout root.
CHECKOUT_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def compile_cache_dir(environ: Optional[Mapping[str, str]] = None) -> str:
    """The directory the cache uses: the environment's, else the
    checkout's ``.jax_cache``."""
    environ = os.environ if environ is None else environ
    return environ.get(ENV_VAR) or str(CHECKOUT_CACHE_DIR)


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns its directory."""
    import jax

    path = compile_cache_dir()
    if not os.environ.get(ENV_VAR):
        jax.config.update("jax_compilation_cache_dir", path)
    return path
