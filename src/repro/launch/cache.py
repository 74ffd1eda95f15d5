"""Persistent XLA compile cache shared by every entry point.

``JAX_COMPILATION_CACHE_DIR``, when set, is where JAX keeps its cache
(JAX reads the variable itself; nothing here overrides it).  Otherwise
the cache lives at one fixed path inside the checkout, ``.jax_cache/``
(git-ignored): the path is part of the cache key, so it must not move
between runs.
"""
from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn the persistent compile cache on; returns its directory."""
    path = os.environ.get(ENV_VAR)
    if path:
        return path
    import jax

    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
