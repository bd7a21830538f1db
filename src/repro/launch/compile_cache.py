"""JAX persistent compilation cache for the entry points.

``JAX_COMPILATION_CACHE_DIR``, when set, is JAX's own setting and nothing
else is configured here.  Otherwise the cache lives at one fixed directory
inside the checkout (``.jax_compile_cache/``, gitignored), so the next run
from the same checkout finds what this one compiled.
"""
from __future__ import annotations

import os
import pathlib

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE = (pathlib.Path(__file__).resolve().parents[3]
                  / ".jax_compile_cache")


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory; call
    before the first compilation.  Returns the directory in use."""
    if os.environ.get(ENV_VAR):
        return os.environ[ENV_VAR]
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
