"""Where JAX keeps its persistent compilation cache.

A compiled program is found again only under the same cache path, so the
path is fixed: ``$JAX_COMPILATION_CACHE_DIR`` when it is set (JAX reads the
variable itself, and nothing here overrides it), otherwise one directory
the caller names inside its checkout.
"""
from __future__ import annotations

import os

import jax

__all__ = ["enable_compile_cache"]


def enable_compile_cache(default_dir: str | os.PathLike) -> str:
    """Turn on the persistent compilation cache; return its directory.

    ``default_dir`` is used only when ``JAX_COMPILATION_CACHE_DIR`` is unset.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.fspath(default_dir)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
