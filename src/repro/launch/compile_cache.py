"""Where this checkout's entry points keep JAX's persistent compile cache."""
from __future__ import annotations

import os
import pathlib

import jax

CHECKOUT = pathlib.Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is JAX's own setting and wins:
    nothing is set in code.  Otherwise the cache lives in the fixed,
    git-ignored ``<checkout>/.jax_cache``, so every run of every entry
    point finds what the last one stored.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
