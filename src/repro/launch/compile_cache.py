"""JAX's persistent compilation cache for the entry points.

A cold run on a TPU spends much of its time compiling (a depth-cut
151,936-vocab model compiles a plain and a KD step). Entry points call
:func:`enable_compile_cache` first thing, so a second process on the
same checkout reads the compiled programs back. It is never called at
package import: library users and the tests keep JAX's defaults.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
# fixed, so the path (part of every cache key's location) never moves
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing else is set here; otherwise the cache goes to
    ``<checkout>/.jax_cache``.
    """
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
