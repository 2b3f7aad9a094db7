"""JAX's persistent compilation cache for the entry points.

A cold process recompiles every program, and at real widths one training
step takes minutes to compile.  :func:`enable_compile_cache` turns the
persistent cache on before the first compile:

  * ``JAX_COMPILATION_CACHE_DIR`` set: JAX already reads it; nothing else
    is configured.
  * otherwise: ``.jax_cache/`` at the root of the checkout, a fixed path
    (the path is part of the cache key, so a moving directory never hits)
    listed in ``.gitignore``.
"""

from __future__ import annotations

import os
import pathlib

import jax

DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; return its directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
