"""Where JAX's persistent compilation cache lives.

Each entry point (``chip_smoke.py``, ``repro.launch.serve``,
``repro.launch.train``, ``benchmarks/run.py``) calls
:func:`enable_compile_cache` once, before its first compile.  No library
module calls it, so importing the package leaves JAX's config alone.
"""
from __future__ import annotations

import os
import pathlib

#: fixed default: the path is part of the cache's key, so it must not move
DEFAULT_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is left for JAX to read
    itself; otherwise the cache goes to ``<repo>/.jax_cache``."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)
