"""JAX's persistent compilation cache, set up once per entry point.

Call ``enable_compile_cache()`` before the first compile of a process that
serves, benchmarks or smoke-tests on an accelerator: a whole-step program
of a full-width model takes tens of seconds to compile, and the cache lets
the next process on the same checkout load it instead.
"""
from __future__ import annotations

import os
import pathlib

import jax

# src/repro/launch/compile_cache.py -> the checkout root
CHECKOUT = pathlib.Path(__file__).resolve().parents[3]
DEFAULT_DIR = CHECKOUT / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn JAX's persistent compilation cache on; return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    this sets nothing else. Otherwise the cache lives at
    ``<checkout>/.jax_cache``: a fixed path, because the directory is part
    of what a later process must find again (never a temporary name, a
    process id or a time)."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
