"""Persistent XLA compilation cache for the entry points.

A cold start of a full-width serving step spends most of its set-up
time compiling. JAX's persistent cache keeps the compiled executables
on disk between processes, and the cache directory is part of what a
later process must find again: a path made from a temporary name, a
pid or the clock never hits. So the directory is either the one the
environment names or one fixed path inside the checkout.
"""

from __future__ import annotations

import os
import pathlib

import jax

# <checkout>/.jax_cache (git-ignored): this file is
# <checkout>/src/repro/launch/compile_cache.py
CHECKOUT_CACHE_DIR = str(
    pathlib.Path(__file__).resolve().parents[3] / ".jax_cache")


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; return its directory.

    With ``JAX_COMPILATION_CACHE_DIR`` set, JAX already reads that
    directory from the environment and nothing else is set here.
    Otherwise the cache goes to ``CHECKOUT_CACHE_DIR``. Call once, at
    the top of an entry point, before anything compiles.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", CHECKOUT_CACHE_DIR)
    return CHECKOUT_CACHE_DIR
