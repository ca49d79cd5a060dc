"""Where JAX's persistent compilation cache lives.

A cache entry is keyed on, among other things, the directory it sits in,
so the directory must not move between runs: it is either the one named
by ``JAX_COMPILATION_CACHE_DIR`` (JAX reads that variable itself) or the
fixed, gitignored ``.jax_cache`` at the root of this checkout.
"""

from __future__ import annotations

import os
import pathlib

import jax

CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; call before the first
    compile.  Leaves a ``JAX_COMPILATION_CACHE_DIR`` from the environment
    to JAX and sets no other directory; without one, uses ``CACHE_DIR``.
    Returns the directory in use."""
    from_env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if from_env:
        return from_env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
