"""JAX's persistent compilation cache for the launchers and the chip smoke.

A 32-layer serving step takes tens of seconds to compile, and a fresh
process starts with nothing compiled.  The persistent cache keeps compiled
programs on disk, keyed by program, device and cache path — so the path
must be fixed: a directory named after a pid, a time or a temporary name
never hits.

Call :func:`enable` from a program's ``main`` (never at import: a library
import must not change JAX's configuration).
"""
from __future__ import annotations

import os
import pathlib

#: the in-checkout default (git-ignored), used when the environment names
#: no cache directory
CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable() -> str:
    """Turn the persistent cache on; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing is set here.  Otherwise the cache goes to :data:`CACHE_DIR`."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
