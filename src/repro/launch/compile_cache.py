"""Persistent XLA compilation cache for the command-line entry points.

A cold process on a TPU spends much of a short run compiling.  JAX keeps
compiled programs on disk when it has a cache directory, and the
directory's path is part of each entry's key, so the directory must not
move between runs:

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself and this module
  sets nothing (the deployment chooses the place);
* otherwise: ``<checkout>/.jax_cache``, a fixed path listed in
  ``.gitignore``.

Call :func:`enable_compile_cache` at the start of an entry point, never
at import time: tests import these modules and must not write a cache.
"""

from __future__ import annotations

import os
from pathlib import Path

CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)


__all__ = ["CHECKOUT_CACHE", "enable_compile_cache"]
