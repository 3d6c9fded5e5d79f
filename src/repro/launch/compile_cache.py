"""JAX's persistent compilation cache, in one fixed place.

Every entry point (``launch/train.py``, ``launch/serve.py``,
``chip_smoke.py``) calls ``enable_compile_cache()`` before its first
compile, so a second run of the same program on the same backend reads its
executables back instead of compiling them again.

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads the variable itself and the
  cache lives there; nothing else is set in code.
* unset: ``<repo>/.jax_cache`` (git-ignored).  The path is part of the cache
  key, so it is fixed, never temporary or per-process.
"""

from __future__ import annotations

import os
import pathlib

import jax

__all__ = ["CHECKOUT_CACHE_DIR", "enable_compile_cache"]

CHECKOUT_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory it writes to."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
