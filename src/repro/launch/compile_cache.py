"""Where JAX keeps its persistent compilation cache for this repo's entry
points (``chip_smoke.py`` and the model examples)."""
from __future__ import annotations

import os
import pathlib

import jax

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"

#: Fixed cache directory inside the checkout (listed in ``.gitignore``).
#: The directory is part of the cache key, so it never carries a temporary
#: name, a process id or a time.
DEFAULT_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and this
    sets nothing; otherwise the cache goes to :data:`DEFAULT_CACHE_DIR`."""
    env = os.environ.get(CACHE_ENV)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)
