"""JAX's persistent compilation cache for the command-line entry points
(``launch/train.py``, ``launch/serve.py``, ``chip_smoke.py``).

Called from ``main()``s only — importing a library module never turns
the cache on.
"""

from __future__ import annotations

import os
import pathlib

import jax

# fixed: the cache directory is part of every entry's key, so a path
# built from a temp name, a pid or the time would never hit
CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory.
    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already uses it and
    nothing is set here; otherwise the cache lives in ``.jax_cache/`` at
    the repository root."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
