"""JAX's persistent compilation cache, placed before the first compile.

Entry points (``launch/train.py``, ``launch/serve.py``, ``chip_smoke.py``)
call :func:`enable` first thing. ``JAX_COMPILATION_CACHE_DIR``, when set,
is read by JAX itself and stands. Otherwise the cache lives at the fixed
``<repo>/.jax_cache`` (gitignored): the directory is part of every entry's
key, so it is never derived from a temp name, a pid or the time.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
