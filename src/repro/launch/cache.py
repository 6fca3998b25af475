"""JAX's persistent compilation cache, placed from outside or at a fixed path.

The entry points (``launch/train.py``, ``launch/serve.py``,
``benchmarks/run.py``, ``chip_smoke.py``) call :func:`enable_compilation_cache`
before anything compiles.  ``JAX_COMPILATION_CACHE_DIR``, when set, names the
directory and nothing else is set.  Otherwise the cache lives at
``<repo>/.jax_cache``: a fixed path, because the path is part of what a later
run must find again.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compilation_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and return
    that directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(DEFAULT_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
