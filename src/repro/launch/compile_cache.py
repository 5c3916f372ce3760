"""Where JAX's persistent compilation cache lives.

Entry points (``chip_smoke.py``, ``launch.serve``/``launch.train`` mains,
the benchmark mains) call :func:`setup_compile_cache` first; importing a
library module never touches the cache.

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself and this sets
  nothing.
* unset: the cache goes to ``<repo>/.jax_cache`` (git-ignored).  The path
  is fixed — never built from a temporary name, a pid or the time —
  because it is part of the cache key: a directory that moves never hits.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def setup_compile_cache() -> str:
    """Place the persistent compile cache; returns the directory in use."""
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
