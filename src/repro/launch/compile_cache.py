"""JAX persistent compilation cache at a fixed place.

Entry points (``chip_smoke.py``, ``launch/train.py``, the examples) call
``use_compile_cache()`` first thing; importing this module does nothing.
A cold TPU process compiles the whole jitted train step, which takes
minutes; with the cache, later processes on the same machine load it.

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX already reads it; nothing is
  changed.
* otherwise: the cache goes to ``<checkout>/.jax_cache`` (git-ignored).
  The directory is fixed — never a temp name, pid or timestamp — because
  a cache that moves between runs never hits.
"""
from __future__ import annotations

import os
from pathlib import Path

CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Enable the persistent compilation cache; return its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
