"""JAX persistent compilation cache at a fixed place.

Entry points (``chip_smoke.py``, ``launch/train.py``, the examples) call
``use_compile_cache()`` first thing; importing this module does nothing.
A cold TPU process compiles the whole jitted train step, which takes
minutes; with the cache, later processes on the same machine load it.

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX already reads it; the directory
  is not changed.
* otherwise: the cache goes to ``<checkout>/.jax_cache`` (git-ignored).
  The directory is fixed — never a temp name, pid or timestamp — because
  a cache that moves between runs never hits.

Either way the cache key includes the program's metadata (``op_name``,
source lines). An executable loaded from the cache keeps the metadata of
the program that wrote the entry, and a profile names its device
operations by it: without this, a program whose spans (``repro.obs.span``)
changed but whose computation did not would run, and be profiled as, an
older program's executable.
"""
from __future__ import annotations

import os
from pathlib import Path

CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Enable the persistent compilation cache, keyed on the program's
    metadata too; return its directory."""
    import jax
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
