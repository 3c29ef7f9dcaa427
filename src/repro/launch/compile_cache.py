"""Persistent XLA compilation cache shared by the repo's entry points.

``chip_smoke.py``, ``python -m repro.launch.serve`` and
``python -m benchmarks.run`` call :func:`enable_compile_cache` before they
compile anything, so a second run on the same machine reads its programs
back instead of recompiling them.

Where the cache lives:

* ``JAX_COMPILATION_CACHE_DIR``, when it is set.  JAX reads the variable
  itself; this module sets no other directory then.
* ``<checkout>/.jax_cache`` otherwise — a fixed path (listed in
  ``.gitignore``), so every run from one checkout finds the same cache.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory."""
    path = os.environ.get(ENV_VAR)
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    # keep every program, not only those that took over a second to build:
    # a cold call on the chip compiles dozens of small scatter/gather programs
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
