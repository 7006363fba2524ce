"""JAX's persistent compilation cache for the command-line entry points.

A cold run on a TPU spends a large share of its time compiling, and every
process of a run (the pipeline, the server, ``chip_smoke.py``) compiles the
same programs.  ``enable()`` lets them share one on-disk cache:

  · where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    nothing is set here — the cache can be placed from outside;
  · otherwise the cache lives in ``<checkout>/.jax_cache`` (gitignored).
    The path is fixed, never a temp name, pid or time: it is part of what
    a later run has to find again.

The test suite leaves the cache off (``tests/conftest.py``).
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
