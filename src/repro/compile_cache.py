"""Placement of JAX's persistent compilation cache, for the entry points.

Entry points (``chip_smoke.py``, ``benchmarks/run.py``,
``examples/quickstart.py``) call :func:`enable` once before their first
compile; library modules never do, so importing :mod:`repro` changes no
global JAX setting.

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself, and nothing is
  set here — the caller placed the cache.
* otherwise the cache goes to ``<checkout>/.jax_cache``: a fixed path,
  because the path is part of what a later run must find again (no
  temporary name, process id or time in it).
"""

from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable() -> str:
    """Turn the persistent cache on; returns the directory it uses."""
    placed = os.environ.get(ENV_VAR)
    if placed:
        return placed
    import jax

    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
