"""Where JAX keeps its persistent compilation cache.

Entry points that compile for a chip (``chip_smoke.py``, the launchers,
the benchmarks) call :func:`setup_compile_cache` once, before their first
compile.  The rule:

  * ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself, and nothing
    here overrides it;
  * unset: the cache lives at the fixed path ``<repo>/results/jax_cache``
    (``results/`` is not committed).  The path is part of the cache key,
    so a cache that moves never hits.

The tests never call it.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[2] / "results" / "jax_cache"


def setup_compile_cache() -> str:
    """Place the compilation cache as above; returns its directory."""
    env = os.environ.get(ENV)
    if env:
        return env
    DEFAULT_DIR.mkdir(parents=True, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
