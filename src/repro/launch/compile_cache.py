"""Persistent XLA compile cache for the command-line entry points.

``enable_compile_cache`` is called by the ``main`` of every entry point
(``chip_smoke.py``, ``repro.launch.serve`` / ``ingest`` / ``tune``,
``benchmarks/run.py``), never on import, so library users and the tests
keep JAX's own defaults.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing
is changed. Otherwise the cache goes to ``<checkout>/.jax_cache``: a fixed
path, so a second run of the same command finds what the first compiled.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compile cache at its directory and cache every
    program, however quick its compile; returns the directory."""
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
