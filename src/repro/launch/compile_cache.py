"""JAX's persistent compilation cache for the entry points that run on the
chip (``repro.launch.train`` and ``chip_smoke.py``).

Compiling a full-width training step takes tens of seconds, and every
fresh process pays it again unless the compiled program is cached on
disk. Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
nothing here overrides it. Otherwise the cache lives at the fixed
``.jax_cache/`` directory of the checkout: the path is part of what a
later run must find again, so it is never derived from a temporary
name, a process id or the time.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    configured = os.environ.get(CACHE_ENV)
    if configured:
        return configured
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)
