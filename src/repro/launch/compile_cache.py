"""Where the launchers keep JAX's persistent compilation cache.

A cache hit needs the same directory every run (the path is part of what
JAX looks up), so the location is fixed: `JAX_COMPILATION_CACHE_DIR` when
the environment sets it (JAX reads that variable itself, and nothing else is
set here), otherwise `.jax_cache/` at the root of this checkout. Never a
temporary name, a process id or a time.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
