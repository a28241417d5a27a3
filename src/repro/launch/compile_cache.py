"""Where JAX keeps its persistent compilation cache.

A launcher calls :func:`enable_compile_cache` once, before its first
compile (never at import). ``JAX_COMPILATION_CACHE_DIR``, when set, wins:
JAX reads it itself and nothing is changed here. Otherwise the cache goes
to one fixed directory inside the checkout. The path is part of what a
cached entry is found by, so it is never a temporary, per-process or
time-stamped directory.
"""
from __future__ import annotations

import os

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = os.path.abspath(
    os.path.join(os.path.dirname(__file__), "..", "..", "..", ".jax_cache")
)


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and return
    that directory."""
    env = os.environ.get(ENV)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
