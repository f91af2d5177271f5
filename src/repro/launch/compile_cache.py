"""JAX's persistent compile cache, placed from outside the program.

A cold process recompiles every phase-1 bucket, every frontier
``while_loop`` and every merge-cover width; the persistent cache lets the
next process on the same machine skip that. Where the cache lives is the
deployment's choice: ``JAX_COMPILATION_CACHE_DIR`` wins (JAX reads it
itself, so nothing is set here), and otherwise the cache sits at one fixed
directory inside the checkout. The next process must find the entries
again, so the path is never derived from a temporary name, a pid or the
time.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compile cache on; returns its directory."""
    placed = os.environ.get(ENV_VAR)
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
