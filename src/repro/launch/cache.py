"""JAX's persistent compilation cache, placed once for every entry point.

``JAX_COMPILATION_CACHE_DIR``, when set, wins: JAX reads it itself and this
module sets nothing.  Otherwise the cache lives at one fixed directory
inside the checkout, ``<repo>/.jax_cache`` (git-ignored) — a fixed path,
because the path is part of what makes a later process find the entries.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_ROOT = Path(__file__).resolve().parents[3]


def compile_cache_dir() -> str:
    """Where compiled programs are cached."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(REPO_ROOT / ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent cache on at :func:`compile_cache_dir`; returns it."""
    path = compile_cache_dir()
    if "JAX_COMPILATION_CACHE_DIR" not in os.environ:
        jax.config.update("jax_compilation_cache_dir", path)
    return path
