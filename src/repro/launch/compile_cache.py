"""Persistent XLA compilation cache for the entry points.

Every entry point (``chip_smoke.py``, ``repro.launch.train``,
``benchmarks.run``) calls :func:`enable_compile_cache` at the start of
``main``, before JAX touches a device. Where ``JAX_COMPILATION_CACHE_DIR``
is set, JAX has already read it and the cache stays there. Otherwise
the cache goes to ``<repo>/.jax_cache`` (gitignored): a fixed path, so
that one run finds what an earlier run in the same checkout compiled.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: the checkout's own cache directory (src/repro/launch -> repo root)
REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and
    return that directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
