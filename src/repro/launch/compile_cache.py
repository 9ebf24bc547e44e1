"""Persistent XLA compilation cache for the entry points.

Full-width step programs take tens of seconds each to compile, so every
entry point (``chip_smoke.py``, ``launch/serve.py``, ``launch/train.py``)
keeps its compiled programs in one persistent cache:

* where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
  this module sets no other path;
* otherwise the cache lives at a fixed directory inside the checkout,
  ``<repo>/.jax_cache`` (git-ignored).  The path never depends on a
  temporary name, pid or time: it is part of the cache key, so a moving
  directory would never hit.

Library code and tests never call this — they run without a persistent
cache.
"""
from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
# src/repro/launch/compile_cache.py -> the checkout root
REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def compile_cache_dir() -> str:
    """The cache directory the entry points use (no side effects)."""
    return os.environ.get(ENV_VAR) or str(REPO_CACHE_DIR)


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory."""
    import jax
    path = compile_cache_dir()
    if not os.environ.get(ENV_VAR):
        jax.config.update("jax_compilation_cache_dir", path)
    return path
