"""JAX persistent compilation cache at a placeable, stable path.

A later run finds an entry only if it looks in the same directory, so the
directory must not move between runs: it is ``$JAX_COMPILATION_CACHE_DIR``
when that is set, and otherwise ``<checkout>/.jax_cache`` (git-ignored) —
never a temp name.
The entry points (``launch/train.py``, ``launch/serve.py``,
``chip_smoke.py``) turn it on; library code and tests never do.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

# src/repro/utils/compile_cache.py -> the checkout root.
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def compile_cache_dir() -> str:
    """Where compiled programs are cached: the env var, else the checkout."""
    return os.environ.get(ENV_VAR) or str(CHECKOUT_CACHE_DIR)


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at ``compile_cache_dir()``."""
    path = compile_cache_dir()
    jax.config.update("jax_compilation_cache_dir", path)
    return path
