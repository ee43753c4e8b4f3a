"""Placement of JAX's persistent compilation cache for every entry point.

``chip_smoke.py``, ``python -m repro.serve`` and ``benchmarks/run.py`` call
:func:`configure_compile_cache` before they compile anything. When
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this module
sets no other directory. Otherwise the cache goes to ``.jax_cache`` at the
root of the checkout: a fixed path, because a later run only finds an entry
again under the same directory.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def configure_compile_cache() -> Path:
    """Point JAX's persistent compilation cache at its directory and
    return that directory."""
    env = os.environ.get(ENV_VAR)
    if env:
        return Path(env)
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return DEFAULT_DIR
