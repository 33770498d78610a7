"""Persistent XLA compile cache at one fixed path inside the checkout.

A cache whose directory moves between runs never hits, so it is
``.jax_cache/`` at the repository root, never a temporary, per-process or
time-stamped name. Where the environment sets
``JAX_COMPILATION_CACHE_DIR``, JAX reads that itself and this module sets
nothing.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compile cache; returns the directory in use.

    Call before the first compilation of the process."""
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
