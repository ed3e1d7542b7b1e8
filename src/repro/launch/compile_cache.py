"""Persistent compilation cache for the command-line entry points.

A chip run compiles every step from scratch unless the compiled programs
survive between processes. JAX keys its cache on the directory too, so the
directory must not move between runs: `JAX_COMPILATION_CACHE_DIR` when the
environment sets it (JAX reads that variable itself, and nothing here
overrides it), otherwise `.jax_cache/` at the root of the checkout. Entry
points call `enable_compile_cache()` before their first compile; importing
this module changes nothing.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: the fixed in-checkout directory (listed in .gitignore)
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
