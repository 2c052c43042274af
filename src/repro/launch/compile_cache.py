"""Where JAX's persistent compilation cache lives.

A cache entry is found again only under the same directory, so the
directory is fixed: ``JAX_COMPILATION_CACHE_DIR`` when the environment
sets it (JAX reads that variable itself), otherwise ``.jax_cache`` at the
root of this checkout.
"""
from __future__ import annotations

import os

import jax

# src/repro/launch/ -> the checkout root
CHECKOUT_CACHE_DIR = os.path.normpath(os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "..", "..", "..",
    ".jax_cache"))


def enable_compile_cache() -> str:
    """Point the persistent compilation cache at its directory (call
    before the first compile) and return that directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", CHECKOUT_CACHE_DIR)
    return CHECKOUT_CACHE_DIR
