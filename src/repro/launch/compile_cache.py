"""Where XLA's persistent compilation cache lives for the launchers.

A 32-layer model compiles for minutes; the cache lets a second process on
the same checkout skip that.  Its directory is part of every entry's key,
so it must not move between runs: ``JAX_COMPILATION_CACHE_DIR`` when the
environment sets it (JAX reads the variable itself — nothing is set in
code), else one fixed directory inside the checkout (``.jax_cache/``,
ignored by git).
"""
from __future__ import annotations

import os

import jax

__all__ = ["CHECKOUT_CACHE_DIR", "enable_compile_cache"]

CHECKOUT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    jax.config.update("jax_compilation_cache_dir", CHECKOUT_CACHE_DIR)
    return CHECKOUT_CACHE_DIR
