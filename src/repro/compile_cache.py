"""Persistent XLA compilation cache for the entry points.

Entry points (``chip_smoke.py``, the ``repro.service.cli`` mains, the
``benchmarks`` and ``examples`` scripts) call :func:`enable_compile_cache`
first thing; importing ``repro`` never touches the cache, so the tests run
without one unless the caller's environment names one.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and this
sets nothing. Otherwise the cache lives at one fixed path in the checkout,
``<repo>/.jax_cache`` (git-ignored): the path is part of the cache key, so a
moving directory would never hit.
"""
from __future__ import annotations

import os

import jax

__all__ = ["CACHE_DIR", "enable_compile_cache"]

#: the checkout's own cache directory (``src/repro/`` is two levels down)
CACHE_DIR = os.path.abspath(os.path.join(os.path.dirname(__file__),
                                         os.pardir, os.pardir, ".jax_cache"))


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    return CACHE_DIR
