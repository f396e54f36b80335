"""Persistent XLA compilation cache for the command-line entry points.

Without it every process compiles its programs from cold; with it, a
second process of the same checkout loads them from disk (qwen2-0.5b
engine warm-up on one TPU v5e: 8.1 s cold, 2.3 s warm). Entry points call
:func:`enable_compile_cache` from ``main()`` — never at import — so tests
and library callers keep JAX's defaults.
"""
from __future__ import annotations

import os

#: fixed in-checkout directory (listed in .gitignore). The path is part of
#: what a later run looks up, so it never comes from a temp name, a pid or
#: the clock.
CACHE_DIR = os.path.abspath(
    os.path.join(os.path.dirname(__file__), "..", "..", "..", ".jax_cache"))


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already caches there
    and nothing else is set. Otherwise the cache lives at
    :data:`CACHE_DIR`."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    return CACHE_DIR
