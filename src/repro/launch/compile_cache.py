"""Persistent XLA compilation cache for the command-line entry points.

Call :func:`enable_compile_cache` once at the top of a ``main()`` — never
at import time and never from tests: the cache is process-wide state, and
a test that compiles for a described (unattached) TPU would write entries
that no later run can read back.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already keeps its cache
there and nothing is changed. Otherwise the cache lives at a fixed path
inside the checkout (``<repo>/.jax_cache``, git-ignored): the directory is
part of the cache key, so a path that moved between runs would never hit.
"""
from __future__ import annotations

import os
from pathlib import Path

DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
