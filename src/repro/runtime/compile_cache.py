"""JAX's persistent compilation cache, at one fixed place per checkout.

Entry points call :func:`enable_compile_cache` once, before their first
compile; importing this module changes nothing.  Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and that directory
is used as is.  Otherwise, on an accelerator, the cache goes to
``<checkout>/.jax_cache``: the directory is part of every entry's key, so it
must not move between runs (no temp name, pid or time in it).  The CPU
backend gets no cache from here: its compiles are quick, and this jaxlib
warns about the host's features on every CPU entry it reads back.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str | None:
    """Turn the persistent cache on; returns the directory it uses (None:
    no cache).

    Every program is cached, however quick its compile: JAX's default skips
    those under a second, and on a TPU the many sub-second programs of a
    serving start-up (the runner ladder, eager cache updates) add up."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not env and jax.default_backend() == "cpu":
        return None
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
