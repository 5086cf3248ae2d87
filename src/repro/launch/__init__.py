"""Command-line entry points: ``python -m repro.launch.cluster`` (batch
clustering) and ``python -m repro.launch.serve`` (the streaming server),
plus the compile-cache placement every program entry point shares."""
from __future__ import annotations

import os
from pathlib import Path

#: The persistent compilation cache's home when the environment names
#: none: fixed inside the checkout (the path is part of the cache key, so
#: it never depends on a temporary name, a process id or the time).
CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Place JAX's persistent compilation cache; returns its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is the only cache: JAX reads
    it itself and nothing is changed here. Otherwise the cache goes to
    :data:`CACHE_DIR`. Call once, before the first compile.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
