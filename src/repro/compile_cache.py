"""JAX's persistent compilation cache, placed from outside the program.

Script entry points (``chip_smoke.py``, the ``main()`` of the benchmark
scripts) call :func:`enable_compile_cache` once, before their first
compile.  Nothing calls it at import time, and no test compiles with it
on.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, the cache uses that directory
and no other.  Otherwise it lives at a fixed path inside the checkout,
``.jax_cache/`` (git ignores it): the path is part of what a cache entry is
found by, so it is never built from a temporary name, a process id or the
time.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: The checkout's own cache directory, used when the environment names none.
DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory.

    Every compile is cached, however quick: a kernel that compiles in under
    JAX's default one-second threshold is still worth not compiling again
    on the next run."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(DEFAULT_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_enable_compilation_cache", True)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
