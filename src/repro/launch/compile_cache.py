"""JAX's persistent compilation cache for the repo's entry points.

A chip run starts with no compiled code, and the main path compiles for
tens of seconds.  Every entry point (``chip_smoke.py``, the
``repro.launch`` mains, ``benchmarks/run.py``) calls
``enable_compile_cache`` once, before it compiles anything, so processes
that share a checkout share compiled programs.  The test suite never
calls it."""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT = Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Turn the cache on and return its directory.  Where
    ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
    sets nothing.  Otherwise the cache lives in ``<checkout>/.jax_cache``
    (gitignored): a fixed path, since a directory that moves between runs
    never hits."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
