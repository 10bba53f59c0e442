"""Where JAX keeps its persistent compilation cache.

Entry scripts (``chip_smoke.py``, ``benchmarks/run.py``) call
:func:`enable` once, before their first compile; importing ``repro``
never touches the cache.  Where ``JAX_COMPILATION_CACHE_DIR`` is set,
JAX already reads it and nothing else is set here.  Otherwise the cache
goes to the fixed path ``<checkout>/.jax_cache`` (listed in
``.gitignore``): the directory is part of each entry's key, so a path
that moved between runs would never hit.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: the checkout root (this file is <checkout>/src/repro/compile_cache.py)
CHECKOUT = Path(__file__).resolve().parents[2]


def enable() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = CHECKOUT / ".jax_cache"
    jax.config.update("jax_compilation_cache_dir", str(path))
    return str(path)
