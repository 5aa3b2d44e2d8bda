"""Where JAX keeps its persistent compilation cache for this checkout.

Every entry point (``chip_smoke.py``, ``repro.launch.train``,
``repro.launch.serve``, ``benchmarks/run.py``) calls
:func:`use_compile_cache` before its first compile, so a second run on the
same machine reuses the compiled programs of the first.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# <checkout>/.jax_cache — a fixed path (listed in .gitignore): a cache
# directory that moves between runs never hits
CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    this sets no other directory.  Otherwise the cache lives in
    ``<checkout>/.jax_cache/``.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
