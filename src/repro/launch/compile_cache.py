"""Where JAX keeps its persistent compilation cache.

Entry points call ``use_compile_cache()`` once, before their first
compile; library modules never do, so importing them changes no JAX
setting. When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
nothing here overrides it. Otherwise the cache lives in ``.jax_cache/`` at
the repository root: a fixed path, because the path is part of each entry's
key and a cache that moves never hits.
"""
from __future__ import annotations

import os
from pathlib import Path

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory; returns
    that directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
