"""JAX's persistent compilation cache for the repository's entry points.

`enable_compile_cache()` is called first thing by `chip_smoke.py`, the
serve and train launchers and `benchmarks/run.py`.  Where
`JAX_COMPILATION_CACHE_DIR` is set, JAX reads it itself and nothing is set
here.  Otherwise the cache lives at `<checkout>/.jax_cache` (git-ignored):
one fixed path, so a later process finds what an earlier one compiled.
"""
from __future__ import annotations

import os
from pathlib import Path

CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
