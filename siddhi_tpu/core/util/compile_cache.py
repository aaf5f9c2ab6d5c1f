"""Where JAX's persistent compilation cache lives.

One rule for every entry point that compiles (``chip_smoke.py``,
``benchmarks/run.py``, ``cluster/worker.py``): where ``JAX_COMPILATION_CACHE_DIR``
is set in the environment, JAX reads it itself and the program sets
nothing; where it is not, the cache goes to ``<checkout>/.jax_cache``
(listed in ``.gitignore``). The path is computed from this file's own
location, so it is the same in every process and every run of one
checkout — the directory is part of the cache key's lookup, and a path
that moves never hits.
"""

from __future__ import annotations

import os
from pathlib import Path

# <checkout>/siddhi_tpu/core/util/compile_cache.py
DEFAULT_CACHE_DIR = str(Path(__file__).resolve().parents[3] / ".jax_cache")


def place_compile_cache() -> str:
    """Point JAX's persistent cache at its place; call before the first
    compile. Returns the directory in force."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    import jax

    jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    return DEFAULT_CACHE_DIR
