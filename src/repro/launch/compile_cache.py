"""Where JAX keeps its persistent compilation cache.

Entry points call ``use_compile_cache()`` once, before they compile;
importing this module changes nothing.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# fixed (never a temp name, pid or time), so entries are found again by the
# next run from the same checkout
REPO_CACHE_DIR = str(Path(__file__).resolve().parents[3] / ".jax_cache")


def use_compile_cache() -> str:
    """Keep compiled programs where ``JAX_COMPILATION_CACHE_DIR`` says (JAX
    reads that variable itself), else in ``<repo>/.jax_cache``.  Returns the
    directory in use.

    Every program is kept, not only those that took over a second to
    compile: a serving run compiles dozens of sub-second programs, which
    together cost as much as its few large ones."""
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", REPO_CACHE_DIR)
    return REPO_CACHE_DIR
