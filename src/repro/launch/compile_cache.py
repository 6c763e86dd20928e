"""JAX's persistent compilation cache for the entry points.

Where `JAX_COMPILATION_CACHE_DIR` is set, JAX reads it itself and this
module sets nothing. Otherwise the cache sits at a fixed path inside the
checkout (`<repo>/.jax_cache`, git-ignored), so later processes on the
same checkout find the programs compiled by earlier ones.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable() -> None:
    """Point JAX's compilation cache at `<repo>/.jax_cache` unless the
    environment names a directory. Call before the first compile."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
