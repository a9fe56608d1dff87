"""JAX's persistent compilation cache, kept at one fixed place.

A process that compiles the same steps as an earlier one (a rerun of a
benchmark or of ``chip_smoke.py``) reads them back instead of compiling
again. The cache key includes the directory, so the directory never
moves: ``JAX_COMPILATION_CACHE_DIR`` when it is set (JAX reads that
variable itself, and nothing here overrides it), and otherwise
``<repo>/.jax_cache``, which ``.gitignore`` lists.
"""

from __future__ import annotations

import os
import pathlib

import jax

#: the cache directory when ``JAX_COMPILATION_CACHE_DIR`` is not set.
REPO_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.
    Call before the first compile."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
