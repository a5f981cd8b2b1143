"""JAX's persistent compilation cache, set up in one place.

Entry points that compile for a chip (``chip_smoke.py``,
``repro.launch.serve``, ``benchmarks.run``) call ``setup_compile_cache``
first, so repeated runs from the same checkout reuse compiled programs.
"""
from __future__ import annotations

import os

import jax

# fixed in-checkout path, so every run from this checkout finds what
# earlier runs compiled
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def setup_compile_cache() -> str:
    """Return the compile-cache directory in use.  ``JAX_COMPILATION_CACHE_DIR``
    wins when set (JAX reads it itself; nothing is set in code);
    otherwise the cache goes to ``DEFAULT_DIR`` (``.jax_cache/`` at the
    repository root)."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
