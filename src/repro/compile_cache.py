"""Placement of JAX's persistent compilation cache.

Entry points (``chip_smoke.py``, the examples, the benchmarks) call
:func:`enable_compile_cache` once before they compile anything; library
code and imports never do.  The cache directory is part of every entry's
key, so it must not move between runs: ``JAX_COMPILATION_CACHE_DIR`` when
it is set (JAX reads that variable itself), otherwise the fixed
``<checkout>/.jax_cache``.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

#: the checkout-local default (listed in .gitignore)
DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and
    return that directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(DEFAULT_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
