"""Persistent XLA compilation cache, placed from outside the program.

Entry points (``chip_smoke.py``, ``launch/serve.py``, ``benchmarks/run.py``)
call ``enable_compile_cache()`` before their first compile.  Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this sets no
other directory; otherwise the cache lives at ``.jax_cache/`` in the root
of the checkout.  The path is fixed — part of each entry's key — so a run
finds what the previous run from the same checkout compiled.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: the checkout's own cache directory (listed in .gitignore)
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on for every compile, however quick (the
    Pallas kernels compile in well under JAX's default one-second
    threshold).  Returns the directory in use."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
