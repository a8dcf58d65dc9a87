"""JAX's persistent compilation cache for the programs that run on a chip.

``enable_compile_cache()`` is called by the entry points that compile for a
device (``chip_smoke.py``, ``benchmarks/run.py``, ``launch/serve.py``,
``launch/train.py``); the tests never turn it on. Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and nothing else
is set. Otherwise the cache sits at one fixed directory inside the checkout
(``.jax_cache/``, git-ignored), so a later run of the same checkout finds
what an earlier one compiled.
"""
from __future__ import annotations

import os
import pathlib

import jax

#: The cache directory when ``JAX_COMPILATION_CACHE_DIR`` is unset.
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
