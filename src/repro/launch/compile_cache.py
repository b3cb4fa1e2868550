"""Where JAX keeps its persistent compilation cache.

``place_compile_cache`` is called from the ``main`` of each entry point
(``chip_smoke.py``, ``launch/serve.py``, ``benchmarks/run.py``), never at
import.  When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
nothing is set here.  Otherwise the cache goes to ``<checkout>/.jax_cache``:
a fixed path, because a later run finds its entries only under the same
directory.
"""
from __future__ import annotations

import os
import pathlib

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"
#: <checkout>/.jax_cache (this file is <checkout>/src/repro/launch/...)
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def place_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory; returns
    the directory in use."""
    env = os.environ.get(ENV)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
