"""JAX's persistent compilation cache, kept at a fixed path.

Entry points that compile the engine (``chip_smoke.py``,
``repro.launch.serve`` and the JAX benches) call
:func:`enable_compile_cache` before their first compile. Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX already keeps its cache there and
nothing is changed. Otherwise the cache goes to ``.jax_cache`` at the root
of the checkout (git-ignored). The path never varies between runs: it is
part of what lets a later process find the entries again.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns its directory."""
    path = os.environ.get(ENV_VAR)
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
