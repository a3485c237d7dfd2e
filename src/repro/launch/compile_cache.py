"""JAX's persistent compilation cache, kept at one fixed place.

A run on an accelerator compiles the cohort step and its helpers before
it does any work; the persistent cache lets the next process skip that,
provided it looks in the same directory.  So :func:`enable_compile_cache`
never builds the path from a temp name, a pid or the time: it uses
``JAX_COMPILATION_CACHE_DIR``
when it is set (JAX reads that variable itself) and otherwise the fixed,
git-ignored ``<checkout>/.jax_cache``.

Entry points call it (``chip_smoke.py``, ``benchmarks/run.py``, the
examples); tests and library imports never do.
"""
from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory."""
    import jax

    path = os.environ.get(ENV_VAR)
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_enable_compilation_cache", True)
    return path
