"""Where JAX's persistent compilation cache lives, for every entry point.

A compiled program is cached under a key that includes the cache path,
so the directory must not move between runs: either the caller places
it with ``JAX_COMPILATION_CACHE_DIR`` (JAX reads that variable itself),
or it is the one fixed directory ``DEFAULT_DIR`` inside the checkout,
which git ignores. Used by ``chip_smoke.py`` and ``benchmarks/run.py``.
"""
from __future__ import annotations

import os
import pathlib

ENV = "JAX_COMPILATION_CACHE_DIR"

#: the in-checkout cache directory (listed in .gitignore)
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def enable() -> str:
    """Place the compilation cache and return its directory. With
    ``JAX_COMPILATION_CACHE_DIR`` set nothing is changed in code;
    otherwise ``jax_compilation_cache_dir`` is set to ``DEFAULT_DIR``.
    Call before the first compile."""
    placed = os.environ.get(ENV)
    if placed:
        return placed
    import jax
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
