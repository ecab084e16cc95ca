"""JAX's persistent compilation cache, at a path that does not move.

A cold run of the full-width step spends most of a minute compiling. The
cache is keyed by its directory too, so the directory must be the same on
every run: ``JAX_COMPILATION_CACHE_DIR`` when the environment sets it
(JAX reads that variable itself, so nothing is set here), otherwise
``<checkout>/.jax_cache``. Call ``enable_compile_cache`` at the top of an
entry point, before the first compile.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"
# src/repro/utils/compile_cache.py -> the checkout root holding src/
_SRC = Path(__file__).resolve().parents[2]
REPO_CACHE_DIR = _SRC.parent / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent cache at its fixed directory; returns it."""
    if os.environ.get(ENV):
        return jax.config.jax_compilation_cache_dir
    if _SRC.name != "src" or not (_SRC.parent / "pyproject.toml").is_file():
        raise RuntimeError(
            f"repro is not imported from a checkout ({_SRC}), so there is "
            f"no <checkout>/.jax_cache; set {ENV} to place the cache")
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
