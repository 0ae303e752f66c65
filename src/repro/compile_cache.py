"""Placement of JAX's persistent compilation cache for entry points.

Entry points (``chip_smoke.py``, ``repro.launch.serve``,
``benchmarks.run``) call :func:`enable_compile_cache` once, before their
first compile; importing this module sets nothing.  The cache key
includes the directory, so the default is one fixed path inside the
checkout — never a temporary, per-process or per-run one.
"""
from __future__ import annotations

import os

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
        __file__)))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent cache on and return its directory.

    When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX has already read it
    and this sets no directory of its own; otherwise the cache goes to
    ``<checkout>/.jax_cache`` (listed in ``.gitignore``)."""
    path = os.environ.get(ENV_VAR)
    if not path:
        path = DEFAULT_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    return path
