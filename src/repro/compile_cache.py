"""Where the program keeps JAX's persistent compilation cache.

Entry points call :func:`enable_compile_cache` before their first JAX
use.  ``JAX_COMPILATION_CACHE_DIR``, when set (even to ``""``, which
disables the cache), is left to JAX, which reads it itself; otherwise the
cache lives in ``<checkout>/.jax_cache``.  That fixed path matters because
the path is part of what the cache is keyed on: a directory that moves
never hits.  The fallback is also exported to the environment, so worker
processes (``serve/transport.py``) share it.
"""
from __future__ import annotations

import os
from pathlib import Path

ENV = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and
    return it (``""`` when the environment disabled it)."""
    if ENV in os.environ:
        return os.environ[ENV]
    path = str(CHECKOUT_CACHE)
    os.environ[ENV] = path
    import jax
    jax.config.update("jax_compilation_cache_dir", path)
    return path
