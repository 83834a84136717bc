"""Where JAX's persistent compilation cache lives.

Entry points (``launch/serve.py``, ``benchmarks/run.py``, ``chip_smoke.py``)
call :func:`configure_compile_cache` before they compile anything, so a
second run of the same program on the same machine reuses the compiled
executables.  Tests never call it.
"""
from __future__ import annotations

import os

import jax

REPO_ROOT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
)
CACHE_DIR = os.path.join(REPO_ROOT, ".jax_cache")


def configure_compile_cache() -> str:
    """Turn the persistent cache on; returns its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is left to JAX, which reads it
    itself.  Otherwise the cache goes to the fixed ``<repo>/.jax_cache``:
    the directory is part of each entry's key, so it must not move between
    runs.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    return CACHE_DIR
