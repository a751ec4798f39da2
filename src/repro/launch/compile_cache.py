"""JAX's persistent compilation cache for the launchers and chip_smoke.py.

The cache key includes the directory, so it lives at one fixed path: the
directory ``JAX_COMPILATION_CACHE_DIR`` names (JAX reads that variable
itself), or else ``<checkout>/.jax_cache``.
"""

from __future__ import annotations

import os
import pathlib

CHECKOUT = pathlib.Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Point JAX's compilation cache at its fixed directory; returns it."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
