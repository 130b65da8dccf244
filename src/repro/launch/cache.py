"""Persistent compilation cache for the entry points (``chip_smoke.py``,
``launch/``, ``examples/``): a full-width AlphaFold program takes minutes to
compile, and a warm cache turns the next run's compile into a read."""
from __future__ import annotations

from repro.exec import envcompat


def enable_compilation_cache() -> str:
    """Point JAX's persistent compilation cache at
    ``envcompat.compilation_cache_dir()`` (the environment's
    ``JAX_COMPILATION_CACHE_DIR`` when set) and return the directory."""
    import jax

    path = envcompat.compilation_cache_dir()
    jax.config.update("jax_compilation_cache_dir", path)
    return path
