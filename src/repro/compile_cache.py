"""Where JAX keeps compiled programs between processes.

One rule for every entry point (``chip_smoke.py``, the launch drivers,
the benchmark runner): when ``JAX_COMPILATION_CACHE_DIR`` is set, JAX
reads it itself and nothing is set here; otherwise the cache lives at a
fixed directory inside the checkout. The path is part of each entry's
key, so it is never built from a temporary name, a pid or the time.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["enable_compile_cache", "DEFAULT_DIR"]

#: ``<checkout>/.jax_cache`` (listed in ``.gitignore``).
DEFAULT_DIR = str(Path(__file__).resolve().parents[2] / ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
