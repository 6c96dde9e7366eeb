"""Where JAX's persistent compilation cache lives for this checkout.

One rule, shared by every entry point that compiles for a chip
(``chip_smoke.py``, ``examples/_setup.py``): where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing is
set in code; where it is not, the cache goes to ``<checkout>/.jax_cache``
— a fixed path (the path is part of the cache key, so a directory that
moves never hits).  Call before the first compile.  The test suite does
not turn it on.
"""

from __future__ import annotations

import os
from pathlib import Path

__all__ = ["enable_compile_cache"]


def enable_compile_cache() -> str:
    """Place the persistent compile cache; returns the directory used."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    path = str(Path(__file__).resolve().parents[2] / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
