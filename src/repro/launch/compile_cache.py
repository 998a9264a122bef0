"""JAX's persistent compilation cache for the repo's entry points.

``enable_compile_cache()`` runs before an entry point's first compile
(``chip_smoke.py``, ``launch/serve.py``, ``launch/train.py``), so a second
process on the same machine loads its programs instead of compiling them.
The cache path is part of what makes an entry reusable, so it is fixed:
``JAX_COMPILATION_CACHE_DIR`` when the environment sets it (JAX reads it
itself and nothing is set here), otherwise ``.jax_cache/`` at the root of
the checkout.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["CHECKOUT_CACHE_DIR", "enable_compile_cache"]

# src/repro/launch/compile_cache.py -> the checkout root
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
