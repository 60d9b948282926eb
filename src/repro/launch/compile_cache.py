"""JAX's persistent compilation cache, kept at one fixed place.

A process that compiles the serving programs at published corpus sizes
spends minutes in the TPU compiler; the persistent cache lets the next
process on the same machine skip that.  The directory is part of what
makes a cache entry findable again, so it never moves: JAX's own
``JAX_COMPILATION_CACHE_DIR`` when the environment sets it (nothing is
set in code then), else ``.jax_cache/`` at the root of the checkout.
"""

from __future__ import annotations

import os
import pathlib

import jax

CHECKOUT_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its fixed directory
    (see module docstring) and return that directory.  Call before the
    first compile."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
