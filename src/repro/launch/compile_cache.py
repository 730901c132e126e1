"""Where compiled programs persist between runs.

JAX reads ``JAX_COMPILATION_CACHE_DIR`` itself; where it is unset, the cache goes
to ``.jax_cache`` at the root of the checkout. The path is fixed because it is
part of the cache key: a directory that moves never hits.
"""

from __future__ import annotations

import os
import pathlib

import jax

CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> None:
    """Place JAX's persistent compilation cache; call before the first compile."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
