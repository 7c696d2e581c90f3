"""Where JAX's persistent compilation cache lives.

The entry points (``launch/train.py``, ``launch/serve.py``, ``chip_smoke.py``)
call :func:`enable_compile_cache` before their first compile.  A cache
directory set from outside through ``JAX_COMPILATION_CACHE_DIR`` is left to
JAX, which reads that variable itself; otherwise the cache goes to the fixed
``<checkout>/.jax_cache``.  The path is part of what a later process must
find again, so it is never derived from a temporary name, a pid or the time.
"""
from __future__ import annotations

import os

import jax

#: the checkout root (``src/repro/launch/`` is three levels below it)
CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))
DEFAULT_DIR = os.path.join(CHECKOUT, ".jax_cache")


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and return
    that directory."""
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return jax.config.jax_compilation_cache_dir
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
