"""JAX's persistent compilation cache for the repo's entry points.

Compiling is a large part of a cold run: the engine's scan, the service's
warmup of every power-of-two batch shape, the kernels. Entry points
(``chip_smoke.py``, ``benchmarks/run.py``) call :func:`enable_compile_cache`
before their first compile; the library and the tests never turn it on.
"""

from __future__ import annotations

import os

import jax

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))
DEFAULT_CACHE_DIR = os.path.join(_CHECKOUT, ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    nothing is overridden. Otherwise the cache goes to ``.jax_cache`` at
    the root of the checkout (git-ignored): a fixed path, so that a later
    run from the same checkout finds what an earlier one compiled.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    return DEFAULT_CACHE_DIR
