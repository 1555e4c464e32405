"""JAX persistent compilation cache: one fixed place per checkout.

Every entry point (``python -m repro.sph``, the engine worker,
``chip_smoke.py``, ``benchmarks/run.py``) calls :func:`enable` before
its first compile, so a restarted worker or a second run loads its
programs instead of compiling them again. The directory is part of the
cache's key, so it never moves:

  * ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself and this
    module chooses nothing;
  * otherwise: ``<checkout>/.jax_cache`` (listed in ``.gitignore``),
    exported to the environment so child processes (engine workers)
    inherit the same directory.
"""
from __future__ import annotations

import os

ENV = "JAX_COMPILATION_CACHE_DIR"

# src/repro/runtime/compile_cache.py -> the checkout root
CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))
DEFAULT_DIR = os.path.join(CHECKOUT, ".jax_cache")


def cache_dir(environ=None) -> str:
    """The directory the cache lives in under ``environ``."""
    environ = os.environ if environ is None else environ
    return environ.get(ENV) or DEFAULT_DIR


def enable() -> str:
    """Turn the persistent cache on (idempotent); returns its directory.

    Initializes no JAX backend: safe in the serving frontend.
    """
    path = cache_dir()
    if not os.environ.get(ENV):
        import jax

        os.environ[ENV] = path
        jax.config.update("jax_compilation_cache_dir", path)
    return path
