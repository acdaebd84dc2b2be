"""One place for JAX's persistent compilation cache.

Every entry point (``chip_smoke.py``, ``benchmarks/run.py``, the examples,
and each ``launch="processes"`` worker) calls :func:`use_compile_cache`
before its first compile, so all processes of one checkout share a cache:

* ``JAX_COMPILATION_CACHE_DIR`` set: nothing is configured here — JAX
  reads that variable itself, and it is the only directory used;
* otherwise the cache lives at ``<checkout>/.jax_cache`` (gitignored). The
  path is fixed on purpose: it is part of the cache key, so a directory
  named after a pid, a time or a temp dir would never hit again.
"""

from __future__ import annotations

import os

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"


def cache_dir() -> str:
    """The directory this checkout's processes compile into, resolved
    without importing JAX (the launcher hands it to its children)."""
    env = os.environ.get(CACHE_ENV)
    if env:
        return env
    checkout = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    return os.path.join(checkout, ".jax_cache")


def use_compile_cache() -> str:
    """Turn the persistent cache on at :func:`cache_dir` and return it."""
    d = cache_dir()
    if not os.environ.get(CACHE_ENV):
        import jax

        jax.config.update("jax_compilation_cache_dir", d)
    return d

