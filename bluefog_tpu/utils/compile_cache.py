"""Where XLA's persistent compile cache lives — decided once, here.

The cache directory is part of the cache key's lookup path, so it must not
move between runs: a directory built from ``tempfile``, a pid or the clock
never hits.  Two cases, no third:

- ``JAX_COMPILATION_CACHE_DIR`` is set — the deployment placed the cache.
  JAX reads that variable itself; this code sets nothing.
- it is not set — the cache is ``<checkout>/.jax_cache`` (git-ignored).

Every entry point that compiles (``chip_smoke.py``, the ``examples/``
trainers, ``bfrun-tpu``) calls :func:`configure_compile_cache`
before its first compile.
"""

from __future__ import annotations

import os

__all__ = ["configure_compile_cache"]

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def configure_compile_cache() -> str:
    """Point JAX at the persistent compile cache; returns the directory."""
    from bluefog_tpu.tracing import startup

    # every entry point calls this just before its first device query: the
    # start's record brackets the runtime's start-up from here
    startup.RECORD.look_at_backend()
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    import jax

    path = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
