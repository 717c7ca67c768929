"""Placement of JAX's persistent compilation cache.

A program that compiles for minutes should pay that once per machine, not
once per process.  The programs call :func:`place_compile_cache` before
their first compile (``chip_smoke.py``, ``benchmark/run.py``,
``examples/*.py``);
nothing calls it at import, and tests never do.

The directory is part of the cache key's world: a cache that moves never
hits.  So it is either where the environment says, or one fixed path inside
the checkout — never a temp dir, a pid or a timestamp.
"""
from __future__ import annotations

import os

CACHE_DIR_ENV = "JAX_COMPILATION_CACHE_DIR"
# <checkout>/.jax_compile_cache (git-ignored): next to the package, so every
# program run from this checkout shares it
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_compile_cache")


def place_compile_cache() -> str:
    """Make sure the persistent compile cache is on; return its directory.

    With ``JAX_COMPILATION_CACHE_DIR`` set, JAX has already read it and this
    writes no config at all — whoever placed the cache owns its settings.
    Otherwise the cache goes to :data:`DEFAULT_CACHE_DIR`, and the minimum
    compile time for an entry drops from JAX's 1.0 s to 0: the serving
    engine's page movers and short prefill buckets compile in well under a
    second each, and a fresh process should find them too.
    """
    placed = os.environ.get(CACHE_DIR_ENV)
    if placed:
        return placed
    import jax

    jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return DEFAULT_CACHE_DIR
