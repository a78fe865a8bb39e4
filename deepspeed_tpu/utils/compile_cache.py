"""Where the persistent XLA compile cache lives.

A cold process recompiles every frame bucket and the train step; the
entry points that run on the chip call ``enable_compile_cache()`` first
thing so later processes (and the other phase of ``chip_smoke.py``) read
the programs back. The directory is part of the cache key, so it is never
a temp name, pid or time: either the one ``JAX_COMPILATION_CACHE_DIR``
names — JAX reads that variable itself, and nothing is set in code — or
``<checkout>/.jax_cache``. The CPU test suite never calls this.
"""

import os

REPO_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent compile cache on; returns the directory in use."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    import jax
    jax.config.update("jax_compilation_cache_dir", REPO_CACHE_DIR)
    return REPO_CACHE_DIR
