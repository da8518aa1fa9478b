"""Persistent XLA compile cache placement — the one rule, in one place.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, jax reads it itself and this
module sets no directory in code, so whoever runs the program (a test
driver, a chip runner) decides where compiled programs live. Where it is
not set, the cache sits at ONE fixed path inside the checkout
(``<repo>/.jax_cache``, git-ignored): the path is part of the cache key,
so a directory built from a temporary name, a pid or the time would
never hit. The test suite, ``chip_smoke.py``, ``bench.py`` and the
multichip gate all call :func:`enable_persistent_compile_cache` before
their first compile, so any one of them warms the others.
"""

from __future__ import annotations

import os

CACHE_DIR_ENV = "JAX_COMPILATION_CACHE_DIR"
REPO_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache")


def enable_persistent_compile_cache() -> str:
    """Turn the persistent compile cache on; return the directory in use.

    Safe to call before or after backend initialization (the config only
    affects future compiles).
    """
    import jax

    cache_dir = os.environ.get(CACHE_DIR_ENV)
    if not cache_dir:
        cache_dir = REPO_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return cache_dir
