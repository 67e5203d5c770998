"""Persistent XLA compile cache at a place that does not move.

The cache directory is part of every entry's key, so a path built from
``tempfile``, a pid or the clock would never hit. Where
``JAX_COMPILATION_CACHE_DIR`` is set JAX reads it itself and this module
sets no directory; otherwise the cache lives in ``<checkout>/.jax_cache``,
derived from the package's own location. ``JAX_ENABLE_COMPILATION_CACHE=false``
(JAX's own switch, which ``tests/conftest.py`` exports) turns the cache
off for a process and every child that inherits its environment.
"""

from __future__ import annotations

import os

# <checkout>/.jax_cache: the directory that holds the package
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache",
)


def configure() -> "str | None":
    """Place JAX's persistent cache. Returns the directory this call
    set, or None where the environment had already placed it."""
    import jax

    # keep every program: the kernels compile in 0.1-6 s each and the
    # default 1 s threshold would drop most of them
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):  # JAX reads it itself
        return None
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
