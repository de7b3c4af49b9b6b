"""Where JAX's persistent compilation cache lives.

Entry points call ``enable_compile_cache()`` before their first compile;
importing this module changes nothing.  ``JAX_COMPILATION_CACHE_DIR``,
when set, is read by JAX itself and left alone.  Otherwise the cache is
``<checkout>/.jax_cache`` (git-ignored): a fixed path, because a cache
in a directory that changes from run to run is never hit.
"""

from __future__ import annotations

import os

CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))
DEFAULT_DIR = os.path.join(CHECKOUT, ".jax_cache")


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and
    return that directory."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = DEFAULT_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    return path
