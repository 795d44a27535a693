"""Where JAX's persistent compilation cache lives.

The cache directory is part of the cache key, so a directory that moves never
hits. ``JAX_COMPILATION_CACHE_DIR`` wins when set (JAX reads it itself and
nothing is set in code); otherwise the cache sits at a fixed path beside the
package, ``<checkout>/.jax_cache``.
"""

import os

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def configure_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and return it."""
    from_env = os.environ.get(CACHE_ENV)
    if from_env:
        return from_env
    import jax
    path = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
