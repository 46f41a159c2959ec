"""Where JAX's persistent compilation cache lives.

The cache directory is part of an entry's key, so a directory that moves
between runs never hits.  Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX
reads it itself and this module sets nothing; otherwise the cache goes to
one fixed directory inside the checkout.  Launchers call
:func:`configure_compile_cache` at start-up; importing sets nothing.
"""

from __future__ import annotations

import os

import jax

#: ``<checkout>/.jax_cache`` (listed in ``.gitignore``)
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def configure_compile_cache() -> str:
    """Point the persistent compile cache at its directory; returns it."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
