"""Where the entry points keep JAX's persistent compilation cache.

``enable_compile_cache()`` is called once, before the first compile, by
the command-line entry points (``launch.solve`` and ``chip_smoke.py``).
When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and the
helper changes nothing.  Otherwise the cache goes to one fixed directory
inside the checkout (listed in ``.gitignore``): the path is part of what a
later run must find again, so it never comes from a temporary name, a
process id or the clock.
"""
from __future__ import annotations

import os

import jax

#: ``<checkout>/.jax_cache`` — src/repro/launch/cache.py is four levels down
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def enable_compile_cache() -> str:
    """Point the persistent compilation cache at its directory; returns it."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if path:
        return path
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
