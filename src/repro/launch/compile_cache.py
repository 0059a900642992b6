"""Where the entry points keep JAX's persistent compilation cache.

A cache's path is part of its key, so it must not move between runs:
``JAX_COMPILATION_CACHE_DIR``, where the environment sets it, holds the
cache (JAX reads that variable itself); otherwise one fixed directory in
the checkout, ``<repo>/.jax_cache``, which ``.gitignore`` lists. Call
:func:`enable` from a ``main()`` before the first compile -- never at
import, and never from tests.
"""

from __future__ import annotations

import os

import jax

_REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
CHECKOUT_DIR = os.path.join(_REPO, ".jax_cache")


def cache_dir() -> str:
    """The directory :func:`enable` puts the cache in."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or CHECKOUT_DIR


def enable() -> str:
    """Turn the persistent compilation cache on for this process."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", CHECKOUT_DIR)
    return cache_dir()
