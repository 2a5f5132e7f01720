"""Where compiled programs (and the autotuner's tile winners) are kept
between runs.

The directory is part of JAX's cache key, so it must not move: no
temporary name, process id or time goes into it.  Placed from outside
through ``JAX_COMPILATION_CACHE_DIR`` (JAX reads that variable itself, so
nothing is set here); otherwise one fixed directory inside the checkout.
"""
from __future__ import annotations

import os

import jax

_ENV = "JAX_COMPILATION_CACHE_DIR"
_DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def cache_dir(path=None) -> str:
    """The compile-cache directory in force: the environment's, else
    ``path``, else ``<checkout>/.jax_cache``."""
    return os.environ.get(_ENV) or path or _DEFAULT_DIR


def enable_compile_cache(path=None) -> str:
    """Turn the persistent compile cache on; call before the first jit
    of an entry point that compiles at real size.  Returns the directory
    (see :func:`cache_dir`)."""
    if not os.environ.get(_ENV):
        jax.config.update("jax_compilation_cache_dir", cache_dir(path))
    return cache_dir(path)
