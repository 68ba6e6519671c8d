"""Where JAX keeps its persistent compilation cache.

Call :func:`enable_compile_cache` once, before the first compile, from
every entry point that compiles (``chip_smoke.py``, ``launch/serve.py``,
``launch/train.py``).  The rule:

  * ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself and the
    program sets no cache path in code;
  * unset: the cache goes to ``<checkout>/.jax_cache``, a fixed path
    (the path is part of what a later run must find again, so it is
    never built from a temporary name, a pid or the time).
"""

from __future__ import annotations

import os

import jax

CHECKOUT = os.path.abspath(
    os.path.join(os.path.dirname(__file__), "..", "..", ".."))
DEFAULT_DIR = os.path.join(CHECKOUT, ".jax_cache")


def enable_compile_cache() -> str:
    """Point the persistent compilation cache at its directory; returns
    the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
