"""Where the persistent XLA compile cache lives.

The entry points (``chip_smoke.py``, ``launch/train.py``,
``launch/serve.py``, ``benchmarks/run.py``) call :func:`enable_compile_cache`
before their first compile, so a second run in the same place reuses the
compiled step programs instead of rebuilding them.

JAX reads ``JAX_COMPILATION_CACHE_DIR`` itself: when it is set, this module
leaves the choice to JAX and sets no other directory.  Otherwise the cache
is one fixed directory inside the checkout, ``<checkout>/.jax_cache``
(gitignored).  It is never a temporary directory, a pid or a timestamp:
the directory is part of how cache entries are found again, so a path
that moves between runs never hits.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["CHECKOUT_CACHE_DIR", "enable_compile_cache"]

#: ``src/repro/launch/compile_cache.py`` -> the checkout root
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compile cache on; return the directory in use.

    Call before the process's first compile: JAX settles whether a process
    uses the cache when it first compiles.
    """
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
