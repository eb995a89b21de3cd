"""Persistent XLA compilation cache for the entry points.

Entry points (``chip_smoke.py``, ``launch/train.py``, ``launch/serve.py``,
``benchmarks/write_path.py``) call :func:`enable_compile_cache` once at
start-up, so a second run of the same program on the same machine loads
its compiled programs instead of compiling them again. Importing this
module changes nothing.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already keeps its cache
there and this helper sets no other directory. Otherwise the cache lives
at ``<checkout>/.jax_cache`` (listed in ``.gitignore``): a fixed path,
because the path is part of each entry's key.
"""

from __future__ import annotations

import os

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"


def default_cache_dir() -> str:
    """``<checkout>/.jax_cache`` — this file is
    ``<checkout>/src/repro/launch/compile_cache.py``."""
    here = os.path.abspath(__file__)
    checkout = here
    for _ in range(4):
        checkout = os.path.dirname(checkout)
    return os.path.join(checkout, ".jax_cache")


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at ``$JAX_COMPILATION_CACHE_DIR``
    or, when that is unset, at :func:`default_cache_dir`. Returns the
    directory in use."""
    import jax

    path = os.environ.get(ENV_VAR) or default_cache_dir()
    jax.config.update("jax_compilation_cache_dir", path)
    return path
