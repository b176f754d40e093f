"""JAX's persistent compilation cache for the repo's entry-point scripts.

``enable()`` is called by ``chip_smoke.py``, the examples, the benchmarks
and ``python -m repro.tune`` — never when the library is imported, and
never by the tests.  Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads
it and this sets no other directory; otherwise the cache lives at a fixed
``<checkout>/.jax_cache`` (git-ignored), so every process run from one
checkout finds the executables the previous ones compiled.
"""
from __future__ import annotations

import os

import jax

#: The default cache directory: ``.jax_cache`` at the checkout root.
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache")


def enable() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = DEFAULT_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    return path
