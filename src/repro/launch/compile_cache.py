"""JAX's persistent compilation cache, placed from outside.

Called from the ``main()`` of every entry point that drives the chip
(``launch/train.py``, ``launch/serve.py``, ``chip_smoke.py``), never at
import.  Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it
and no path is set; otherwise the cache lives at the fixed path
``<checkout>/.cache/jax``.  The path is part of what a later run must
find again, so it is never built from a temporary name, a pid or the
time.

The cache key includes the programs' metadata (``op_name``, locations),
which JAX strips by default: an executable built from the same
computation under other names would otherwise be loaded, and the profiler
would show its instruction names and its ``op_name`` metadata, not this
program's named scopes.
"""

from __future__ import annotations

import os

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = os.path.abspath(os.path.join(
    os.path.dirname(__file__), "..", "..", "..", ".cache", "jax"))


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    path = os.environ.get(ENV)
    if not path:
        path = DEFAULT_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    return path
