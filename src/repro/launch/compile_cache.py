"""JAX's persistent compilation cache, placed from outside.

Called from the ``main()`` of every entry point that drives the chip
(``launch/train.py``, ``launch/serve.py``, ``chip_smoke.py``), never at
import.  Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it
and nothing else is set; otherwise the cache lives at the fixed path
``<checkout>/.cache/jax``.  The path is part of what a later run must
find again, so it is never built from a temporary name, a pid or the
time.
"""

from __future__ import annotations

import os

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = os.path.abspath(os.path.join(
    os.path.dirname(__file__), "..", "..", "..", ".cache", "jax"))


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    path = os.environ.get(ENV)
    if not path:
        path = DEFAULT_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    return path
