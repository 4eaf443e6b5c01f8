"""Production mesh construction.

A FUNCTION (not a module constant) so importing this module never touches
jax device state — the dry-run sets XLA_FLAGS before first jax init.
"""

from __future__ import annotations

import jax

from repro.distributed.sharding import make_mesh


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2 pods = 512 chips with a leading `pod`
    axis (outer data parallelism, hierarchical gradient reduction)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh():
    """Degenerate 1x1 mesh over the real local device (smoke/examples)."""
    n = len(jax.devices())
    return make_mesh((n, 1), ("data", "model"))
