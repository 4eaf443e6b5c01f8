"""Device ms per traced training step in the program's ``mamba`` named
scope: every Mamba-2 mixer, its projections, conv, scan and gated norm,
forward, remat recompute and backward (``scopes_hybrid.scope_ms``)."""

import importlib


def read(run):
    pkg = run["devtrace"].__package__
    return importlib.import_module(pkg + ".scopes_hybrid").scope_ms(
        run, "mamba")
