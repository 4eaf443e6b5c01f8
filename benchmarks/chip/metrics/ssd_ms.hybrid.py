"""Device ms per traced training step in the program's ``ssd`` named
scope: the chunked scan of every Mamba-2 mixer, its Pallas forward, the
forward's remat recompute and the chunked-jnp backward
(``scopes_hybrid.scope_ms``)."""

import importlib


def read(run):
    pkg = run["devtrace"].__package__
    return importlib.import_module(pkg + ".scopes_hybrid").scope_ms(
        run, "ssd")
