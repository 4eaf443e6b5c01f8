"""Device ms per traced training step in the program's ``attention`` named
scope: attention, its projections, rope, the flash forward and its
custom backward.  Ops are matched to the scope by their ``op_name``
(``scopes.train_ms``)."""

import importlib


def read(run):
    scopes = importlib.import_module(run["devtrace"].__package__ + ".scopes")
    return scopes.train_ms(run, "attention")
