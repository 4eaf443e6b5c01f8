"""Device ms per traced training step in the program's ``tt_bp`` named
scope: the TT input-gradient (dX) plans.  Ops are matched to the scope
by their ``op_name`` (``scopes.train_ms``)."""

import importlib


def read(run):
    scopes = importlib.import_module(run["devtrace"].__package__ + ".scopes")
    return scopes.train_ms(run, "tt_bp")
