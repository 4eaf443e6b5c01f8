"""Device ms per traced training step in the program's ``tt_fp`` named
scope: the TT forward contraction plans, with their recompute inside the
layers' remat.  Ops are matched to the scope by their ``op_name``
(``scopes.train_ms``)."""

import importlib


def read(run):
    scopes = importlib.import_module(run["devtrace"].__package__ + ".scopes")
    return scopes.train_ms(run, "tt_fp")
