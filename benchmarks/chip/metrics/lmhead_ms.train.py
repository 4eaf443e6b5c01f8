"""Device ms per traced training step in the program's ``lm_head`` named
scope: the LM head and the loss over its logits, forward and
backward.  Ops are matched to the scope by their ``op_name``
(``scopes.train_ms``)."""

import importlib


def read(run):
    scopes = importlib.import_module(run["devtrace"].__package__ + ".scopes")
    return scopes.train_ms(run, "lm_head")
