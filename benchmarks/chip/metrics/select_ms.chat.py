"""Device ms per engine tick in the program's ``engine_select`` named
scope, the per-slot select over the whole KV cache: the scope's device
time in the traced window over the bridged ``serve.tick`` spans that start
in it (``scopes.tick_ms``)."""

import importlib


def read(run):
    scopes = importlib.import_module(run["devtrace"].__package__ + ".scopes")
    return scopes.tick_ms(run, "engine_select")
