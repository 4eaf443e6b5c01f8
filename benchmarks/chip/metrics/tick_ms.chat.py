"""Median time of one engine tick (``ServeEngine.step()``) in the window,
in ms, on the host clock around the call."""

import statistics


def read(run):
    ticks = [(b - a) * 1e3 for a, b in run["ticks"] if run["t0"] <= a < run["end"]]
    return statistics.median(ticks) if ticks else None
