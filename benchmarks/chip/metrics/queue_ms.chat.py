"""Median wait of a request in the engine's queue, in ms: from when it was
due to when the engine admitted it to a slot, over the requests admitted
in the window."""

import statistics


def read(run):
    waits = [(r.req.t_admit - r.due) * 1e3 for r in run["records"]
             if r.req.t_admit is not None and run["t0"] <= r.req.t_admit < run["end"]]
    return statistics.median(waits) if waits else None
