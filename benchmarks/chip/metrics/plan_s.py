"""Seconds of set-up the system spends planning: the union of its own
``csse.search`` and ``autotune.sweep`` spans (the plan compiler runs
inside them and at trace time), from the system's in-memory tracer."""

NAMES = ("csse.search", "autotune.sweep")


def read(run):
    spans = [(e["ts"] * 1e-6, (e["ts"] + e["dur"]) * 1e-6)
             for e in run["program_spans"] if e["name"] in NAMES]
    if not spans:
        return None
    merged = run["devtrace"].union(spans)
    return sum(e - s for s, e in merged)
