"""Median host ms of an engine tick outside waiting on the device: over
the program's ``serve.tick`` spans that start in the measured window, the
tick's length less that of the ``serve.fetch`` spans inside it."""

import statistics


def read(run):
    try:
        from repro.telemetry import mono_us
    except ImportError:          # a system whose tracer has no such clock
        return None
    lo, hi = mono_us(run["t0"]), mono_us(run["end"])
    spans = [e for e in run["program_spans"]
             if e["name"] in ("serve.tick", "serve.fetch")]
    fetches = [(e["ts"], e["ts"] + e["dur"]) for e in spans
               if e["name"] == "serve.fetch"]
    host = []
    for e in spans:
        if e["name"] != "serve.tick" or not lo <= e["ts"] < hi:
            continue
        end = e["ts"] + e["dur"]
        wait = sum(b - a for a, b in fetches if e["ts"] <= a and b <= end)
        host.append((e["dur"] - wait) * 1e-3)
    return statistics.median(host) if host else None
