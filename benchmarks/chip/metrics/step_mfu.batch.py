"""Share of the chip's roofline that the serving ticks reach, in %: each
prefill or decode call made while the profiler ran is given the least time
its work allows (``flops.serve_call_least_s``: its FLOPs at the bf16 peak,
or every weight and every live key and value read once at one byte each
at the HBM peak, whichever is larger), and their sum is divided by the
length of that stretch on the host clock."""


def read(run):
    lo, hi = run["trace_host"]
    calls = [c for c in run["calls"] if lo <= c[0] < hi]
    if hi <= lo or not calls:
        return None
    least = sum(run["flops"].serve_call_least_s(
        run["config"], run["peak"], [int(x) for x in new],
        [int(x) for x in cached]) for _, new, cached in calls)
    return 100.0 * least / (hi - lo)
