"""Share of the traced window in which no operation runs on the device,
in %, averaged over the chips: 1 minus the union of the device's op
intervals over the window's length."""


def read(run):
    lo, hi = run["window"]
    busy = run["devtrace"].busy(run["trace"], lo, hi)
    if hi <= lo or busy <= 0:
        return None
    return 100.0 * (1.0 - busy / (hi - lo))
