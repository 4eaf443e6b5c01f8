"""Device time by named scopes that ``scopes.SCOPES`` does not list
(``mamba``, ``ssd``), for the hybrid cell's readers.

It matches scopes itself: ``scopes.report`` attaches the op names to the
trace (compiling the train step again for its text where the op events
carry none), then :func:`scopes.in_scope` decides each op.  It returns
nothing where it finds nothing, as ``scopes.py`` does.
"""

from __future__ import annotations

from . import devtrace, scopes


def op_names(run: dict) -> dict:
    """The trace's op names, attached once."""
    scopes.report(run, lambda: scopes.train_texts(run))
    return run["trace"]["op_names"]


def in_scope_fn(names: dict, scope: str):
    """A test of an op event's name, memoised by its ``op_name``."""
    memo: dict[str, bool] = {}

    def test(event: str) -> bool:
        name = names.get(event, "")
        if name not in memo:
            memo[name] = scopes.in_scope(name, scope)
        return memo[name]

    return test


def scope_ms(run: dict, scope: str) -> float | None:
    """Device ms per traced training step in ``scope``, averaged over the
    devices; None where no op of the trace lies in it."""
    if not run.get("steps_traced"):
        return None
    names = op_names(run)
    if not any(scopes.in_scope(n, scope) for n in set(names.values())):
        return None
    inside = in_scope_fn(names, scope)
    tr, (lo, hi) = run["trace"], run["window"]
    per_dev = [devtrace.covered(devtrace.union(
        (s, e) for n, s, e in ops if inside(n)), lo, hi)
        for ops in tr["ops"].values()]
    return 1e3 * sum(per_dev) / len(per_dev) / run["steps_traced"]


def kernel_calls(run: dict, scope: str) -> list:
    """The Pallas kernel events in ``scope`` (custom calls whose op_name
    ends in ``pallas_call``; the compiler's other custom calls there, such
    as buffer allocations, are left out) that start in the traced window,
    over every device."""
    names = op_names(run)
    inside = in_scope_fn(names, scope)
    kernel = in_scope_fn(names, "pallas_call")
    lo, hi = run["window"]
    return [(n, s, e) for ops in run["trace"]["ops"].values()
            for n, s, e in ops
            if lo <= s < hi and "custom-call(" in n and inside(n)
            and kernel(n)]
