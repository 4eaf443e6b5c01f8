"""Model FLOP utilization of the hybrid training step, in %: the FLOPs
the steps in the traced window need (``flops_hybrid.train_step_flops``,
no recompute) over the window's length on the profiler's clock, the chips
and their bf16 peak."""

import importlib


def read(run):
    lo, hi = run["window"]
    if hi <= lo or not run["steps_traced"]:
        return None
    fh = importlib.import_module(run["devtrace"].__package__
                                 + ".flops_hybrid")
    work = fh.train_step_flops(run["config"], run["mix"]["batch"],
                               run["mix"]["seq"]) * run["steps_traced"]
    return 100.0 * work / ((hi - lo) * run["chips"] * run["peak"]["flops_s"])
