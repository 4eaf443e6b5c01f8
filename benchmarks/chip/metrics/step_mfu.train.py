"""Model FLOP utilization of the training step, in %: the FLOPs the steps
in the traced window need (``flops.train_step_flops``, no recompute) over
the window's length on the profiler's clock, the chips and their bf16
peak."""


def read(run):
    lo, hi = run["window"]
    if hi <= lo or not run["steps_traced"]:
        return None
    work = run["step_flops"] * run["steps_traced"]
    return 100.0 * work / ((hi - lo) * run["chips"] * run["peak"]["flops_s"])
