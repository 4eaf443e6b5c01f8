"""Share of its roofline that the Pallas SSD scan forward reaches, in %:
for each of its calls in the traced window (the custom calls in the
``ssd`` scope), the least time the scan allows (the larger of its FLOPs
at the bf16 peak and its bytes at the HBM peak, ``flops_hybrid.ssd_call``:
the algorithm's floor, ``B`` and ``C`` once per group, counted at the
published chunk size whatever chunk or layout the program uses),
summed and divided by the summed device time of those calls."""

import importlib


def read(run):
    pkg = run["devtrace"].__package__
    calls = importlib.import_module(pkg + ".scopes_hybrid").kernel_calls(
        run, "ssd")
    if not calls:
        return None
    cfg, peak = run["config"], run["peak"]
    fh = importlib.import_module(pkg + ".flops_hybrid")
    fl, nb = fh.ssd_call(run["mix"]["batch"] // run["chips"],
                         cfg["mamba_n_heads"], cfg["mamba_n_groups"],
                         run["mix"]["seq"], cfg["mamba_d_state"],
                         cfg["mamba_d_head"], cfg["mamba_chunk_size"])
    least = max(fl / peak["flops_s"], nb / peak["hbm_bytes_s"])
    spent = sum(e - s for _, s, e in calls)
    return 100.0 * least * len(calls) / spent
