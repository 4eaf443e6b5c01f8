"""Offered-load sweep of an open-loop serving cell, to find its knee.

    python3 benchmarks/chip/sweep.py --workload <cell> --seed <n> \
        --rates 2,3,4,5 [--seconds 30]

One process, one engine: for each rate the cell's traffic is offered at
that rate for ``--seconds``, then the engine is drained.  One JSON line per
rate: requests offered and finished, output tokens per second, the
time-to-first-token median and 90th percentile, and the queue at the end.
A rate the system sustains ends with an empty queue and a TTFT tail that
does not grow with the window.  Run once when a serving cell is defined;
not part of a benchmark run.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmarks.chip import harness, serve_cell, traffic  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=30)
    args = ap.parse_args()
    cell = harness.load_cell(args.workload, os.getcwd())
    devs, _, _ = harness.devices_for(cell, require_chip=True)
    harness.prepare_program(cell.root)
    vocab = cell.config["vocab_size"]
    prog = serve_cell.Program(cell, devs, args.seed)
    prog.warm(vocab)
    for rate in (float(r) for r in args.rates.split(",")):
        mix = {**cell.mix, "arrival": {"kind": "poisson", "rate_per_s": rate}}
        specs = traffic.generate(mix, args.seed, args.seconds, vocab)
        out = serve_cell.serve_window(prog, specs, mix, args.seconds, None)
        recs, t0, end = out["records"], out["t0"], out["end"]
        first = [(r.stamps[0] - r.due) * 1e3 for r in recs
                 if r.stamps and r.stamps[0] <= end]
        late = [(r.stamps[0] - r.due) * 1e3 for r in recs
                if r.stamps and end - args.seconds / 3 <= r.stamps[0] <= end]
        toks = sum(1 for r in recs for s in r.stamps if t0 <= s <= end)
        print(json.dumps({
            "rate": rate, "offered": len(recs),
            "finished": sum(1 for r in recs if r.req.done
                            and r.stamps[-1] <= end),
            "tok_s": toks / args.seconds,
            "ttft_p50_ms": float(np.percentile(first, 50)) if first else None,
            "ttft_p90_ms": float(np.percentile(first, 90)) if first else None,
            "ttft_p90_last_third_ms": (float(np.percentile(late, 90))
                                       if late else None),
            "queued_at_end": len(prog.engine.queue),
            "ticks": len(out["ticks"])}), flush=True)
        prog.engine.run()
        prog.engine.completed.clear()
    return 0


if __name__ == "__main__":
    sys.exit(main())
