"""Readings that set the limits of a cell's output check, on the chip.

    python3 benchmarks/chip/calibrate.py --workload <cell> \
        --seeds 1,2,... [--control-seeds 1,2,3] [--seconds 51]

For each seed of ``--seeds`` it takes the numbers the benchmark compares
from a sound run of the system (the lower readings); for each seed of
``--control-seeds`` the same numbers with the control in the system's
place: the plain reference computed with float8 operands, one precision
below the bfloat16 the configuration states (the upper reading).  For a
training cell it also reads the fault "half of the batch left out, the
mean taken over the rest", planted in the reference put in the system's
place.  A training cell's system readings need no window: all seeds go
through one compiled step.  A serving cell runs its window at the cell's
own load for each seed and compares the served tokens with both
references.

This process never touches JAX: each piece runs in a child process of its
own, one after another, so that each starts with the chip's memory empty.
One JSON line per reading goes to standard output.  Not part of a
benchmark run.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmarks.chip import harness, serve_cell, traffic, train_cell  # noqa: E402


def emit(**kw) -> None:
    print(json.dumps(kw), flush=True)


# -- pieces, each in its own process -------------------------------------------


def part_program(cell, devs, seeds) -> dict:
    prog = train_cell.Program(cell, devs)
    out = {}
    for s in seeds:
        prog.start(s)
        out[s] = train_cell.program_readings(prog, s, None)
    return out


def part_serve(cell, devs, seed, control, seconds) -> dict:
    mix, cfg = cell.mix, cell.config
    prog = serve_cell.Program(cell, devs, seed)
    prog.warm(cfg["vocab_size"])
    specs = traffic.generate(mix, seed, seconds, cfg["vocab_size"])
    out = serve_cell.serve_window(prog, specs, mix, seconds, None)
    chk = mix["check"]
    serve_cell.finish_for_check(prog.engine, out["records"], chk["tokens"],
                                chk["wait_s"], out["end"])
    prog.engine = None
    del prog
    harness.free_device()
    sample = serve_cell.pick_sample(out["records"], seed, chk["tokens"],
                                    chk["max_requests"])
    precs = ("f32", "fp8") if control else ("f32",)
    gaps = serve_cell.logit_gaps(cell, seed, sample, precs)
    return {p: [float(max(g)), len(g)] for p, g in gaps.items()}


def run_part(args, cell) -> None:
    devs, _, _ = harness.devices_for(cell, require_chip=True)
    harness.prepare_program(cell.root)
    seeds = [int(x) for x in args.seeds.split(",")]
    kind, *rest = args.part.split(":")
    if kind == "program":
        res = part_program(cell, devs, seeds)
    elif kind == "reference":
        res = train_cell.reference_readings(cell, seeds[0], prec=rest[0],
                                            half_batch=rest[1] == "half")
    else:
        res = part_serve(cell, devs, seeds[0], rest[0] == "control",
                         args.seconds)
    print("PART " + json.dumps(res), flush=True)


# -- the orchestration -------------------------------------------------------------


def child(args, part: str, seeds) -> dict | None:
    cmd = [sys.executable, os.path.abspath(__file__), "--workload",
           args.workload, "--seeds", ",".join(map(str, seeds)),
           "--seconds", str(args.seconds), "--part", part]
    p = subprocess.run(cmd, capture_output=True, text=True)
    for line in p.stdout.splitlines():
        if line.startswith("PART "):
            return json.loads(line[5:])
    emit(kind="failed", part=part, seeds=seeds, rc=p.returncode,
         stderr=p.stderr[-1500:])
    return None


def calibrate_train(args, seeds, control) -> None:
    got = child(args, "program", seeds) or {}
    for s in seeds:
        ref = child(args, "reference:f32:full", [s])
        if ref is None or str(s) not in got:
            continue
        emit(kind="program", seed=s, **train_cell.compare(got[str(s)], ref),
             loss=got[str(s)]["loss"], ref_loss=ref["loss"])
        if s in control:
            for kind, part in (("control_fp8", "reference:fp8:full"),
                               ("fault_half_batch", "reference:f32:half")):
                other = child(args, part, [s])
                if other is not None:
                    emit(kind=kind, seed=s, **train_cell.compare(other, ref))


def calibrate_serve(args, seeds, control) -> None:
    for s in seeds:
        res = child(args, "serve:" + ("control" if s in control else "sound"),
                    [s])
        if res is None:
            continue
        emit(kind="program", seed=s, logit_gap=res["f32"][0],
             tokens=res["f32"][1])
        if "fp8" in res:
            emit(kind="control_fp8", seed=s, logit_gap=res["fp8"][0],
                 tokens=res["fp8"][1])


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=51)
    ap.add_argument("--part", default=None, help=argparse.SUPPRESS)
    args = ap.parse_args()
    cell = harness.load_cell(args.workload, os.getcwd())
    if args.part:
        run_part(args, cell)
        return 0
    seeds = [int(x) for x in args.seeds.split(",")]
    control = {int(x) for x in args.control_seeds.split(",") if x}
    t = time.monotonic()
    if cell.mix["driver"] == "train":
        calibrate_train(args, seeds, control)
    else:
        calibrate_serve(args, seeds, control)
    emit(kind="done", seconds=time.monotonic() - t)
    return 0


if __name__ == "__main__":
    sys.exit(main())
