"""Runs of a cell from throwaway files, for the CPU tests: a checkout made
of a ``BENCHMARK.json``, a configuration file and a mix the harness has
never seen, with ``src/`` linked from this repository."""

from __future__ import annotations

import contextlib
import io
import json
import os
import shutil

from . import harness

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
DATA = os.path.join(HERE, "testdata")


def make_checkout(tmp: str, mix_name: str) -> str:
    """A checkout with one cell, ``smoke-tt.<mix_name>``."""
    os.makedirs(os.path.join(tmp, "bench", "mixes"), exist_ok=True)
    os.symlink(os.path.join(ROOT, "src"), os.path.join(tmp, "src"))
    shutil.copy(os.path.join(DATA, "smoke-tt.json"),
                os.path.join(tmp, "bench", "smoke-tt.json"))
    shutil.copy(os.path.join(DATA, mix_name + ".json"),
                os.path.join(tmp, "bench", "mixes", mix_name + ".json"))
    with open(os.path.join(DATA, mix_name + ".json")) as f:
        driver = json.load(f)["driver"]
    cell = f"smoke-tt.{mix_name}"
    e2e = ["train_tok_s"] if driver == "train" else ["ttft_p50_ms",
                                                     "itl_p95_ms"]
    manifest = {
        "command": ["python3", "bench/run.py"], "paths": ["bench"],
        "run_seconds": 2,
        "configs": [{"name": "smoke-tt", "source": "test",
                     "file": "bench/smoke-tt.json", "reduced": [],
                     "why": "test"}],
        "workloads": [{"name": cell, "config": "smoke-tt",
                       "traffic": mix_name, "chips": 1, "why": "test"}],
        "end_to_end": [{"name": "setup_s", "unit": "s", "better": "lower",
                        "bound": 0.25, "source": "host_clock"}] + [
            {"name": n, "unit": "ms", "better": "lower", "bound": 0.1,
             "source": "host_clock", "workloads": [cell]} for n in e2e],
        "per_layer": []}
    with open(os.path.join(tmp, "BENCHMARK.json"), "w") as f:
        json.dump(manifest, f)
    return cell


def run(tmp: str, cell: str, seed: int, seconds: float,
        fault: str | None = None) -> tuple[int, dict | None]:
    """One run with the look for a chip skipped; (exit code, result)."""
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        rc = harness.main(["--workload", cell, "--seed", str(seed),
                           "--seconds", str(seconds), "--trace", "0"],
                          root=tmp, bench_dir=os.path.join(tmp, "bench"),
                          require_chip=False, fault=fault)
    lines = out.getvalue().strip().splitlines()
    return rc, (json.loads(lines[-1]) if rc == 0 and lines else None)
