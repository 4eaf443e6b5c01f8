"""The hybrid training cell from files the harness has never seen, run on
the CPU at a tiny size (``testdata/smoke-hybrid.json``: the registry's
Granite-4.0-H entry at widths 64, the first six layers of the published pattern,
the published Mamba-2 state, head and chunk sizes): sound, it is correct;
with the timed path broken, or the reference in float8 in its place, it
is not."""

import json
import os
import shutil

import pytest

from . import cellrun, harness, train_cell

CONFIG, MIX = "smoke-hybrid", "tiny-train-hybrid"


def make_checkout(tmp: str) -> str:
    """A checkout with one cell, ``smoke-hybrid.tiny-train-hybrid``."""
    os.makedirs(os.path.join(tmp, "bench", "mixes"))
    os.symlink(os.path.join(cellrun.ROOT, "src"), os.path.join(tmp, "src"))
    shutil.copy(os.path.join(cellrun.DATA, CONFIG + ".json"),
                os.path.join(tmp, "bench", CONFIG + ".json"))
    shutil.copy(os.path.join(cellrun.DATA, MIX + ".json"),
                os.path.join(tmp, "bench", "mixes", MIX + ".json"))
    cell = f"{CONFIG}.{MIX}"
    manifest = {
        "command": ["python3", "bench/run.py"], "paths": ["bench"],
        "run_seconds": 2,
        "configs": [{"name": CONFIG, "source": "test",
                     "file": f"bench/{CONFIG}.json", "reduced": [],
                     "why": "test"}],
        "workloads": [{"name": cell, "config": CONFIG, "traffic": MIX,
                       "chips": 1, "why": "test"}],
        "end_to_end": [
            {"name": "setup_s", "unit": "s", "better": "lower",
             "bound": 0.25, "source": "host_clock"},
            {"name": "train_tok_s", "unit": "tokens/s", "better": "higher",
             "bound": 0.01, "source": "host_clock", "workloads": [cell]}],
        "per_layer": []}
    with open(os.path.join(tmp, "BENCHMARK.json"), "w") as f:
        json.dump(manifest, f)
    return cell


@pytest.fixture(scope="module")
def cell(tmp_path_factory):
    tmp = str(tmp_path_factory.mktemp("hybrid-checkout"))
    return tmp, make_checkout(tmp)


def test_hybrid_training_cell_runs_and_is_correct(cell):
    rc, res = cellrun.run(*cell, seed=2**31 + 23, seconds=1)
    assert rc == 0 and res["correct"], res and res["checks"]
    assert set(res["metrics"]) == {"train_tok_s", "setup_s"}
    assert res["attempted"] >= 1 and res["failed"] == 0


@pytest.mark.parametrize("fault", ["frozen_state", "half_batch"])
def test_hybrid_training_cell_catches_a_broken_step(cell, fault):
    rc, res = cellrun.run(*cell, seed=2**31 + 23, seconds=1, fault=fault)
    assert rc == 0 and res["correct"] is False


def test_hybrid_fp8_control_fails_the_limits(cell):
    """The reference in float8 put in the system's place reads above at
    least one limit."""
    tmp, name = cell
    c = harness.load_cell(name, tmp, os.path.join(tmp, "bench"))
    harness.prepare_program(tmp)
    ref = train_cell.reference_readings(c, 5)
    ctl = train_cell.compare(
        train_cell.reference_readings(c, 5, prec="fp8"), ref)
    assert any(ctl[k] > v for k, v in c.mix["limits"].items())
