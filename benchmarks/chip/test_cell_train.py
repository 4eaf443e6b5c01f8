"""A training cell from files the harness has never seen, run on the CPU
at a tiny size: sound, it is correct; with the timed path broken, it is
not."""

import pytest

from . import cellrun


@pytest.fixture(scope="module")
def cell(tmp_path_factory):
    tmp = str(tmp_path_factory.mktemp("train-checkout"))
    return tmp, cellrun.make_checkout(tmp, "tiny-train")


def test_training_cell_runs_and_is_correct(cell):
    rc, res = cellrun.run(*cell, seed=2**31 + 17, seconds=1)
    assert rc == 0 and res["correct"]
    assert list(res)[-1] == "checks"
    assert set(res["metrics"]) == {"train_tok_s", "setup_s"}
    assert res["attempted"] >= 1 and res["failed"] == 0


@pytest.mark.parametrize("fault", ["frozen_state", "half_batch"])
def test_training_cell_catches_a_broken_step(cell, fault):
    rc, res = cellrun.run(*cell, seed=2**31 + 17, seconds=1, fault=fault)
    assert rc == 0 and res["correct"] is False


def test_fp8_control_fails_the_limits(cell):
    """The reference in float8 put in the system's place reads above at
    least one limit."""
    import os

    from . import harness, train_cell
    tmp, name = cell
    c = harness.load_cell(name, tmp, os.path.join(tmp, "bench"))
    harness.prepare_program(tmp)
    ref = train_cell.reference_readings(c, 5)
    ctl = train_cell.compare(
        train_cell.reference_readings(c, 5, prec="fp8"), ref)
    assert any(ctl[k] > v for k, v in c.mix["limits"].items())
