"""The correctness check of the serving cells, at a size a test run holds:
a sound run is correct, and each fault the cell can have, planted under the
timed path, makes ``correct`` false: a state left unchanged, half of each
lane group left out, an image altered where it is produced. The emulated
K/V exchange left out is not caught yet (PERF.md, Open questions)."""
import pytest

import cells
from bench import faults

CELL = "dit256-serve-poisson"


def test_sound_run_is_correct():
    out = cells.run(cells.tiny_cell(CELL))
    assert out["correct"] is True
    assert out["failed"] == 0 and out["attempted"] == 20
    assert out["checks"]["image_rel_l2"]["value"] < 1e-3


def test_saturated_run_counts_unfinished_as_failed():
    cell = cells.tiny_cell("dit256-serve-sat", rate_per_s=100.0, drain_s=0.0)
    cell["check"]["sample"] = 8
    out = cells.run(cell, seconds=2.0)
    assert out["correct"] is True
    assert out["attempted"] == 200 and out["failed"] > 0


@pytest.mark.parametrize("fault", list(faults.FAULTS["serve"].values()))
def test_fault_is_not_correct(fault, monkeypatch):
    fault(monkeypatch.setattr)
    out = cells.run(cells.tiny_cell(CELL))
    assert out["correct"] is False
