"""The correctness check of the closed-loop generate cells, at a size a test
run holds: a sound run is correct, the fp8 control is not, and each fault
the cell can have, planted under the timed path, makes ``correct`` false:
a state left unchanged, an image altered where it is produced (a batch of
one has no half to leave out). The emulated K/V exchange left out is not
caught yet (PERF.md, Open questions)."""
import pytest

import cells
from bench import faults, harness

CELL = "dit256-gen-2speed"


def test_reference_plan_is_the_programs():
    from repro.core import sampler
    from repro.core.pipeline import StadiConfig, StadiPipeline
    from repro.configs.diffusion import DiTConfig
    ref = harness.load_module(harness.BENCH / "reference" / "dit_stadi.py")
    for occ in ([0.0, 0.6], [0.0, 0.0, 0.5, 0.5], [0.0, 0.3, 0.8]):
        for latent in (8, 32, 128):
            cfg = DiTConfig(latent_size=latent)
            plan = StadiPipeline(cfg, None, sampler.linear_schedule(),
                                 StadiConfig.from_occupancies(
                                     occ, m_base=20, m_warmup=4)).plan()
            steps, ratios, rows = ref.stadi_plan([1 - o for o in occ], 20, 4,
                                                 latent // 2)
            assert (steps, ratios, rows) == (list(plan.temporal.steps),
                                             list(plan.temporal.ratios),
                                             list(plan.patches))


def test_sound_run_is_correct():
    out = cells.run(cells.tiny_cell(CELL))
    assert out["correct"] is True
    assert out["checks"]["image_rel_l2"]["value"] < 1e-3
    assert list(out["checks"]) == ["image_rel_l2", "plan_mismatch"]


def test_control_fails():
    """The reference at fp8 in the program's place reads far above the
    program and above the cell's limit."""
    cell = cells.tiny_cell(CELL)
    run = harness.Run(cell, 7)
    drv = harness.load_module(harness.BENCH / "drivers" / "generate.py")
    run.driver = drv.Driver(run)
    run.weights = run.make_weights()
    run.driver.setup()
    run.driver.window(0.3)
    out = harness.compare(run, run.driver.outputs(), control=True)
    assert out["control_rel_l2"] > 3 * out["image_rel_l2"]
    assert out["control_rel_l2"] > cell["check"]["limits"]["image_rel_l2"]


@pytest.mark.parametrize("fault", list(faults.FAULTS["generate"].values()))
def test_fault_is_not_correct(fault, monkeypatch):
    fault(monkeypatch.setattr)
    out = cells.run(cells.tiny_cell(CELL))
    assert out["correct"] is False

