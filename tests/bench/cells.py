"""Helpers of the benchmark's CPU tests: a cell cut to a size a test run
holds (same traffic and limits; narrow, shallow model), run through the
harness with the accelerator check skipped."""
import time

from bench import harness

TINY = dict(latent_size=8, n_layers=2, d_model=64, n_heads=4, n_classes=10)


def tiny_cell(name: str, **traffic) -> dict:
    cell = harness.cell_spec(name)
    cell["config"]["sizes"].update(TINY)
    if cell["traffic"]["driver"] == "serve":
        # a burst that fills every slot; every output is compared
        cell["traffic"].update(rate_per_s=40.0, drain_s=60.0)
        cell["check"]["sample"] = 1000
    cell["traffic"].update(traffic)
    return cell


def run(cell: dict, seed: int = 2**40 + 11, seconds: float = 0.5) -> dict:
    return harness.run_cell(cell, seed, seconds, False, time.perf_counter(),
                            check_device=False)
