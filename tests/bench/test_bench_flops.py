"""bench/flops.py against hand counts and against the executed plans."""
import math

import pytest

from bench import flops, harness

SIZES = {n: harness.load_json(harness.BENCH / "configs" / f"{n}.json")["sizes"]
         for n in ("sdxl-dit", "dit-xl-2-256")}


def test_sdxl_dit_full_image_by_hand():
    # 28 layers x 4096 tokens x 24 d^2 (qkv 3d^2, out d^2, MLP 8d^2; 2 FLOP
    # per MAC) and 28 x 4 x 4096^2 x 1152 for q.k^T and p.v
    parts = flops.eval_flops(SIZES["sdxl-dit"], 4096)
    assert math.isclose(parts["proj_mlp"], 3.653e12, rel_tol=1e-3)
    assert math.isclose(parts["attention"], 2.165e12, rel_tol=1e-3)
    assert parts["other"] < 1e-2 * flops.total(parts)


def test_dit_256_full_image_by_hand():
    parts = flops.eval_flops(SIZES["dit-xl-2-256"], 256)
    assert math.isclose(flops.total(parts), 0.236e12, rel_tol=1e-2)
    assert parts["attention"] < 0.04 * flops.total(parts)


@pytest.mark.parametrize("name,rows,equiv", [
    ("sdxl-dit", [38, 26], 16.75), ("dit-xl-2-256", [10, 6], 17.0)])
def test_executed_plan(name, rows, equiv):
    """Warm-up steps plus each worker's steps x its rows, at the plan the
    program executes on the two-speed grid."""
    from repro.core import sampler
    from repro.core.pipeline import StadiConfig, StadiPipeline
    from repro.configs.diffusion import DiTConfig
    sizes = SIZES[name]
    cfg = DiTConfig(**sizes)
    pipe = StadiPipeline(cfg, None, sampler.linear_schedule(T=1000),
                         StadiConfig.from_occupancies([0.0, 0.6], m_base=20,
                                                      m_warmup=4))
    plan = pipe.plan()
    assert list(plan.patches) == rows
    assert list(plan.temporal.steps) == [20, 12]
    got = flops.image_flops(sizes, 4, 20, plan.temporal.ratios, plan.patches)
    wp = sizes["latent_size"] // 2
    full = flops.eval_flops(sizes, wp * wp)
    want = 4 * flops.total(full) + sum(
        s * flops.total(flops.eval_flops(sizes, r * wp))
        for s, r in zip([16, 8], rows))
    assert got == want
    # projections and attention are linear in the query rows
    linear = full["proj_mlp"] + full["attention"]
    other = got - equiv * linear
    assert 0 < other < 0.01 * got
