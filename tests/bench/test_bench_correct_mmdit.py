"""The correctness check of the MMDiT generate cell, at a size a test run
holds, through its driver (``generate_prompt``) and its reference
(``mmdit_stadi``): a sound run is correct, the fp8 control is not, and each
fault planted in what the cell adds makes ``correct`` false: the flow
update returning its input, the context K/V left out of joint attention,
the context stream's MLP skipped. The image K/V exchange left out is run
too, and its reading reported. Besides: the bench config is the repo's
``sd3-medium``, ``bench/flops_mmdit.py`` against a hand count, and the
context-token reader on hand-made spans."""
import math

import jax
import pytest

import cells
from bench import flops_mmdit, harness

CELL = "sd3m-gen-2speed"
TEXT = dict(cond_seq_len=8, cond_dim=32, pooled_dim=16, pos_embed_max_size=6)
SIZES = harness.load_json(harness.BENCH / "configs" / "sd3-medium.json")[
    "sizes"]


def tiny_cell() -> dict:
    cell = cells.tiny_cell(CELL)
    cell["config"]["sizes"].update(TEXT)
    return cell


def test_sd3_medium_is_the_repo_config_as_it_is():
    from repro.configs import get_config
    repo = get_config("sd3-medium")
    for k, v in SIZES.items():
        assert getattr(repo, k) == v, k


def test_sound_run_is_correct():
    out = cells.run(tiny_cell())
    assert out["correct"] is True
    assert out["checks"]["image_rel_l2"]["value"] < 1e-3
    assert list(out["checks"]) == ["image_rel_l2", "plan_mismatch"]


def test_control_fails():
    """The reference at fp8 in the program's place reads far above the
    program and above the cell's limit."""
    cell = tiny_cell()
    run = harness.Run(cell, 7)
    drv = harness.load_module(harness.BENCH / "drivers"
                              / "generate_prompt.py")
    run.driver = drv.Driver(run)
    run.weights = run.make_weights()
    run.driver.setup()
    run.driver.window(0.3)
    out = harness.compare(run, run.driver.outputs(), control=True)
    assert out["control_rel_l2"] > 3 * out["image_rel_l2"]
    assert out["control_rel_l2"] > cell["check"]["limits"]["image_rel_l2"]


def unchanged_state(setattr):
    """Every flow update returns its input: the latent never moves."""
    from repro.core import sampler
    setattr(sampler, "flow_step", lambda sched, x, v, t0, t1: x)


def context_kv_left_out(setattr):
    """Joint attention reads the image keys alone."""
    from repro.models import layers
    from repro.models.diffusion import mmdit

    def image_only(q, k, v, cq, ck, cv):
        out = layers.attend(q, k, v)
        return out, (None if cq is None else layers.attend(cq, k, v))
    setattr(mmdit, "joint_attention", image_only)


def context_mlp_skipped(setattr):
    """The context stream takes its attention residual but not its MLP."""
    from repro.models.diffusion import mmdit

    def no_mlp(bp, ctx, catt, cmod):
        B, Lc, D = ctx.shape
        return ctx + cmod[2][:, None] * mmdit._linear(
            catt.reshape(B, Lc, D), bp["cwo"], bp["cwo_b"])
    setattr(mmdit, "context_update", no_mlp)


def exchange_left_out(setattr):
    """The published image K/V never take the workers' fresh rows."""
    from repro.core import buffers
    setattr(buffers, "merge", lambda published, pending, step, axis=2:
            buffers.Published(published.k, published.v, step))


@pytest.fixture
def planted(monkeypatch):
    """Plants a fault and drops the compiled programs on both sides of the
    test, so that programs traced with the fault and without it never
    meet."""
    def plant(fault):
        fault(monkeypatch.setattr)
        jax.clear_caches()
    yield plant
    monkeypatch.undo()
    jax.clear_caches()


@pytest.mark.parametrize("fault", [unchanged_state, context_kv_left_out,
                                   context_mlp_skipped], ids=lambda f:
                         f.__name__)
def test_fault_is_not_correct(fault, planted):
    planted(fault)
    out = cells.run(tiny_cell())
    assert out["correct"] is False


def test_exchange_left_out_reading(planted, capsys):
    """What the check reads with the image K/V exchange left out (PERF.md
    section 4): at this size it is caught, far above the sound run."""
    planted(exchange_left_out)
    out = cells.run(tiny_cell())
    gap = out["checks"]["image_rel_l2"]["value"]
    with capsys.disabled():
        print(f"\n[exchange left out] image_rel_l2 = {gap!r}")
    assert out["correct"] is False and gap > 1e-2


def test_full_image_by_hand():
    # 24 layers x 4096 tokens x 12 d^2 (q/k/v 3d^2, out d^2, MLP 8d^2);
    # the context stream: 333 tokens x (23 x 12 d^2 + the last block's k/v
    # 2 d^2) + the 4096 -> d embedder; joint attention of 4096 + 333
    # queries (4096 in the last block) against 4429 keys, 2 x 2 x d a pair
    parts = flops_mmdit.eval_flops(SIZES, 4096)
    D = 1536
    assert parts["image"] == 2 * 4096 * 24 * 12 * D * D
    assert math.isclose(parts["image"], 5.566e12, rel_tol=1e-3)
    assert math.isclose(parts["context"], 0.4410e12, rel_tol=1e-3)
    assert math.isclose(parts["attention"],
                        4 * 4429 * D * (24 * 4096 + 23 * 333), rel_tol=0)
    assert math.isclose(parts["attention"], 2.883e12, rel_tol=1e-3)
    assert parts["other"] < 1e-2 * sum(parts.values())


def test_executed_plan_flops():
    """Warm-up full evaluations plus each worker's steps x its rows, each
    evaluation carrying the whole context stream, at the plan the program
    executes on the two-speed grid (rows [38, 26], steps [20, 12])."""
    got = flops_mmdit.image_flops(SIZES, 4, 20, [1, 2], [38, 26])
    full = sum(flops_mmdit.eval_flops(SIZES, 4096).values())
    rows = [sum(flops_mmdit.eval_flops(SIZES, r * 64).values())
            for r in (38, 26)]
    assert got == 4 * full + 16 * rows[0] + 8 * rows[1]
    assert math.isclose(got, 155e12, rel_tol=0.01)


def test_context_token_share_on_hand_made_spans():
    """The reader's sum over ``exec.model`` spans inside ``stadi.generate``:
    on the plan's dispatches (4 full, 16 of 38 rows, 8 of 26, 333 context
    tokens each) it reads 9324 / 77932; model spans outside a call, or
    without ``ctx``, do not count."""
    reader = harness.load_module(harness.BENCH / "metrics"
                                 / "context_token_share.mmdit.py")
    spans = [(0, 1000, "stadi.generate", {})]
    for i, rows in enumerate([64] * 4 + [38] * 16 + [26] * 8):
        spans.append((10 + i, 11 + i, "exec.model",
                      {"rows": rows, "ctx": 333}))
    spans += [(2000, 2001, "exec.model", {"rows": 64, "ctx": 333}),
              (500, 501, "exec.model", {"rows": 64})]
    assert reader.context_share(spans, 64) == pytest.approx(
        100 * 9324 / 77932)
    assert 100 * 9324 / 77932 == pytest.approx(11.964, abs=1e-3)
    assert reader.context_share(spans[-1:], 64) is None
