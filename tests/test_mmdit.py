"""The MMDiT family (SD3's dual-stream joint-attention denoiser) and the
rectified-flow sampler against the plain reference
``bench/reference/mmdit_stadi.py``, at a tiny size on the reference's
seeded random weights (non-zero modulation, gates and head).

Tolerance: both sides compute in float32 on the CPU, the reference under
``highest`` precision; they differ only in the order of summation and in
how the Euler step's sigma difference is rounded (``(t_to - t_from) /
1000`` against ``sigma_to - sigma_from``), each an ULP or so (about 1e-6
relative, read on the CPU). A relative L2 gap of 1e-4 leaves two orders of
magnitude to those, and is ten times below what leaving out the image K/V
exchange, the context K/V or the context MLP reads
(``tests/bench/test_bench_correct_mmdit.py``)."""
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.configs.diffusion import DiTConfig
from repro.core import patch_parallel as pp
from repro.core import sampler
from repro.core.pipeline import StadiConfig, StadiPipeline
from repro.models.diffusion import dit, mmdit

TOL = 1e-4
SIZES = dict(family="mmdit", latent_size=16, channels=4, patch_size=2,
             n_layers=3, d_model=64, n_heads=4, mlp_ratio=4.0,
             cond_seq_len=8, cond_dim=32, pooled_dim=16,
             pos_embed_max_size=12, flow_shift=3.0, n_classes=10,
             param_dtype="float32", dtype="float32")
KEY = tuple(sorted(SIZES.items()))
CFG = DiTConfig(arch_id="tiny-mmdit", **SIZES)
STADI = {"occupancies": [0.0, 0.6], "m_base": 20, "m_warmup": 4}


def _reference():
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "bench", "reference", "mmdit_stadi.py")
    spec = importlib.util.spec_from_file_location("mmdit_stadi", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _reference()


def rel_l2(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


@pytest.fixture(scope="module")
def weights():
    return jax.jit(lambda k: REF.make_weights(k, SIZES))(
        jax.random.PRNGKey(3))


def _inputs(cls=4):
    x = np.random.default_rng(11).standard_normal((16, 16, 4),
                                                  dtype=np.float32)
    ctx, pooled = REF.prompt(SIZES, cls)
    return x, ctx, pooled, mmdit.TextCond(jnp.asarray(ctx[None]),
                                          jnp.asarray(pooled[None]))


def test_init_params_is_the_reference_layout():
    layout = lambda f: jax.tree.map(lambda a: (a.shape, a.dtype),
                                    jax.eval_shape(f, jax.random.PRNGKey(0)))
    for sizes in (SIZES, dict(get_config("sd3-medium").__dict__)):
        cfg = DiTConfig(**sizes)
        want = jax.eval_shape(lambda k: REF.make_weights(k, sizes),
                              jax.random.PRNGKey(0))
        assert layout(lambda k: dit.init_params(k, cfg)) == \
            layout(lambda k: REF.make_weights(k, sizes))
    # SD3-Medium's transformer: 24 x 36 D^2 in the blocks, about 2.0 B
    n = sum(a.size for a in jax.tree.leaves(want))
    assert 2.0e9 < n < 2.1e9


def test_pos_table_is_the_references():
    for side, max_size in ((4, 6), (64, 192)):
        np.testing.assert_array_equal(mmdit.pos_table(side, max_size, 96),
                                      REF._pos_table(side, max_size, 96))


def test_full_forward(weights):
    x, ctx, pooled, cond = _inputs()
    t = 700.0
    got, kv = pp._jit_full_step(weights, CFG, jnp.asarray(x[None]), t, cond)
    want, kv_ref = REF.patch_forward(weights, KEY, x, t, ctx, pooled, 0,
                                     None, None)
    assert rel_l2(got[0], want) < TOL
    for a, b in zip(kv, kv_ref):
        assert a.shape[:3] == (3, 1, 64) and rel_l2(a[:, 0], b) < TOL


def test_patch_forward_with_stale_buffers(weights):
    """Rows 3-4 of 8 against random published image K/V, through the joint
    blocks and the context_pre_only last one; the fresh K/V it returns are
    its own rows', the last block's included."""
    x, ctx, pooled, cond = _inputs()
    rng = np.random.default_rng(5)
    bk, bv = (rng.standard_normal((3, 1, 64, 4, 16), dtype=np.float32)
              for _ in range(2))
    rows = x[6:10]
    call = lambda bk, bv, cond=cond: pp._jit_patch_step(
        weights, CFG, jnp.asarray(rows[None]), 300.0, cond, 3,
        jnp.asarray(bk), jnp.asarray(bv))
    got, kv = call(bk, bv)
    want, kv_ref = REF.patch_forward(weights, KEY, rows, 300.0, ctx, pooled,
                                     3, bk[:, 0], bv[:, 0])
    assert got.shape == (1, 4, 16, 4) and rel_l2(got[0], want) < TOL
    for a, b in zip(kv, kv_ref):
        assert a.shape[:3] == (3, 1, 16) and rel_l2(a[:, 0], b) < TOL
    # the stale rows and the context are read: other buffers or another
    # prompt's context move the velocity
    assert rel_l2(call(bk[::-1], bv[::-1])[0], got) > 1e-2
    other = mmdit.TextCond(_inputs(cls=5)[3].context, cond.pooled)
    assert rel_l2(call(bk, bv, other)[0], got) > 1e-2


def test_flow_grid_and_update():
    sched = sampler.FlowSchedule(shift=3.0)
    np.testing.assert_array_equal(sched.sigmas(20), REF.sigmas(20, 3.0))
    ts = np.asarray(sampler.timesteps(sched, 20))
    assert ts[0] == 1000.0 and ts[-1] == 0.0 and np.all(np.diff(ts) < 0)
    np.testing.assert_array_equal(ts, np.float32(1000) * REF.sigmas(20, 3.0))
    x = jax.random.normal(jax.random.PRNGKey(0), (3, 1, 4, 4, 2))
    v = jax.random.normal(jax.random.PRNGKey(1), x.shape)
    sg = REF.sigmas(20, 3.0)
    # one scalar step, and per-lane [G] steps over a lane stack
    got = sampler.step(sched, x, v, ts[2], ts[5])
    np.testing.assert_allclose(got, x + (sg[5] - sg[2]) * v, rtol=1e-6,
                               atol=1e-6)
    lanes = sampler.step(sched, x, v, ts[[0, 2, 4]], ts[[1, 3, 5]])
    for g, (a, b) in enumerate([(0, 1), (2, 3), (4, 5)]):
        np.testing.assert_allclose(lanes[g], x[g] + (sg[b] - sg[a]) * v[g],
                                   rtol=1e-6, atol=1e-6)
    # traced inline under a scan: the same update as the compiled program
    scanned = jax.lax.scan(
        lambda c, _: (sampler.step(sched, c, v, ts[2], ts[5]), None),
        x, None, length=1)[0]
    np.testing.assert_allclose(scanned, got, rtol=1e-6, atol=1e-6)


def test_generate_on_the_two_speed_grid(weights):
    x, _, _, cond = _inputs(cls=7)
    pipe = StadiPipeline(CFG, weights, sampler.FlowSchedule(shift=3.0),
                         StadiConfig.from_occupancies(
                             STADI["occupancies"], m_base=20, m_warmup=4))
    res = pipe.generate(jnp.asarray(x[None]), cond)
    assert list(res.plan.patches) == [5, 3]
    assert list(res.plan.temporal.steps) == [20, 12]
    want = REF.generate(weights, SIZES, STADI, x, 7)
    assert rel_l2(res.image[0], want) < TOL
    assert rel_l2(want, x) > 0.1              # the image moved


def _engine(pipe):
    from repro.serving.diffusion_engine import DiffusionServingEngine
    return DiffusionServingEngine(pipe, slots=2)


@pytest.mark.parametrize("knobs", [
    dict(backend="spmd"), dict(backend="spmd_guidance"),
    dict(backend="spmd_seq"), dict(backend="spmd_frames"),
    dict(backend="spmd_pipefuse"), dict(backend="pipefuse"),
    dict(seq_shards=2), dict(num_frames=2), dict(cfg_scale=4.0),
    "serving engine"], ids=str)
def test_out_of_scope_paths_raise_for_mmdit(knobs):
    """No path but the emulated executor runs the MMDiT family: each of the
    others says so, naming the family, before any DiT block could run on
    MMDiT weights."""
    occ = [0.0, 0.6]
    with pytest.raises(ValueError, match="'mmdit' family"):
        if knobs == "serving engine":
            pipe = StadiPipeline(CFG, None, sampler.FlowSchedule(shift=3.0),
                                 StadiConfig.from_occupancies(occ))
            _engine(pipe)
        else:
            StadiPipeline(CFG, None, sampler.FlowSchedule(shift=3.0),
                          StadiConfig.from_occupancies(occ, **knobs))
