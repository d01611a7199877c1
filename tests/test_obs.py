"""Profiler spans inside the program (repro.obs): with a trace running, one
tiny ``generate`` and three serving rounds write every span of their path,
properly nested, with the counters the benchmark's readers use (token rows
on each denoiser dispatch; real and padded lanes on each engine lane
group); images are bitwise the same with the profiler on and off."""
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.configs import get_config
from repro.core import sampler as sampler_lib
from repro.core.pipeline import StadiConfig, StadiPipeline
from repro.models.diffusion import dit
from repro.serving import DiffusionServingEngine

SRC = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))), "src", "repro")
SLOTS = 3


@pytest.fixture(scope="module")
def pipe():
    cfg = get_config("tiny-dit").reduced()
    params = dit.init_params(jax.random.PRNGKey(0), cfg)
    config = StadiConfig.from_occupancies([0.0, 0.5], m_base=6, m_warmup=2)
    return StadiPipeline(cfg, params, sampler_lib.linear_schedule(T=100),
                         config)


def _inputs(cfg, n):
    xs = [jax.random.normal(jax.random.PRNGKey(7 + i),
                            (1, cfg.latent_size, cfg.latent_size,
                             cfg.channels)) for i in range(n)]
    return xs, [jnp.asarray([i % cfg.n_classes], jnp.int32)
                for i in range(n)]


def _traced(path, fn):
    """Run ``fn`` under the profiler (the benchmark's options); returns its
    result and the program spans as (start, end, name, args)."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    opts.enable_hlo_proto = False
    jax.profiler.start_trace(str(path), profiler_options=opts)
    try:
        out = fn()
        jax.block_until_ready(out)
    finally:
        jax.profiler.stop_trace()
    from jax.profiler import ProfileData
    (xplane,) = path.glob("plugins/profile/*/*.xplane.pb")
    spans = []
    for plane in ProfileData.from_file(str(xplane)).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            spans += [(int(e.start_ns), int(e.end_ns), e.name, dict(e.stats))
                      for e in line.events if e.name in obs.SPANS]
    return out, sorted(spans)


def _parent(spans, child, names):
    """The innermost span named one of ``names`` that encloses ``child``."""
    a, b = child[0], child[1]
    outer = [s for s in spans if s is not child and s[2] in names
             and s[0] <= a and b <= s[1]]
    return max(outer, key=lambda s: s[0]) if outer else None


def _launches_in_sampler_spans(path):
    """The launches made inside each ``exec.sampler`` span of the trace
    under ``path``, counted as ``bench/program_trace.py`` counts them: a
    ``PjitFunction(...)`` event on the span's thread, once where a second
    one nests inside it."""
    from jax.profiler import ProfileData
    (xplane,) = path.glob("plugins/profile/*/*.xplane.pb")
    counts = []
    for plane in ProfileData.from_file(str(xplane)).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            events = [(int(e.start_ns), int(e.end_ns), e.name)
                      for e in line.events]
            calls, end = [], None
            for a, b, name in sorted(events):
                if name.startswith("PjitFunction") and (end is None
                                                        or a >= end):
                    calls.append(a)
                    end = b
            counts += [sum(a <= t < b for t in calls)
                       for a, b, name in events if name == "exec.sampler"]
    return counts


@pytest.mark.parametrize("path", ["generate", "engine"])
def test_each_sampler_span_is_one_launch(pipe, tmp_path, path):
    """Every DDIM update is one compiled program: in a traced ``generate``
    and in traced engine rounds (two warm-up rounds and an adaptive one)
    each ``exec.sampler`` span holds exactly one launch."""
    xs, conds = _inputs(pipe.model_cfg, 2)

    def fresh_run():
        if path == "generate":
            return lambda: pipe.generate(xs[0], conds[0]).image
        engine = DiffusionServingEngine(pipe, slots=SLOTS)
        for x, c in zip(xs, conds):
            engine.submit(x, c)
        return lambda: [engine.step() for _ in range(3)]

    fresh_run()()                        # compile outside the trace
    _traced(tmp_path, fresh_run())
    counts = _launches_in_sampler_spans(tmp_path)
    assert counts and counts == [1] * len(counts), counts


def test_every_span_in_the_source_is_declared():
    used = set()
    for root, _, files in os.walk(SRC):
        for f in files:
            if f.endswith(".py"):
                with open(os.path.join(root, f)) as fh:
                    used |= set(re.findall(r'obs\.span\(\s*"([^"]+)"',
                                           fh.read()))
    assert used == set(obs.SPANS)
    assert len(set(obs.SPANS)) == len(obs.SPANS)


def test_generate_spans_nest_and_image_is_bitwise(pipe, tmp_path):
    cfg = pipe.model_cfg
    (x,), (c,) = _inputs(cfg, 1)
    off = np.asarray(pipe.generate(x, c).image)
    res, spans = _traced(tmp_path, lambda: pipe.generate(x, c))
    np.testing.assert_array_equal(np.asarray(res.image), off)

    names = {s[2] for s in spans}
    assert names == {"stadi.generate", "stadi.plan", "exec.warmup",
                     "exec.interval", "exec.model", "exec.sampler",
                     "exec.buffers", "exec.exchange", "exec.record"}
    (root,) = [s for s in spans if s[2] == "stadi.generate"]
    assert all(root[0] <= s[0] and s[1] <= root[1] for s in spans)
    assert len([s for s in spans if s[2] == "stadi.plan"]) == 1
    steps = ("exec.warmup", "exec.interval")
    models = [s for s in spans if s[2] == "exec.model"]
    temporal = res.plan.temporal
    warm = [s for s in models if _parent(spans, s, steps)[2] == "exec.warmup"]
    assert len(warm) == temporal.m_warmup
    assert all(s[3]["rows"] == cfg.tokens_per_side for s in warm)
    adaptive = [r for r in res.trace.events if not r.synchronous]
    assert len(models) - len(warm) == sum(sum(r.substeps) for r in adaptive)
    assert {s[3]["rows"] for s in models} <= (set(res.plan.patches)
                                              | {cfg.tokens_per_side})
    # one lane a dispatch: no span of generate counts lanes
    assert all(s[3] == {} for s in spans if s[2] != "exec.model")
    for s in spans:
        if s[2] in ("exec.model", "exec.sampler", "exec.buffers"):
            assert _parent(spans, s, steps) is not None, s
    assert len([s for s in spans if s[2] == "exec.exchange"]) \
        == len(adaptive)
    assert len([s for s in spans if s[2] == "exec.record"]) == 1


def _serve_traced(pipe, tmp_path, slots, n):
    """Serve ``n`` requests through ``slots`` slots, the first three rounds
    traced, and again untraced; returns (images traced, images untraced,
    spans)."""
    xs, conds = _inputs(pipe.model_cfg, n)

    def serve(rounds_traced):
        engine = DiffusionServingEngine(pipe, slots=slots)
        for x, c in zip(xs, conds):
            engine.submit(x, c)
        spans = None
        if rounds_traced:
            _, spans = _traced(tmp_path, lambda: [engine.step()
                                                  for _ in range(3)])
        engine.run_to_completion()
        return [np.asarray(r.image) for r in engine.completed], spans

    off, _ = serve(False)
    on, spans = serve(True)
    return on, off, spans


def _lane_groups(spans):
    return [s for s in spans if s[2] in ("exec.warmup", "exec.interval")]


def test_engine_rounds_nest_and_count_padded_lanes(pipe, tmp_path):
    on, off, spans = _serve_traced(pipe, tmp_path, SLOTS, 2)
    assert len(on) == len(off) == 2
    for a, b in zip(on, off):
        np.testing.assert_array_equal(a, b)

    rounds = [s for s in spans if s[2] == "engine.round"]
    assert len(rounds) == 3
    names = {s[2] for s in spans}
    assert names == {"engine.round", "engine.admit",
                     "engine.lanes", "engine.cost_model", "engine.retire",
                     "exec.warmup", "exec.interval", "exec.model",
                     "exec.sampler", "exec.buffers"}
    for s in spans:
        if s[2] != "engine.round":
            assert _parent(spans, s, ("engine.round",)) is not None, s
    for name in ("engine.admit", "engine.retire"):
        assert len([s for s in spans if s[2] == name]) == 3
    # two warm-up rounds, then one adaptive interval, each one lane group;
    # two lanes run at the ladder's rung 3 (1, 3 at three slots)
    groups = _lane_groups(spans)
    assert [(_parent(spans, g, ("engine.round",)), g[2]) for g in groups] \
        == list(zip(rounds, ["exec.warmup", "exec.warmup", "exec.interval"]))
    assert all(g[3] == {"lanes": 2, "padded": 1} for g in groups)
    assert all(g[3]["lanes"] + g[3]["padded"] == SLOTS for g in groups)
    models = [s for s in spans if s[2] == "exec.model"]
    assert models and all(
        _parent(spans, s, ("exec.warmup", "exec.interval")) is not None
        and set(s[3]) == {"rows"} for s in models)


def test_engine_group_pads_to_its_rung(pipe, tmp_path):
    """Three lanes at four slots run at rung 4 of the ladder 1, 4: each
    lane group's span counts the one padded lane, the padding
    ``padded_row_share.serve`` reads."""
    on, off, spans = _serve_traced(pipe, tmp_path, 4, 3)
    assert len(on) == len(off) == 3
    for a, b in zip(on, off):
        np.testing.assert_array_equal(a, b)
    groups = _lane_groups(spans)
    assert [g[2] for g in groups] == ["exec.warmup", "exec.warmup",
                                      "exec.interval"]
    assert all(g[3] == {"lanes": 3, "padded": 1} for g in groups)
    models = [s for s in spans if s[2] == "exec.model"]
    assert models and all(
        _parent(spans, s, ("exec.warmup", "exec.interval")) is not None
        for s in models)
