"""Unified STADI pipeline: one config object, pluggable planners and
execution backends (DESIGN.md §8, §14).

    cfg    = get_config("tiny-dit").reduced()
    params = dit.init_params(jax.random.PRNGKey(0), cfg)
    sched  = sampler.linear_schedule(T=1000)
    config = StadiConfig.from_occupancies([0.0, 0.6], m_base=16, m_warmup=4)
    pipe   = StadiPipeline(cfg, params, sched, config)
    result = pipe.generate(x_T, cond)          # result.image, result.trace

``StadiConfig`` captures the cluster (``DeviceProfile``s), the schedule knobs
(Eq. 4 / Eq. 5 parameters), the planner name and the backend name.
Planners live in :mod:`repro.core.planners`; backends are registered here:

    "emulated"  exact-numerics logical-worker engine (patch_parallel)
    "spmd"      real shard_map execution over jax.devices() (core/spmd)
    "simulate"  trace-only latency modeling (no numerics; needs a CostModel)

``StadiPipeline.plan()`` is the ONE planning entrypoint: it runs the
configured planner and returns a fully-populated five-axis
:class:`~repro.core.planners.ExecutionPlan` (steps x patches x stages x
guidance x seq) in a single pass — the ``--num-stages`` / ``--cfg-scale`` /
``--seq-shards`` config wiring is resolved onto the plan there, not at
execution time. The historical ``plan_stages`` / ``plan_guidance`` /
``plan_seq`` free functions survive as deprecation shims. With
``plan_cache_dir`` set, ``plan()`` consults a persistent
:class:`~repro.serving.plan_cache.PlanCache` before any planner search
(DESIGN.md §14).

Backends declare what they can execute at registration time —
``register_executor(name, supports={...}, requires={...})`` — and
:func:`check_backend_can_run` rejects plan/backend mismatches uniformly
from that declaration, so a new executor cannot silently skip gating.

``rebalance_every=k`` turns on online rebalancing (emulated backend): every k
adaptive intervals the measured per-device interval latencies are fed through
:class:`repro.core.hetero.OnlineProfiler`, and when the EWMA speed estimate
drifts past ``rebalance_threshold`` the remaining fine steps are re-planned
with the configured planner. In this single-host emulation "measured" latency
is synthesized from the cost model at ``measured_speeds`` (the ground-truth
speeds the run actually experiences, e.g. after an occupancy change).
"""
from __future__ import annotations

import dataclasses
import hashlib
import inspect
import os
import warnings
from typing import Callable, Dict, List, Optional, Protocol, Sequence, Tuple

from repro import obs
from repro.configs.diffusion import DiTConfig
from repro.core import hetero
from repro.core import patch_parallel as pp
from repro.core import simulate as sim
from repro.core.hetero import DeviceProfile
from repro.core.patch_parallel import ExecutionTrace
from repro.core.planners import ExecutionPlan, get_planner
from repro.core.sampler import FlowSchedule, NoiseSchedule
from repro.core.simulate import CostModel


@dataclasses.dataclass(frozen=True)
class StadiConfig:
    """Everything STADI needs to know that is not the model or the input."""
    cluster: Tuple[DeviceProfile, ...]
    # schedule knobs (paper §IV, Eq. 4 / Eq. 5)
    m_base: int = 16
    m_warmup: int = 4
    a: float = 0.75
    b: float = 0.25
    tiers: Tuple[int, ...] = (1, 2)
    granularity: int = 1
    min_patch: Optional[int] = None
    # strategy selection
    planner: str = "stadi"
    backend: str = "emulated"
    # boundary-exchange policy (DESIGN.md §10): "sync" | "stale_async" |
    # "predictive"; exchange_refresh = E => one corrective full refresh
    # every E interval boundaries (ignored by "sync")
    exchange: str = "sync"
    exchange_refresh: int = 2
    # displaced patch pipeline (DESIGN.md §11): number of depth stages the
    # DiT block stack is split into (1 = no depth parallelism; 0 = let the
    # stadi_pipefuse planner search). micro_patches pins the micro-batch
    # count streaming through the stage chain (0 = auto). depth is the DiT
    # block count — StadiPipeline fills it in from the model config.
    num_stages: int = 1
    micro_patches: int = 0
    depth: Optional[int] = None
    # classifier-free guidance (DESIGN.md §12): cfg_scale > 0 turns every
    # generation into a guided one (eps = eps_u + w*(eps_c - eps_u));
    # guidance picks the placement — "none" defaults to "fused" when
    # cfg_scale is set, or lets the stadi_guidance planner auto-search.
    # "split"/"interleaved" placement requires planner="stadi_guidance"
    # (logical workers become cond/uncond device pairs); uncond_refresh is
    # the interleaved reuse cadence. latent_bytes / kv_row_bytes are byte
    # provenance for the guided planner cost model — StadiPipeline fills
    # them in from the model config (leave 0).
    guidance: str = "none"
    cfg_scale: float = 0.0
    uncond_refresh: int = 2
    latent_bytes: int = 0
    kv_row_bytes: int = 0
    # sequence-parallel attention (DESIGN.md §13): number of Ulysses/ring
    # shards each patch worker's attention is split across (1 = attention-
    # unsharded; 0 = let the stadi_seq planner search). n_heads is the
    # attention head count the seq planner scatters — StadiPipeline fills
    # it in from the model config (leave None).
    seq_shards: int = 1
    n_heads: Optional[int] = None
    # video / multi-frame diffusion (DESIGN.md §16): number of latent
    # frames denoised jointly (1 = image — every path is bitwise the
    # pre-frame pipeline). frame_groups picks the placement: 1 =
    # frame-sequential (every worker runs all frames), > 1 = frame-
    # parallel member rows (requires planner='stadi_video'), 0 = let the
    # stadi_video planner search.
    num_frames: int = 1
    frame_groups: int = 0
    # prompt conditioning (DESIGN.md §17): length bucket of the prompt-token
    # sequence the planner prices (CostModel.t_xattn per token read). 0 =
    # derive from the model config (cond_seq_len when cross_attn, else
    # unconditioned/class-conditioned — no cross-attention cost). Setting it
    # explicitly pins the serving bucket a cached plan is keyed under.
    cond_bucket: int = 0
    # run the Pallas stale-KV attention kernel (repro.kernels) inside the
    # DiT blocks instead of the reference buffer-rewrite attend — the
    # fused freshness-select hot path (interpret mode off-TPU)
    use_pallas_attention: bool = False
    # latency modeling ("simulate" backend; also latency reporting elsewhere)
    cost_model: Optional[CostModel] = None
    # online rebalancing (beyond-paper, DESIGN.md §7.1)
    rebalance_every: int = 0             # adaptive intervals between checks; 0 = off
    rebalance_threshold: float = 0.2     # max relative speed drift tolerated
    profiler_alpha: float = 0.5          # EWMA weight for OnlineProfiler
    # persistent plan cache (DESIGN.md §14): directory for serialized
    # planner outputs keyed by (cluster signature, model hash, workload
    # shape). None = no cache; StadiPipeline.plan() consults it before any
    # planner search and OnlineProfiler drift invalidates stale entries.
    plan_cache_dir: Optional[str] = None

    @classmethod
    def from_occupancies(cls, occupancies: Sequence[float],
                         capabilities: Optional[Sequence[float]] = None,
                         **knobs) -> "StadiConfig":
        """Paper's experimental grid: homogeneous GPUs + per-device occupancy."""
        cluster = tuple(hetero.make_cluster(occupancies, capabilities))
        return cls(cluster=cluster, **knobs)

    @property
    def speeds(self) -> List[float]:
        return [d.v for d in self.cluster]

    @property
    def n_devices(self) -> int:
        return len(self.cluster)


@dataclasses.dataclass
class ReplanEvent:
    """One online re-allocation (fine-step granularity provenance)."""
    fine_step: int
    drift: float
    speeds_before: List[float]
    speeds_after: List[float]
    plan: ExecutionPlan


@dataclasses.dataclass
class PipelineResult:
    """What ``StadiPipeline.generate`` returns, for every backend.

    image is None for the trace-only "simulate" backend; latency_s is None
    unless a cost model was configured.
    """
    image: Optional[object]
    trace: ExecutionTrace
    plan: ExecutionPlan
    latency_s: Optional[float] = None
    replans: List[ReplanEvent] = dataclasses.field(default_factory=list)
    #: Pallas kernel path hits/misses recorded while TRACING this call
    #: ({"hits": {kind: n}, "misses": {reason: n}}) — jit caching means a
    #: repeat call with cached traces legitimately reports {} (§15).
    kernel_stats: Dict = dataclasses.field(default_factory=dict)


class Executor(Protocol):
    """A backend: executes an ExecutionPlan, returns (image | None, trace)."""

    def __call__(self, params, model_cfg: DiTConfig, sched: NoiseSchedule,
                 x_T, cond, plan: ExecutionPlan, config: StadiConfig,
                 interval_hook=None) -> Tuple[Optional[object], ExecutionTrace]:
        ...


# ----------------------------------------------------------------------
# executor registry: declarative backend capabilities (DESIGN.md §14)
# ----------------------------------------------------------------------

#: the ONE normalized executor call signature — StadiPipeline invokes every
#: backend strictly by these keywords, and register_executor rejects any
#: executor whose signature spells them differently (the historical
#: per-backend kwarg drift cannot re-enter the registry)
EXECUTOR_KWARGS = ("params", "model_cfg", "sched", "x_T", "cond", "plan",
                   "config", "interval_hook")

#: every feature token a plan can demand from a backend
PLAN_FEATURES = ("stages", "guidance.fused", "guidance.split",
                 "guidance.interleaved", "seq", "seq.uneven", "frames")

#: valid ``requires=`` tokens: a concrete feature, or a bare axis prefix
#: ("guidance", "seq") satisfied by any mode of that axis
_REQUIRE_PREFIXES = ("guidance", "seq", "stages", "frames")


@dataclasses.dataclass(frozen=True)
class BackendSpec:
    """One registered executor plus its declared capabilities.

    supports: feature tokens (from :data:`PLAN_FEATURES`) the backend can
        execute; a plan demanding anything else is rejected uniformly by
        :func:`check_backend_can_run`.
    requires: tokens the backend NEEDS a plan to demand (e.g. the
        "spmd_guidance" mesh is meaningless without a guided plan).
    """
    fn: Executor
    supports: frozenset
    requires: frozenset


EXECUTORS: Dict[str, BackendSpec] = {}


def register_executor(name: str, *, supports: Sequence[str] = (),
                      requires: Sequence[str] = ()
                      ) -> Callable[[Executor], Executor]:
    supports_f = frozenset(supports)
    requires_f = frozenset(requires)
    bad = (supports_f - set(PLAN_FEATURES)) | \
        (requires_f - set(PLAN_FEATURES) - set(_REQUIRE_PREFIXES))
    if bad:
        raise ValueError(f"executor {name!r} declares unknown capability "
                         f"tokens {sorted(bad)}; known: {PLAN_FEATURES}")

    def deco(fn: Executor) -> Executor:
        sig = tuple(inspect.signature(fn).parameters)
        if sig != EXECUTOR_KWARGS:
            raise TypeError(
                f"executor {name!r} must accept exactly the normalized "
                f"kwargs {EXECUTOR_KWARGS}, got {sig}")
        EXECUTORS[name] = BackendSpec(fn, supports_f, requires_f)
        return fn
    return deco


def get_executor_spec(name: str) -> BackendSpec:
    try:
        return EXECUTORS[name]
    except KeyError:
        raise KeyError(f"unknown backend {name!r}; registered: "
                       f"{sorted(EXECUTORS)}") from None


def get_executor(name: str) -> Executor:
    return get_executor_spec(name).fn


def backends_supporting(feature: str) -> Tuple[str, ...]:
    """All registered backends whose declaration covers ``feature`` (a
    token from :data:`PLAN_FEATURES`, or a bare axis prefix matching any
    mode, e.g. "guidance")."""
    def covers(spec: BackendSpec) -> bool:
        return any(f == feature or f.startswith(feature + ".")
                   for f in spec.supports)
    return tuple(sorted(n for n, s in EXECUTORS.items() if covers(s)))


# ----------------------------------------------------------------------
# serving hooks: round-granular steppers for continuous batching
# ----------------------------------------------------------------------
#
# An Executor runs one whole generation; the diffusion serving engine
# (repro.serving.diffusion_engine) instead drives MANY in-flight requests one
# scheduling round at a time, so each backend that supports serving also
# registers a *stepper factory*: ``factory(pipeline, plan, slots) -> Stepper``
# where a Stepper exposes
#
#     warmup_step(xs, t_from, t_to, conds) -> (xs', pub_k, pub_v)
#     interval(xs, fine0, conds, pub_k, pub_v) -> (xs', pub_k', pub_v')
#     cohort_only: bool    # True => every lane of interval() shares fine0
#
# over lane-stacked state (leading axis = slot lane). The "emulated" stepper
# vmaps the denoiser so lanes at different noise-schedule positions share one
# dispatch; the "spmd" stepper shard_maps each interval across jax.devices().

STEPPER_FACTORIES: Dict[str, Callable] = {}


def register_stepper_factory(name: str) -> Callable:
    def deco(fn):
        STEPPER_FACTORIES[name] = fn
        return fn
    return deco


def get_stepper_factory(name: str):
    try:
        return STEPPER_FACTORIES[name]
    except KeyError:
        raise KeyError(
            f"backend {name!r} has no serving stepper; registered: "
            f"{sorted(STEPPER_FACTORIES)} (the 'simulate' backend has no "
            "numerics to serve)") from None


# ----------------------------------------------------------------------
# plan-axis resolution: config knobs -> plan fields (DESIGN.md §14)
# ----------------------------------------------------------------------
#
# StadiPipeline.plan() populates all five axes onto the ExecutionPlan in
# one pass via these private resolvers; executors read plan.stages /
# plan.guidance / plan.seq directly. The historical plan_stages /
# plan_guidance / plan_seq free functions below are deprecation shims.

def _resolve_stages(plan, model_cfg, config) -> Optional[List[int]]:
    """The stage split a staged executor should run: the plan's own (from
    the stadi_pipefuse planner) or, for plain planners, a speed-
    proportional split of config.num_stages (the --num-stages wiring)."""
    if plan.stages is not None:
        return list(plan.stages)
    if config.num_stages <= 1:
        return None
    if config.num_stages > config.n_devices:
        raise ValueError(
            f"num_stages={config.num_stages} is infeasible: the chain needs "
            f"one device per stage and the cluster has {config.n_devices} "
            "(the stadi_pipefuse planner rejects this identically)")
    chain = sim.chain_speeds(config.speeds, config.num_stages)
    return hetero.stage_partition(model_cfg.n_layers, chain)


def _resolve_seq(plan, model_cfg, config):
    """The SeqPlan an executor should run: the plan's own (from the
    stadi_seq planner) or, for plain planners with ``seq_shards > 1``, a
    uniform-shard plan (the --seq-shards wiring). None = attention-
    unsharded."""
    if plan.seq is not None and len(plan.seq.segments) > 1:
        return plan.seq
    S = config.seq_shards
    if S in (0, 1):
        return None
    from repro.core import seqpar
    if S > config.n_devices:
        raise ValueError(
            f"seq_shards={S} is infeasible: every patch-worker group needs "
            f"one device per sequence shard and the cluster has "
            f"{config.n_devices} (the stadi_seq planner rejects this "
            "identically)")
    if model_cfg.n_heads < S:
        raise ValueError(
            f"seq_shards={S} cannot scatter {model_cfg.n_heads} attention "
            "heads (Ulysses needs >= 1 head per shard)")
    return seqpar.make_seq_plan(model_cfg.n_heads, model_cfg.tokens_per_side,
                                S)


def _resolve_frames(plan, config):
    """The FramePlan an executor should run: the plan's own (from the
    stadi_video planner) or, for plain planners with ``num_frames > 1``,
    the frame-sequential placement (the --num-frames wiring: every patch
    worker evaluates all frames). None = single-frame image path."""
    if plan.frames is not None and plan.frames.num_frames > 1:
        return plan.frames
    F = config.num_frames
    if F <= 1:
        return None
    from repro.core import frames as frames_lib
    if config.frame_groups > 1:
        raise ValueError(
            f"frame_groups={config.frame_groups} places frame chunks on "
            "device member rows — plan it with planner='stadi_video' "
            f"(planner {config.planner!r} allocates per-device workers)")
    return frames_lib.FramePlan(F, (F,))


def _resolve_guidance(plan, config):
    """The GuidancePlan an executor should run: the plan's own (from the
    stadi_guidance planner) or, for plain planners with ``cfg_scale`` set,
    a fused-placement plan (the --cfg-scale wiring). None = unguided."""
    if plan.guidance is not None:
        return plan.guidance
    if config.cfg_scale <= 0.0 and config.guidance == "none":
        return None
    from repro.core.guidance import GuidancePlan
    if config.guidance in ("split", "interleaved"):
        raise ValueError(
            f"guidance={config.guidance!r} placement pairs devices across "
            "branch groups — plan it with planner='stadi_guidance' "
            f"(planner {config.planner!r} allocates per-device workers)")
    if config.cfg_scale <= 0.0:
        raise ValueError(f"guidance={config.guidance!r} needs cfg_scale > 0")
    return GuidancePlan("fused", config.cfg_scale)


def _deprecated(old: str, new: str) -> None:
    warnings.warn(f"{old} is deprecated; {new}", DeprecationWarning,
                  stacklevel=3)


def plan_stages(plan, model_cfg, config) -> Optional[List[int]]:
    """Deprecated: ``StadiPipeline.plan()`` populates ``plan.stages``."""
    _deprecated("plan_stages()",
                "StadiPipeline.plan() returns a fully-populated plan — "
                "read plan.stages")
    return _resolve_stages(plan, model_cfg, config)


def plan_seq(plan, model_cfg, config):
    """Deprecated: ``StadiPipeline.plan()`` populates ``plan.seq``."""
    _deprecated("plan_seq()",
                "StadiPipeline.plan() returns a fully-populated plan — "
                "read plan.seq")
    return _resolve_seq(plan, model_cfg, config)


def plan_guidance(plan, config):
    """Deprecated: ``StadiPipeline.plan()`` populates ``plan.guidance``."""
    _deprecated("plan_guidance()",
                "StadiPipeline.plan() returns a fully-populated plan — "
                "read plan.guidance")
    return _resolve_guidance(plan, config)


# ----------------------------------------------------------------------
# uniform plan/backend gating from the capability declarations
# ----------------------------------------------------------------------

def required_features(plan, config) -> Tuple[List[str], Optional[object]]:
    """Feature tokens a (plan, config) pair demands of a backend, in the
    deterministic check order (stages, guidance, seq, frames), plus the
    resolved GuidancePlan (None = unguided)."""
    feats: List[str] = []
    if plan.stages is not None and len(plan.stages) > 1:
        feats.append("stages")
    gplan = _resolve_guidance(plan, config)
    if gplan is not None:
        feats.append("guidance." + gplan.mode)
    seq_sharded = ((plan.seq is not None and len(plan.seq.segments) > 1)
                   or config.seq_shards > 1)
    if seq_sharded:
        feats.append("seq")
        if (plan.seq is not None and len(plan.seq.segments) > 1
                and not plan.seq.even_heads()):
            feats.append("seq.uneven")
    framed = ((plan.frames is not None and plan.frames.num_frames > 1)
              or config.num_frames > 1)
    if framed:
        feats.append("frames")
    return feats, gplan


#: per-(backend, feature) rejection messages more specific than the
#: generic capability complaint — kept at least as pointed as the historic
#: if-chain's (tested); format fields: mode, scale, backend, stages, heads
_BACKEND_FEATURE_ERRORS: Dict[Tuple[str, str], str] = {
    ("spmd", "guidance.split"):
        "{mode!r} guidance on SPMD needs the guidance mesh axis: use "
        "backend='spmd_guidance'",
    ("spmd", "guidance.interleaved"):
        "{mode!r} guidance on SPMD needs the guidance mesh axis: use "
        "backend='spmd_guidance'",
    ("spmd_guidance", "guidance.fused"):
        "backend 'spmd_guidance' runs the split guidance mesh; fused CFG "
        "runs on the plain 'spmd' backend",
    ("spmd_guidance", "guidance.interleaved"):
        "interleaved uncond reuse is not implemented on SPMD; use the "
        "'emulated' or 'pipefuse' backend",
    ("spmd_seq", "seq.uneven"):
        "spmd_seq needs an even head scatter for the all-to-all (got "
        "{heads}); speed-proportional uneven heads are the cost model's "
        "planning view — run uneven plans on the 'emulated' backend, or "
        "pin seq_shards to a divisor of n_heads",
}

#: messages for a backend whose ``requires`` declaration is unmet
_BACKEND_REQUIRES_ERRORS: Dict[Tuple[str, str], str] = {
    ("spmd_guidance", "guidance"):
        "backend 'spmd_guidance' needs a guided plan: set cfg_scale > 0 "
        "with planner='stadi_guidance' and guidance='split'",
    ("spmd_seq", "seq"):
        "backend 'spmd_seq' runs the sequence mesh and needs a "
        "seq-sharded plan: set seq_shards > 1, or planner='stadi_seq' "
        "with seq_shards=0 (auto); an attention-unsharded plan runs on "
        "the plain 'spmd' backend",
    ("spmd_frames", "frames"):
        "backend 'spmd_frames' runs the frame mesh and needs a "
        "multi-frame plan: set num_frames > 1 (optionally "
        "planner='stadi_video' for the frame-parallel placement); a "
        "single-frame plan runs on the plain 'spmd' backend",
}


def _reject_message(backend: str, feature: str, plan, gplan) -> str:
    heads = list(plan.seq.heads) if plan.seq is not None else None
    override = _BACKEND_FEATURE_ERRORS.get((backend, feature))
    if override is not None:
        return override.format(
            mode=getattr(gplan, "mode", None),
            scale=getattr(gplan, "scale", None),
            backend=backend, stages=plan.stages, heads=heads)
    if feature == "stages":
        return (f"the planned stage split {plan.stages} needs a staged "
                f"backend ({list(backends_supporting('stages'))}), not "
                f"{backend!r}; pin num_stages=1 to force pure patch "
                "parallelism")
    if feature.startswith("guidance."):
        return (f"guided generation (cfg_scale={gplan.scale}) needs a "
                f"guided backend ({list(backends_supporting('guidance'))}), "
                f"not {backend!r}")
    if feature == "seq":
        return (f"a sequence-sharded plan (seq_shards > 1) needs a seq "
                f"backend ({list(backends_supporting('seq'))}), not "
                f"{backend!r}; pin seq_shards=1 to force attention-"
                "unsharded execution")
    if feature == "frames":
        return (f"a multi-frame plan (num_frames > 1) needs a frame "
                f"backend ({list(backends_supporting('frames'))}), not "
                f"{backend!r}; pin num_frames=1 for the image path")
    return (f"{backend!r} does not support the planned {feature!r} "
            f"(supported by {list(backends_supporting(feature))})")


def check_backend_can_run(plan, config) -> None:
    """Reject plan/backend mismatches from the capability declarations.

    A staged plan silently degrades to whole-model patch parallelism on a
    non-staged backend (while staged costs/placements get reported), so
    fail fast — reachable via planner='stadi_pipefuse', num_stages=0
    (auto) picking a pipeline on backend='emulated'. Every demanded
    feature must be in the backend's ``supports``; every backend
    ``requires`` token must be demanded by the plan.
    """
    spec = get_executor_spec(config.backend)
    feats, gplan = required_features(plan, config)
    for f in feats:
        if f not in spec.supports:
            raise ValueError(_reject_message(config.backend, f, plan, gplan))
    for req in spec.requires:
        if not any(f == req or f.startswith(req + ".") for f in feats):
            msg = _BACKEND_REQUIRES_ERRORS.get((config.backend, req))
            raise ValueError(msg or f"backend {config.backend!r} requires "
                             f"a plan demanding {req!r}")


# ----------------------------------------------------------------------
# registered executors
# ----------------------------------------------------------------------

@register_executor("emulated", supports={"guidance.fused", "guidance.split",
                                         "guidance.interleaved", "seq",
                                         "seq.uneven", "frames"})
def emulated_executor(params, model_cfg, sched, x_T, cond, plan, config,
                      interval_hook=None):
    fplan = plan.frames
    if fplan is not None and fplan.num_frames > 1:
        # the multi-frame interpreter (DESIGN.md §16); fused CFG composes
        # with the frame axis (§17) — split/interleaved guidance and seq
        # sharding are rejected at pipeline construction
        from repro.core import frames as frames_lib
        res = frames_lib.run_frames(params, model_cfg, sched, x_T, cond,
                                    plan.temporal, plan.patches,
                                    interval_hook=interval_hook,
                                    exchange=config.exchange,
                                    exchange_refresh=config.exchange_refresh,
                                    frames=fplan,
                                    guidance=plan.guidance)
        return res.image, res.trace
    res = pp.run_schedule(params, model_cfg, sched, x_T, cond,
                          plan.temporal, plan.patches,
                          interval_hook=interval_hook,
                          exchange=config.exchange,
                          exchange_refresh=config.exchange_refresh,
                          guidance=plan.guidance,
                          seq=plan.seq)
    return res.image, res.trace


@register_executor("spmd", supports={"guidance.fused"})
def spmd_executor(params, model_cfg, sched, x_T, cond, plan, config,
                  interval_hook=None):
    # interval_hook is never passed here: generate() rejects rebalancing on
    # non-emulated backends (the shard_map program is static)
    from repro.core import spmd
    img = spmd.run_spmd(params, model_cfg, sched, x_T, cond,
                        plan.temporal, plan.patches,
                        exchange=config.exchange,
                        exchange_refresh=config.exchange_refresh,
                        guidance=plan.guidance)
    trace = sim.build_trace(plan.temporal, plan.patches, model_cfg,
                            batch=int(x_T.shape[0]),
                            exchange=config.exchange,
                            exchange_refresh=config.exchange_refresh,
                            guidance=plan.guidance)
    return img, trace


@register_executor("spmd_guidance", supports={"guidance.split"},
                   requires={"guidance"})
def spmd_guidance_executor(params, model_cfg, sched, x_T, cond, plan,
                           config, interval_hook=None):
    """Split-CFG over a ("guide", "dev") shard_map mesh (DESIGN.md §12):
    axis "guide" carries the cond/uncond branch groups, axis "dev" the
    patch workers of each group; needs 2 * n_pairs devices."""
    from repro.core import spmd
    img = spmd.run_spmd_guidance(params, model_cfg, sched, x_T, cond,
                                 plan.temporal, plan.patches, plan.guidance,
                                 exchange=config.exchange,
                                 exchange_refresh=config.exchange_refresh)
    trace = sim.build_trace(plan.temporal, plan.patches, model_cfg,
                            batch=int(x_T.shape[0]),
                            exchange=config.exchange,
                            exchange_refresh=config.exchange_refresh,
                            guidance=plan.guidance)
    return img, trace


@register_executor("simulate", supports=PLAN_FEATURES)
def simulate_executor(params, model_cfg, sched, x_T, cond, plan, config,
                      interval_hook=None):
    batch = int(x_T.shape[0]) if x_T is not None else 1
    trace = sim.build_trace(plan.temporal, plan.patches, model_cfg,
                            batch=batch, exchange=config.exchange,
                            exchange_refresh=config.exchange_refresh,
                            stages=plan.stages,
                            guidance=plan.guidance,
                            seq=plan.seq,
                            frames=plan.frames,
                            cond_tokens=(config.cond_bucket or None))
    return None, trace


@register_executor("spmd_seq", supports={"seq"}, requires={"seq"})
def spmd_seq_executor(params, model_cfg, sched, x_T, cond, plan, config,
                      interval_hook=None):
    """Sequence-parallel SPMD over a ("seq", "dev") shard_map mesh
    (DESIGN.md §13): axis "seq" carries the Ulysses/ring members of every
    patch-worker group; needs seq_shards * n_workers devices."""
    from repro.core import spmd
    splan = plan.seq
    if splan is None:
        raise ValueError(
            "backend 'spmd_seq' runs the sequence mesh and needs a "
            "seq-sharded plan: set seq_shards > 1, or planner='stadi_seq' "
            "with seq_shards=0 (auto); an attention-unsharded plan runs on "
            "the plain 'spmd' backend")
    if plan.guidance is not None:
        raise ValueError("guided generation is not implemented on the "
                         "'spmd_seq' backend; the 'emulated' backend runs "
                         "seq x CFG numerics")
    img = spmd.run_spmd_seq(params, model_cfg, sched, x_T, cond,
                            plan.temporal, plan.patches, splan,
                            exchange=config.exchange,
                            exchange_refresh=config.exchange_refresh)
    trace = sim.build_trace(plan.temporal, plan.patches, model_cfg,
                            batch=int(x_T.shape[0]),
                            exchange=config.exchange,
                            exchange_refresh=config.exchange_refresh,
                            seq=splan)
    return img, trace


@register_executor("spmd_frames", supports={"frames"}, requires={"frames"})
def spmd_frames_executor(params, model_cfg, sched, x_T, cond, plan, config,
                         interval_hook=None):
    """Multi-frame SPMD over a ("frame", "dev") shard_map mesh (DESIGN.md
    §16): axis "frame" carries the group-member rows of the frame
    partition, axis "dev" the patch-worker columns of each row; needs
    n_groups * n_workers devices."""
    from repro.core import spmd
    fplan = plan.frames
    if fplan is None or fplan.num_frames <= 1:
        raise ValueError(
            "backend 'spmd_frames' runs the frame mesh and needs a "
            "multi-frame plan: set num_frames > 1 (optionally "
            "planner='stadi_video' for the frame-parallel placement); a "
            "single-frame plan runs on the plain 'spmd' backend")
    img = spmd.run_spmd_frames(params, model_cfg, sched, x_T, cond,
                               plan.temporal, plan.patches, fplan,
                               exchange=config.exchange,
                               exchange_refresh=config.exchange_refresh)
    trace = sim.build_trace(plan.temporal, plan.patches, model_cfg,
                            batch=int(x_T.shape[0]),
                            exchange=config.exchange,
                            exchange_refresh=config.exchange_refresh,
                            frames=fplan)
    return img, trace


@register_executor("pipefuse", supports={"stages", "guidance.fused",
                                         "guidance.split",
                                         "guidance.interleaved"})
def pipefuse_executor(params, model_cfg, sched, x_T, cond, plan, config,
                      interval_hook=None):
    """Displaced patch pipeline (DESIGN.md §11): emulated interpreter;
    bitwise-identical to "emulated" when the stage count is 1."""
    from repro.core import pipefuse
    stages = plan.stages or [model_cfg.n_layers]
    res = pipefuse.run_pipefuse(params, model_cfg, sched, x_T, cond,
                                plan.temporal, plan.patches, stages,
                                exchange=config.exchange,
                                exchange_refresh=config.exchange_refresh,
                                interval_hook=interval_hook,
                                guidance=plan.guidance)
    return res.image, res.trace


@register_executor("spmd_pipefuse", supports={"stages"})
def spmd_pipefuse_executor(params, model_cfg, sched, x_T, cond, plan,
                           config, interval_hook=None):
    """Real shard_map stage chain over jax.devices() (devices = stages)."""
    from repro.core import spmd
    stages = plan.stages or [model_cfg.n_layers]
    img = spmd.run_spmd_pipefuse(params, model_cfg, sched, x_T, cond,
                                 plan.temporal, plan.patches, stages,
                                 exchange=config.exchange,
                                 exchange_refresh=config.exchange_refresh)
    trace = sim.build_trace(plan.temporal, plan.patches, model_cfg,
                            batch=int(x_T.shape[0]),
                            exchange=config.exchange,
                            exchange_refresh=config.exchange_refresh,
                            stages=stages)
    return img, trace


#: backends that can execute a depth-partitioned (staged) plan — derived
#: from the capability declarations, kept as module names for back-compat
STAGED_BACKENDS = backends_supporting("stages")

#: backends that can execute a sequence-sharded plan (DESIGN.md §13)
SEQ_BACKENDS = backends_supporting("seq")

#: backends that can execute a guided (classifier-free guidance) plan; the
#: mapping is mode-dependent — see check_backend_can_run
GUIDED_BACKENDS = backends_supporting("guidance")

#: backends that can execute a multi-frame (video) plan (DESIGN.md §16)
FRAME_BACKENDS = backends_supporting("frames")


def _env_use_pallas() -> bool:
    """STADI_USE_PALLAS=1 force-routes every pipeline through the Pallas
    kernel bodies (the CI kernel leg; combine with STADI_PALLAS_INTERPRET=1
    off-TPU)."""
    return os.environ.get("STADI_USE_PALLAS", "").strip() not in ("", "0")


class StadiPipeline:
    """One-call STADI inference: plan -> execute -> (optionally) rebalance.

    model_cfg/params/sched describe the denoiser; config describes the
    cluster and strategy. ``generate`` is the only entry point callers need;
    ``plan`` is the one planning entrypoint (a fully-populated five-axis
    ExecutionPlan, cached persistently when ``plan_cache_dir`` is set).
    """

    def __init__(self, model_cfg: DiTConfig, params, sched: NoiseSchedule,
                 config: StadiConfig):
        if config.use_pallas_attention or _env_use_pallas():
            # thread the kernel flag into the model config the executors'
            # jitted steps close over (DiTConfig is the static jit key).
            # STADI_USE_PALLAS=1 force-enables it process-wide — the CI
            # kernel leg runs the whole matrix through the Pallas bodies
            # without touching each test's config.
            model_cfg = model_cfg.replace(use_pallas_attention=True)
            config = dataclasses.replace(config, use_pallas_attention=True)
        self.model_cfg = model_cfg
        self.params = params
        self.sched = sched
        self.config = config
        get_planner(config.planner)      # fail fast on typos
        get_executor(config.backend)
        from repro.core.comm import get_exchange
        get_exchange(config.exchange, config.exchange_refresh)
        if config.num_stages < 0:
            raise ValueError(f"num_stages must be >= 0 (0 = auto), got "
                             f"{config.num_stages}")
        if config.num_stages > 1 and config.backend not in STAGED_BACKENDS:
            raise ValueError(
                f"num_stages={config.num_stages} needs a staged backend "
                f"({sorted(STAGED_BACKENDS)}), not {config.backend!r} — "
                "the displaced patch pipeline (DESIGN.md §11)")
        from repro.core.guidance import GUIDANCE_MODES
        if config.guidance != "none" and config.guidance not in GUIDANCE_MODES:
            raise ValueError(f"unknown guidance mode {config.guidance!r}; "
                             f"one of {('none',) + GUIDANCE_MODES}")
        if config.guidance != "none" and config.cfg_scale <= 0.0:
            raise ValueError(f"guidance={config.guidance!r} needs "
                             "cfg_scale > 0")
        guided = config.cfg_scale > 0.0 or config.guidance != "none"
        if guided and config.rebalance_every:
            raise ValueError("online rebalancing is not supported with "
                             "guidance (the branch pairing is static)")
        if config.seq_shards < 0:
            raise ValueError(f"seq_shards must be >= 0 (0 = auto), got "
                             f"{config.seq_shards}")
        if config.seq_shards > config.n_devices:
            raise ValueError(
                f"seq_shards={config.seq_shards} is infeasible: every "
                "patch-worker group needs one device per sequence shard "
                f"and the cluster has {config.n_devices}")
        if config.seq_shards > 1:
            if config.backend not in SEQ_BACKENDS:
                raise ValueError(
                    f"seq_shards={config.seq_shards} needs a seq backend "
                    f"({sorted(SEQ_BACKENDS)}), not {config.backend!r} — "
                    "sequence-parallel attention (DESIGN.md §13)")
            if model_cfg.n_heads < config.seq_shards:
                raise ValueError(
                    f"seq_shards={config.seq_shards} cannot scatter "
                    f"{model_cfg.n_heads} attention heads (Ulysses needs "
                    ">= 1 head per shard)")
            if config.rebalance_every:
                raise ValueError("online rebalancing is not supported with "
                                 "sequence sharding (the device grouping "
                                 "is static)")
        if config.num_frames < 1:
            raise ValueError(f"num_frames must be >= 1, got "
                             f"{config.num_frames}")
        if config.frame_groups < 0:
            raise ValueError(f"frame_groups must be >= 0 (0 = auto), got "
                             f"{config.frame_groups}")
        if config.num_frames > 1:
            if config.backend not in FRAME_BACKENDS:
                raise ValueError(
                    f"num_frames={config.num_frames} needs a frame backend "
                    f"({sorted(FRAME_BACKENDS)}), not {config.backend!r} — "
                    "multi-frame diffusion (DESIGN.md §16)")
            if config.frame_groups > config.num_frames:
                raise ValueError(
                    f"frame_groups={config.frame_groups} cannot split "
                    f"{config.num_frames} frames (>= 1 frame per group)")
            if config.frame_groups > config.n_devices:
                raise ValueError(
                    f"frame_groups={config.frame_groups} is infeasible: "
                    "every group-member row needs at least one device and "
                    f"the cluster has {config.n_devices}")
            if guided and config.guidance in ("split", "interleaved"):
                raise ValueError(
                    f"guidance={config.guidance!r} is not composed with "
                    "the frame axis: guided video runs FUSED classifier-"
                    "free guidance only (branch pairing and frame grouping "
                    "compete for the same devices) — use guidance='fused' "
                    "or guidance='none' with cfg_scale > 0")
            if config.seq_shards != 1:
                raise ValueError(
                    "sequence sharding is not composed with the frame axis "
                    "yet (ring groups and frame rows compete for the same "
                    "devices) — pin seq_shards=1 with num_frames > 1")
            if config.num_stages != 1:
                raise ValueError(
                    "the displaced patch pipeline is not composed with the "
                    "frame axis yet — pin num_stages=1 with num_frames > 1")
            if config.rebalance_every:
                raise ValueError("online rebalancing is not supported with "
                                 "the frame axis (the frame grouping is "
                                 "static)")
        elif config.frame_groups > 1:
            raise ValueError(f"frame_groups={config.frame_groups} needs "
                             "num_frames > 1 (there is only one frame to "
                             "place)")
        # prompt conditioning (DESIGN.md §17)
        if config.cond_bucket < 0:
            raise ValueError(f"cond_bucket must be >= 0 (0 = derive from "
                             f"the model config), got {config.cond_bucket}")
        if config.cond_bucket > 0 and not model_cfg.cross_attn:
            raise ValueError(
                f"cond_bucket={config.cond_bucket} prices prompt-token "
                "cross-attention but the model has cross_attn=False — "
                "use DiTConfig.text_conditioned()")
        if config.cond_bucket > model_cfg.cond_seq_len:
            raise ValueError(
                f"cond_bucket={config.cond_bucket} exceeds the model's "
                f"cond_seq_len={model_cfg.cond_seq_len} (the encoder "
                "never emits a longer prompt bucket)")
        # MMDiT and the flow sampler (DESIGN.md §18) run on the emulated
        # executor (and the trace-only simulator) alone
        if model_cfg.family == "mmdit" or isinstance(sched, FlowSchedule):
            what = (f"the {model_cfg.family!r} family"
                    if model_cfg.family == "mmdit"
                    else "the flow-matching sampler")
            for bad, name in (
                    (config.backend not in ("emulated", "simulate"),
                     f"backend {config.backend!r}"),
                    (guided, "classifier-free guidance"),
                    (config.seq_shards != 1, "sequence sharding"),
                    (config.num_frames != 1, "the frame axis"),
                    (config.num_stages != 1, "the displaced patch pipeline")):
                if bad:
                    raise ValueError(
                        f"{what} runs on the 'emulated' backend without "
                        f"guidance, sequence sharding, frames or stages; "
                        f"not with {name}")
        # persistent plan cache (DESIGN.md §14)
        self.plan_cache = None
        self.last_plan_key: Optional[str] = None
        #: live planner searches actually executed (cache hits skip these)
        self.planner_calls = 0
        #: cumulative Pallas kernel path hits/misses traced by this
        #: pipeline's generate() calls (per-call deltas land on each
        #: PipelineResult.kernel_stats)
        self.kernel_stats: Dict[str, Dict[str, int]] = {"hits": {},
                                                        "misses": {}}
        if config.plan_cache_dir:
            from repro.serving.plan_cache import PlanCache
            self.plan_cache = PlanCache(config.plan_cache_dir)

    @property
    def p_total(self) -> int:
        return self.model_cfg.tokens_per_side

    # ------------------------------------------------------------------
    # planning: the ONE entrypoint (steps x patches x stages x guidance
    # x seq resolved in a single pass)
    # ------------------------------------------------------------------

    def _plan_knobs(self) -> StadiConfig:
        """The config with model-derived provenance filled in (depth, head
        count, byte sizes) — what planners actually see."""
        knobs = self.config
        if knobs.depth is None:          # stage planning needs the DiT depth
            knobs = dataclasses.replace(knobs, depth=self.model_cfg.n_layers)
        if knobs.n_heads is None:        # seq planning needs the head count
            knobs = dataclasses.replace(knobs,
                                        n_heads=self.model_cfg.n_heads)
        if knobs.latent_bytes == 0:      # guided planning needs byte sizes
            cfg = self.model_cfg
            knobs = dataclasses.replace(
                knobs,
                latent_bytes=int(cfg.latent_size ** 2 * cfg.channels * 4),
                kv_row_bytes=int(2 * cfg.n_layers * cfg.tokens_per_side
                                 * cfg.d_model * 2))
        if knobs.cond_bucket == 0 and self.model_cfg.cross_attn:
            # prompt planning prices the full cond_seq_len unless a
            # serving bucket pins a shorter one (DESIGN.md §17)
            knobs = dataclasses.replace(
                knobs, cond_bucket=self.model_cfg.cond_seq_len)
        return knobs

    def _model_key(self) -> str:
        """Content hash of the model config (DiTConfig is a frozen
        dataclass, so its repr is a deterministic fingerprint)."""
        return hashlib.sha256(repr(self.model_cfg).encode()).hexdigest()[:16]

    def _workload_key(self, knobs: StadiConfig) -> Dict:
        """The workload-shape component of the plan-cache key: every knob
        that changes what the planner returns (resolution enters through
        p_total / byte provenance, steps through m_base)."""
        cm = knobs.cost_model
        return {
            "planner": knobs.planner,
            "p_total": self.p_total,
            "m_base": knobs.m_base, "m_warmup": knobs.m_warmup,
            "a": knobs.a, "b": knobs.b, "tiers": list(knobs.tiers),
            "granularity": knobs.granularity, "min_patch": knobs.min_patch,
            "exchange": knobs.exchange,
            "exchange_refresh": knobs.exchange_refresh,
            "num_stages": knobs.num_stages,
            "micro_patches": knobs.micro_patches, "depth": knobs.depth,
            "guidance": knobs.guidance, "cfg_scale": knobs.cfg_scale,
            "uncond_refresh": knobs.uncond_refresh,
            "latent_bytes": knobs.latent_bytes,
            "kv_row_bytes": knobs.kv_row_bytes,
            "seq_shards": knobs.seq_shards, "n_heads": knobs.n_heads,
            # frame axis (DESIGN.md §16): a cached image plan must never be
            # served to a video workload (and vice versa)
            "num_frames": knobs.num_frames,
            "frame_groups": knobs.frame_groups,
            # prompt axis (DESIGN.md §17): a plan priced for one prompt
            # bucket must never be served to another (t_xattn scales with
            # the token count), nor a class-conditional plan to a prompt
            # workload
            "cond_bucket": knobs.cond_bucket,
            "cross_attn": bool(self.model_cfg.cross_attn),
            "cost_model": (None if cm is None else dataclasses.asdict(cm)),
        }

    def plan(self, speeds: Optional[Sequence[float]] = None, *,
             use_cache: bool = True) -> ExecutionPlan:
        """Run the configured planner (no execution) and return a fully-
        populated six-axis ExecutionPlan: ``stages`` / ``guidance`` /
        ``seq`` / ``frames`` are resolved from the planner output or the
        config knobs in this one pass. With a plan cache configured, the persistent cache
        is consulted before any planner search (``use_cache=False`` forces
        a live search without touching the cache)."""
        with obs.span("stadi.plan"):
            speeds = list(speeds) if speeds is not None else self.config.speeds
            knobs = self._plan_knobs()
            key = None
            if self.plan_cache is not None and use_cache:
                key = self.plan_cache.signature(speeds, self._model_key(),
                                                self._workload_key(knobs))
                hit = self.plan_cache.get(key)
                if hit is not None:
                    self.last_plan_key = key
                    return hit
            raw = get_planner(self.config.planner)(speeds, knobs, self.p_total)
            self.planner_calls += 1
            plan = dataclasses.replace(
                raw,
                stages=_resolve_stages(raw, self.model_cfg, knobs),
                guidance=_resolve_guidance(raw, knobs),
                seq=(raw.seq if raw.seq is not None
                     else _resolve_seq(raw, self.model_cfg, knobs)),
                frames=(raw.frames if raw.frames is not None
                        else _resolve_frames(raw, knobs)))
            if key is not None:
                self.plan_cache.put(key, plan)
                self.last_plan_key = key
            return plan

    def generate(self, x_T=None, cond=None, *,
                 measured_speeds: Optional[Sequence[float]] = None
                 ) -> PipelineResult:
        """Plan and execute one generation.

        measured_speeds: ground-truth effective speeds the run experiences
        (defaults to the configured cluster's). When they drift from the
        planned speeds and ``rebalance_every`` is on, the profiler detects it
        and the remaining steps are re-planned mid-run.
        """
        with obs.span("stadi.generate"):
            config = self.config
            plan = self.plan()
            check_backend_can_run(plan, config)
            replans: List[ReplanEvent] = []
            hook = None
            if config.rebalance_every > 0:
                if config.backend != "emulated":
                    raise ValueError("rebalance_every requires the "
                                     "'emulated' backend, not "
                                     f"{config.backend!r}")
                hook = self._make_rebalance_hook(plan, measured_speeds,
                                                 replans)
            # ONE normalized call shape for every backend (EXECUTOR_KWARGS):
            # strictly keyword, so per-backend kwarg drift cannot creep in
            from repro.kernels import ops as kops
            kstats_before = kops.kernel_stats_snapshot()
            image, trace = get_executor(config.backend)(
                params=self.params, model_cfg=self.model_cfg,
                sched=self.sched,
                x_T=x_T, cond=cond, plan=plan, config=config,
                interval_hook=hook)
            kernel_stats = kops.kernel_stats_delta(
                kstats_before, kops.kernel_stats_snapshot())
            for bucket, counts in kernel_stats.items():
                for key, n in counts.items():
                    self.kernel_stats[bucket][key] = (
                        self.kernel_stats[bucket].get(key, 0) + n)
            latency = None
            if config.cost_model is not None:
                lat_speeds = (list(measured_speeds)
                              if measured_speeds is not None
                              else config.speeds)
                latency = sim.simulate_trace(trace, lat_speeds,
                                             config.cost_model)
            elif config.backend == "simulate":
                raise ValueError("the 'simulate' backend needs "
                                 "config.cost_model")
            return PipelineResult(image, trace, plan, latency, replans,
                                  kernel_stats)

    def generate_many(self, x_Ts: Sequence, conds: Sequence, *,
                      slots: int = 4) -> List[PipelineResult]:
        """Continuous-batched generation of many requests (serving engine).

        Admits all requests into a :class:`repro.serving.diffusion_engine.
        DiffusionServingEngine` with ``slots`` concurrent lanes and drains
        them; per-request images are bitwise identical to calling
        :meth:`generate` once per request on the emulated backend. Each
        result's ``latency_s`` is the per-request modeled serving latency
        (queueing + batched service, via the cost model) rather than the
        single-request makespan — None when no cost model is configured.
        Results come back in submission order. For SLO verdicts and
        round-level stats, drive a DiffusionServingEngine directly.
        """
        from repro.serving.diffusion_engine import DiffusionServingEngine
        if len(x_Ts) != len(conds):
            raise ValueError(f"{len(x_Ts)} inputs vs {len(conds)} conds")
        engine = DiffusionServingEngine(self, slots=slots)
        reqs = [engine.submit(x, c) for x, c in zip(x_Ts, conds)]
        engine.run_to_completion()
        trace = sim.build_trace(engine.plan.temporal, engine.plan.patches,
                                self.model_cfg, batch=1,
                                exchange=self.config.exchange,
                                exchange_refresh=self.config.exchange_refresh,
                                stages=engine.stages,
                                guidance=engine.plan.guidance)
        report_latency = self.config.cost_model is not None
        return [PipelineResult(r.image, trace, engine.plan,
                               r.modeled_latency_s if report_latency else None)
                for r in reqs]

    # ------------------------------------------------------------------
    # online rebalancing (beyond-paper §7.1): OnlineProfiler in the hot path
    # ------------------------------------------------------------------

    def _make_rebalance_hook(self, plan: ExecutionPlan,
                             measured_speeds: Optional[Sequence[float]],
                             replans: List[ReplanEvent]):
        config = self.config
        cm = config.cost_model or CostModel(t_fixed=1e-3, t_row=1e-3)
        true_speeds = (list(measured_speeds) if measured_speeds is not None
                       else config.speeds)
        profiler = hetero.OnlineProfiler(plan.speeds, alpha=config.profiler_alpha)
        state = {"baseline": list(plan.speeds), "since": 0}

        def hook(next_fine_step: int, ev):
            # feed measured per-device interval latencies into the profiler;
            # work is nominal seconds at v=1 so observed_v converges on the
            # device's true effective speed
            hetero.feed_profiler(profiler, cm, ev.substeps, ev.patches,
                                 true_speeds)
            state["since"] += 1
            if state["since"] < config.rebalance_every:
                return None
            state["since"] = 0
            drift = profiler.drift(state["baseline"])
            if drift <= config.rebalance_threshold:
                return None
            f_rem = plan.temporal.m_base - next_fine_step
            tiers = tuple(t for t in config.tiers if f_rem % t == 0) or (1,)
            knobs = dataclasses.replace(config, m_base=f_rem, m_warmup=0,
                                        tiers=tiers)
            new = get_planner(config.planner)(profiler.speeds, knobs,
                                              self.p_total)
            if f_rem % new.temporal.lcm:
                return None              # cannot fit an interval; keep going
            if self.plan_cache is not None and self.last_plan_key:
                # the persisted plan was computed from speeds that no
                # longer hold — drop it so the next plan() re-searches
                self.plan_cache.invalidate(self.last_plan_key)
            replans.append(ReplanEvent(next_fine_step, drift,
                                       list(state["baseline"]),
                                       list(profiler.speeds), new))
            state["baseline"] = list(profiler.speeds)
            return new.temporal, new.patches

        return hook
