"""Slot-based continuous batching for diffusion requests over StadiPipeline.

The LLM engine (:mod:`repro.serving.engine`) batches decode steps; this is
its diffusion counterpart (DESIGN.md §9). Each :class:`DiffusionRequest`
carries its own position on the fine DDIM grid, so requests admitted at
different times coexist in one denoise dispatch:

    pipe   = StadiPipeline(cfg, params, sched, config)      # any planner
    engine = DiffusionServingEngine(pipe, slots=8)
    reqs   = [engine.submit(x_T, cond) for ...]             # FIFO queue
    engine.run_to_completion()
    stats  = engine.stats()          # per-request latency / SLO, throughput

One scheduling **round** = admit (FIFO, lowest free slot) -> one warmup fine
step for warmup-phase lanes -> one adaptive interval (``plan.lcm`` fine
steps) for adaptive-phase lanes -> retire finished lanes. All per-lane state
(latent, stale-KV ``Published`` buffers, class condition) lives in
slot-major stacked arrays, so a batched step is a gather / one vmapped
denoiser dispatch / scatter.

Numerics: the "emulated" stepper mirrors ``patch_parallel.run_schedule``
call-for-call — same jit boundaries, eager DDIM updates, publish-at-first-
substep and merge-at-interval-boundary buffer semantics — and vmap lanes are
computed independently, so every request's final image is **bitwise
identical** to a single-request ``pipe.generate`` (tested). The "spmd"
stepper instead shard_maps each interval across ``jax.devices()`` for
cohorts of requests that share a fine-step position.

Latency: every round is costed against ``StadiConfig.cluster`` with the
``simulate`` cost model — per-round device placement assigns the heaviest
patch-worker load to the fastest device (deterministic) — and each request
accrues modeled wall-clock from submission to completion, giving queueing +
service latency and SLO accounting that tests can assert exactly.
"""
from __future__ import annotations

import dataclasses
import functools
import time
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core import buffers as buf_lib
from repro.core import comm as comm_lib
from repro.core import events as ir
from repro.core import hetero
from repro.core import patch_parallel as pp
from repro.core import pipefuse as pipefuse_lib
from repro.core import sampler as sampler_lib
from repro.core import simulate as sim
from repro.core.pipeline import (ReplanEvent, StadiPipeline,
                                 check_backend_can_run, get_stepper_factory,
                                 register_stepper_factory)
from repro.core.planners import ExecutionPlan
from repro.core.schedule import patch_bounds
from repro.core.simulate import CostModel
from repro.models.diffusion import dit


@dataclasses.dataclass
class DiffusionRequest:
    """One queued generation request plus its serving statistics.

    ``fine_step`` is the request's own position on the fine DDIM grid
    (0..m_base); the engine advances it by 1 per warmup round and by
    ``plan.lcm`` per adaptive round.
    """
    uid: int
    x_T: jnp.ndarray                     # [1, H, W, C]
    # class conditioning: [1] int32; prompt conditioning (DESIGN.md §17):
    # [1, L, cond_dim+1] float32 tokens+mask, L the request's length bucket
    cond: jnp.ndarray
    slo_s: Optional[float] = None        # modeled-latency SLO target
    # classifier-free guidance (DESIGN.md §12): None = unguided request;
    # > 0 = this request denoises with eps_u + cfg_scale*(eps_c - eps_u)
    # (per-lane state; CFG and non-CFG requests coexist in one batch)
    cfg_scale: Optional[float] = None

    @property
    def guided(self) -> bool:
        return self.cfg_scale is not None and self.cfg_scale > 0.0
    # engine-owned state
    fine_step: int = 0
    image: Optional[jnp.ndarray] = None
    done: bool = False
    preempt_count: int = 0               # evictions back to the queue head
    # statistics (rounds are engine scheduling rounds; latency is modeled
    # wall-clock on the configured cluster, queueing included)
    submit_round: int = -1
    admit_round: int = -1
    finish_round: int = -1
    submit_clock_s: float = 0.0
    modeled_latency_s: float = 0.0
    wall_latency_s: float = 0.0
    _submit_wall: float = 0.0

    @property
    def queue_rounds(self) -> int:
        return self.admit_round - self.submit_round

    @property
    def slo_met(self) -> Optional[bool]:
        if self.slo_s is None or not self.done:
            return None
        return self.modeled_latency_s <= self.slo_s


@dataclasses.dataclass
class RoundReport:
    """What one scheduling round did (admissions, groups, placement, cost)."""
    index: int
    admitted: List[Tuple[int, int]] = dataclasses.field(default_factory=list)
    warmup_lanes: List[int] = dataclasses.field(default_factory=list)
    adaptive_lanes: List[int] = dataclasses.field(default_factory=list)
    exchange_kinds: List[str] = dataclasses.field(default_factory=list)
    placement: Optional[Tuple[Tuple[int, int], ...]] = None  # (worker, device)
    modeled_s: float = 0.0
    wall_s: float = 0.0


# ----------------------------------------------------------------------
# steppers (registered into repro.core.pipeline.STEPPER_FACTORIES)
# ----------------------------------------------------------------------
#
# The vmapped denoiser steps are MODULE-LEVEL jitted functions (params as an
# argument, cfg/row_start static) so every engine instance shares one
# compilation cache — per-instance jax.jit wrappers would recompile the hot
# loop for each engine and hand the throughput win back to the sequential
# baseline, whose pp._jit_* functions are likewise cached at module level.

@functools.partial(jax.jit, static_argnames=("cfg",))
def _vmap_full_step(params, cfg, xs, ts, conds):
    """Lane-stacked synchronous full-image step: xs [G,1,H,W,C], ts [G]."""
    def one(x, t, cond):
        return dit.forward_patch(params, cfg, x, t, cond, 0, buffers=None,
                                 return_kv=True)
    return jax.vmap(one)(xs, ts, conds)


@functools.partial(jax.jit, static_argnames=("cfg", "row_start"))
def _vmap_patch_step(params, cfg, xs_loc, ts, conds, bks, bvs, row_start):
    """Lane-stacked stale-KV patch step (vmapped ``pp._jit_patch_step``)."""
    def one(x_loc, t, cond, bk, bv):
        return dit.forward_patch(params, cfg, x_loc, t, cond, row_start,
                                 buffers=(bk, bv), return_kv=True)
    return jax.vmap(one)(xs_loc, ts, conds, bks, bvs)


# Guided (classifier-free guidance, DESIGN.md §12) lane steps: the per-lane
# body is the SAME branch-vmapped fused-CFG eval as the single-request
# engine's pp._jit_guided_*_step, lane-vmapped on top — so a guided lane
# stays bitwise identical to a single-request guided ``generate``. scales
# is per-lane data: one compiled program serves every cfg_scale in flight.
# With Pallas on, the combine is the same fused epilogue generate uses
# (DESIGN.md §15) — applied inside the lane vmap so scale stays scalar and
# the kernel path is taken; XLA fuses the batched program differently from
# the unbatched one, so the engine≡generate guarantee is bitwise for
# reference numerics and ≈1e-6 relative under forced kernels.


def _lane_cfg_combine(cfg, eps2, scale):
    if cfg.use_pallas_attention:
        from repro.kernels import ops as kops
        return kops.cfg_epilogue(eps2[0], eps2[1], scale, with_delta=False)
    return sampler_lib.cfg_combine(eps2[0], eps2[1], scale)

@functools.partial(jax.jit, static_argnames=("cfg",))
def _vmap_guided_full_step(params, cfg, xs, ts, conds, scales):
    """Lane-stacked guided synchronous step: xs [G,1,H,W,C], scales [G].
    Returns (eps [G,1,H,W,C], (k2, v2) [G,2,L,1,N,H,hd])."""
    def one(x, t, cond, scale):
        def branch(c):
            return dit.forward_patch(params, cfg, x, t, c, 0, buffers=None,
                                     return_kv=True)
        eps2, kv2 = jax.vmap(branch)(dit.guidance_conds(cond))
        return _lane_cfg_combine(cfg, eps2, scale), kv2
    return jax.vmap(one)(xs, ts, conds, scales)


@functools.partial(jax.jit, static_argnames=("cfg", "row_start"))
def _vmap_guided_patch_step(params, cfg, xs_loc, ts, conds, bk2s, bv2s,
                            scales, row_start):
    """Lane-stacked guided stale-KV patch step against branch-stacked
    published buffers bk2s/bv2s [G,2,L,1,N,H,hd]."""
    def one(x_loc, t, cond, bk2, bv2, scale):
        def branch(c, bk, bv):
            return dit.forward_patch(params, cfg, x_loc, t, c, row_start,
                                     buffers=(bk, bv), return_kv=True)
        eps2, kv2 = jax.vmap(branch)(dit.guidance_conds(cond), bk2, bv2)
        return _lane_cfg_combine(cfg, eps2, scale), kv2
    return jax.vmap(one)(xs_loc, ts, conds, bk2s, bv2s, scales)


class _VmapWarmupMixin:
    """Warmup / bootstrap steps shared by both steppers: synchronous
    full-image forwards, vmapped over lanes (per-lane timestep)."""

    #: can this stepper run guided (CFG) lanes? (DESIGN.md §12)
    supports_guidance = False

    def _init_warmup(self, params, model_cfg, sched):
        self.params = params
        self.model_cfg = model_cfg
        self.sched = sched

    def _warmup_finish(self, xs, t_from, t_to, eps, ks, vs):
        shape = (xs.shape[0],) + (1,) * (xs.ndim - 1)
        with obs.span("exec.sampler"):
            xs = sampler_lib.ddim_step(self.sched, xs, eps,
                                       t_from.reshape(shape),
                                       t_to.reshape(shape))
        return xs, ks, vs

    def _model_span(self):
        """The ``exec.model`` span of a full-image dispatch."""
        return obs.span("exec.model", rows=self.model_cfg.tokens_per_side)

    def warmup_step(self, xs, t_from, t_to, conds):
        """One synchronous fine step per lane: returns (xs', ks, vs)."""
        with self._model_span():
            eps, (ks, vs) = _vmap_full_step(self.params, self.model_cfg, xs,
                                            t_from, conds)
        return self._warmup_finish(xs, t_from, t_to, eps, ks, vs)

    def warmup_step_guided(self, xs, t_from, t_to, conds, scales):
        """Guided synchronous step per lane: returns (xs', k2s, v2s) with
        branch-stacked fresh K/V [G,2,L,1,N,H,hd]."""
        with self._model_span():
            eps, (k2s, v2s) = _vmap_guided_full_step(
                self.params, self.model_cfg, xs, t_from, conds, scales)
        return self._warmup_finish(xs, t_from, t_to, eps, k2s, v2s)



@register_stepper_factory("emulated")
class EmulatedStepper(_VmapWarmupMixin):
    """vmapped mirror of ``run_schedule``'s adaptive loop: per (worker,
    substep) one jitted denoiser dispatch covers every lane, lanes may sit at
    different fine steps (timestep is per-lane data). Bitwise identical per
    lane to the single-request engine."""

    cohort_only = False
    supports_guidance = True

    def __init__(self, pipeline: StadiPipeline, plan: ExecutionPlan,
                 slots: int):
        self._init_warmup(pipeline.params, pipeline.model_cfg, pipeline.sched)
        self.plan = plan
        self._ts = sampler_lib.ddim_timesteps(pipeline.sched.T,
                                              plan.temporal.m_base)

    def _interval_impl(self, xs, fine0, conds, pub_k, pub_v, merge,
                       step_fn, tok_axis):
        """The ONE lane-interval loop both the plain and guided entry
        points share: per (worker, substep) one ``step_fn`` dispatch covers
        every lane, slabs scatter back, and first-substep K/V merges into
        the published buffers at ``tok_axis`` (3 plain, 4 branch-stacked)
        in ascending worker order — mirroring ``buffers.merge``."""
        plan, cfg = self.plan.temporal, self.model_cfg
        R, p = plan.lcm, cfg.patch_size
        G = xs.shape[0]
        fine0 = np.asarray(fine0)
        bounds_tok = patch_bounds(self.plan.patches)
        bounds_lat = [(a * p, b * p) for a, b in bounds_tok]
        workers = [i for i in plan.active if self.plan.patches[i] > 0]
        tshape = (G,) + (1,) * (xs.ndim - 1)

        pending, new_slabs = {}, {}
        for i in workers:
            r = plan.ratios[i]
            lo, hi = bounds_lat[i]
            with obs.span("exec.buffers"):
                x_loc = xs[:, :, lo:hi]
            for s in range(R // r):
                with obs.span("exec.buffers"):
                    t_from = self._ts[fine0 + s * r]
                    t_to = self._ts[fine0 + (s + 1) * r]
                with obs.span("exec.model", rows=self.plan.patches[i]):
                    eps, (k, v) = step_fn(x_loc, t_from, bounds_tok[i][0])
                with obs.span("exec.sampler"):
                    x_loc = sampler_lib.ddim_step(self.sched, x_loc, eps,
                                                  t_from.reshape(tshape),
                                                  t_to.reshape(tshape))
                if s == 0:           # Alg.1: publish the first substep's KV
                    pending[i] = (k, v)
            new_slabs[i] = x_loc
        # interval boundary: all-gather of x + buffer merge (same order as
        # buffers.merge: ascending worker id)
        with obs.span("exec.buffers"):
            for i in workers:
                lo, hi = bounds_lat[i]
                xs = xs.at[:, :, lo:hi].set(new_slabs[i])
            if merge:
                for i in sorted(pending):
                    k, v = pending[i]
                    start = bounds_tok[i][0] * cfg.tokens_per_side
                    pub_k = jax.lax.dynamic_update_slice_in_dim(
                        pub_k, k.astype(pub_k.dtype), start, axis=tok_axis)
                    pub_v = jax.lax.dynamic_update_slice_in_dim(
                        pub_v, v.astype(pub_v.dtype), start, axis=tok_axis)
        return xs, pub_k, pub_v

    def interval(self, xs, fine0, conds, pub_k, pub_v, merge: bool = True):
        """One adaptive interval (plan.lcm fine steps) for every lane.

        xs [G,1,H,W,C]; fine0 int per lane; pub_{k,v} [G,L,1,N,H,hd] — the
        READ buffers (the engine passes extrapolated copies for predictive
        boundaries). ``merge=False`` is the "skip"/"predict" trailing
        boundary: fresh K/V is never broadcast, the buffers come back
        untouched.
        """
        def step(x_loc, t_from, row0):
            return _vmap_patch_step(self.params, self.model_cfg, x_loc,
                                    t_from, conds, pub_k, pub_v, row0)
        return self._interval_impl(xs, fine0, conds, pub_k, pub_v, merge,
                                   step, tok_axis=3)

    def interval_guided(self, xs, fine0, conds, scales, pub_k, pub_v,
                        merge: bool = True):
        """One adaptive interval for GUIDED lanes (DESIGN.md §12): the
        same worker/substep structure as :meth:`interval`, every denoiser
        dispatch a branch-vmapped fused-CFG eval against branch-stacked
        buffers pub_{k,v} [G,2,L,1,N,H,hd]; scales [G] is per-lane data."""
        def step(x_loc, t_from, row0):
            return _vmap_guided_patch_step(self.params, self.model_cfg,
                                           x_loc, t_from, conds, pub_k,
                                           pub_v, scales, row0)
        return self._interval_impl(xs, fine0, conds, pub_k, pub_v, merge,
                                   step, tok_axis=4)


@functools.partial(jax.jit, static_argnames=("cfg", "row_start", "bounds"))
def _vmap_displaced_step(params, cfg, xs_loc, ts, conds, ctx_ks, ctx_vs,
                         row_start, bounds):
    """Lane-stacked displaced micro-task (vmapped ``pipefuse.
    displaced_step``): every lane carries its own stage contexts."""
    def one(x_loc, t, cond, ck, cv):
        return pipefuse_lib.displaced_step(params, cfg, x_loc, t, cond,
                                           row_start, ck, cv, bounds)
    return jax.vmap(one)(xs_loc, ts, conds, ctx_ks, ctx_vs)


@register_stepper_factory("pipefuse")
class PipefuseStepper(EmulatedStepper):
    """Displaced patch-pipeline serving (DESIGN.md §11): at one stage this
    IS the EmulatedStepper (bitwise); at S > 1 each interval runs the same
    substep-major micro order as ``pipefuse.run_pipefuse`` with lane-stacked
    displaced contexts, so per-request images stay bitwise identical to a
    single-request ``generate`` on the pipefuse backend."""

    def __init__(self, pipeline: StadiPipeline, plan: ExecutionPlan,
                 slots: int):
        super().__init__(pipeline, plan, slots)
        self.stages = plan.stages or [pipeline.model_cfg.n_layers]
        self.bounds = pipefuse_lib.stage_bounds(self.stages)

    @property
    def wants_ctx(self) -> bool:
        return len(self.stages) > 1

    @property
    def supports_guidance(self) -> bool:
        # at one stage this IS the EmulatedStepper; lane-stacked displaced
        # contexts don't carry guided branch state (future work)
        return not self.wants_ctx

    def interval_ctx(self, xs, fine0, conds, pub_k, pub_v, ctx_k, ctx_v,
                     merge: bool = True):
        """One adaptive interval through the stage chain.

        ctx_{k,v} [G,L,1,N,H,hd] are the lanes' displaced contexts (reset to
        the published buffers by the engine on fill intervals). Returns
        (xs', pub_k', pub_v', ctx_k', ctx_v').
        """
        plan, cfg = self.plan.temporal, self.model_cfg
        R, p = plan.lcm, cfg.patch_size
        G = xs.shape[0]
        fine0 = np.asarray(fine0)
        bounds_tok = patch_bounds(self.plan.patches)
        bounds_lat = [(a * p, b * p) for a, b in bounds_tok]
        workers = [i for i in plan.active if self.plan.patches[i] > 0]
        tshape = (G,) + (1,) * (xs.ndim - 1)

        pending, slabs = {}, {}
        for i in workers:
            lo, hi = bounds_lat[i]
            slabs[i] = xs[:, :, lo:hi]
        for f in range(R):                   # substep-major micro order
            for i in workers:
                r = plan.ratios[i]
                if f % r:
                    continue
                t_from = self._ts[fine0 + f]
                t_to = self._ts[fine0 + f + r]
                eps, k, v, ctx_k, ctx_v = _vmap_displaced_step(
                    self.params, cfg, slabs[i], t_from, conds, ctx_k, ctx_v,
                    bounds_tok[i][0], self.bounds)
                slabs[i] = sampler_lib.ddim_step(self.sched, slabs[i], eps,
                                                 t_from.reshape(tshape),
                                                 t_to.reshape(tshape))
                if f == 0:
                    pending[i] = (k, v)
        for i in workers:
            lo, hi = bounds_lat[i]
            xs = xs.at[:, :, lo:hi].set(slabs[i])
        if merge:
            for i in sorted(pending):
                k, v = pending[i]
                start = bounds_tok[i][0] * cfg.tokens_per_side
                pub_k = jax.lax.dynamic_update_slice_in_dim(
                    pub_k, k.astype(pub_k.dtype), start, axis=3)
                pub_v = jax.lax.dynamic_update_slice_in_dim(
                    pub_v, v.astype(pub_v.dtype), start, axis=3)
        return xs, pub_k, pub_v, ctx_k, ctx_v


@register_stepper_factory("spmd")
class SpmdStepper(_VmapWarmupMixin):
    """shard_map adaptive intervals over real ``jax.devices()``: lanes are
    stacked on the model batch axis, so every lane of one call must share a
    fine-step position (``cohort_only``) — the engine groups cohorts by
    ``fine_step``. Warmup stays on the host (synchronous steps are exact
    full-image forwards, which SPMD executes redundantly anyway)."""

    cohort_only = True

    _cache: Dict[Tuple, object] = {}          # shared across engine instances

    def __init__(self, pipeline: StadiPipeline, plan: ExecutionPlan,
                 slots: int):
        from repro.core import spmd
        self._init_warmup(pipeline.params, pipeline.model_cfg, pipeline.sched)
        self.plan = plan
        n_workers = len(plan.patches)
        if n_workers > len(jax.devices()):
            raise ValueError(
                f"spmd serving needs {n_workers} devices, have "
                f"{len(jax.devices())} (set STADI_HOST_DEVICES)")
        sched = pipeline.sched            # content-keyed: id() could alias
        self._key = (pipeline.model_cfg, tuple(plan.patches),
                     tuple(plan.temporal.ratios), plan.temporal.m_base,
                     plan.temporal.m_warmup, sched.T,
                     np.asarray(sched.alpha_bar).tobytes())
        self._spmd = spmd
        self._variant("full")             # compile the common case eagerly

    def _variant(self, kind: str):
        """One compiled interval program per boundary kind ("full" merges
        fresh K/V, "skip" leaves the buffers stale — predictive callers
        extrapolate host-side and use the "skip" variant)."""
        key = self._key + (kind,)
        if key not in SpmdStepper._cache:
            SpmdStepper._cache[key] = self._spmd.make_interval_step(
                self.model_cfg, self.sched, self.plan.temporal,
                self.plan.patches, exchange_kind=kind)
        return SpmdStepper._cache[key]

    def interval(self, xs, fine0, conds, pub_k, pub_v, merge: bool = True):
        fine0 = np.asarray(fine0)
        assert (fine0 == fine0[0]).all(), \
            "spmd stepper is cohort-only: lanes must share fine_step"
        # lane-major [G,1,...] -> batch-major [G,...] / [L,G,N,H,hd]
        x = xs[:, 0]
        bk = jnp.moveaxis(pub_k[:, :, 0], 0, 1)
        bv = jnp.moveaxis(pub_v[:, :, 0], 0, 1)
        fn = self._variant("full" if merge else "skip")
        x, bk, bv = fn(self.params, x, conds[:, 0], bk, bv,
                       jnp.int32(fine0[0]))
        return (x[:, None], jnp.moveaxis(bk, 1, 0)[:, :, None],
                jnp.moveaxis(bv, 1, 0)[:, :, None])


# ----------------------------------------------------------------------
# the engine
# ----------------------------------------------------------------------

class DiffusionServingEngine:
    """Continuous batching of diffusion requests over one StadiPipeline.

    Admission: FIFO queue into the lowest free slot at the start of every
    round; a slot freed this round is refilled next round. Placement: each
    round the plan's patch-workers are assigned to cluster devices by the
    cost model (heaviest load -> fastest device, deterministic ties), and the
    modeled round time — batched compute, boundary all-gather, masked async
    KV — is accrued to every in-flight request.
    """

    def __init__(self, pipeline: StadiPipeline, *, slots: int = 4,
                 cost_model: Optional[CostModel] = None,
                 rebalance_every: int = 0,
                 rebalance_threshold: float = 0.2,
                 measured_speeds: Optional[Sequence[float]] = None):
        config = pipeline.config
        if config.rebalance_every:
            raise ValueError("serving drives placement per round; disable "
                             "rebalance_every on the pipeline config (the "
                             "engine's own rebalance_every kwarg replans "
                             "between rounds)")
        if slots < 1:
            raise ValueError("need at least one slot")
        self.pipeline = pipeline
        self.slots = slots
        self.plan = pipeline.plan()
        check_backend_can_run(self.plan, config)
        # classifier-free guidance (DESIGN.md §12/§14): serving batches
        # FUSED lane cohorts (every worker computes both branches) and
        # SPLIT lane cohorts (workers are cond/uncond device PAIRS, eps
        # exchanged between dispatches — same numerics by construction,
        # pair-placed cost). Interleaved uncond reuse remains a
        # per-generation optimization.
        gplan = self.plan.guidance
        if gplan is not None and gplan.mode == "interleaved":
            raise ValueError(
                "serving batches fused- or split-CFG lane cohorts; "
                "'interleaved' uncond reuse is per-generation — use "
                "pipe.generate, or set guidance='fused'|'split'")
        self.default_scale = gplan.scale if gplan is not None else None
        self.cm = cost_model or config.cost_model
        # placement needs SOME cost model; flag the uncalibrated fallback so
        # modeled latencies / SLO verdicts are never mistaken for calibrated
        self.cm_calibrated = self.cm is not None
        if self.cm is None:
            self.cm = CostModel(t_fixed=1e-3, t_row=1e-3)
        cfg = pipeline.model_cfg
        self._ts = sampler_lib.ddim_timesteps(pipeline.sched.T,
                                              self.plan.temporal.m_base)
        H, C = cfg.latent_size, cfg.channels
        self._x = jnp.zeros((slots, 1, H, H, C), jnp.float32)
        kshape = (slots,) + dit.buffer_shape(cfg, 1)
        kdt = jnp.dtype(cfg.dtype)
        self._kshape = kshape
        self._pub_k = jnp.zeros(kshape, kdt)
        self._pub_v = jnp.zeros(kshape, kdt)
        self._cond = jnp.zeros((slots, 1), jnp.int32)
        # prompt conditioning (DESIGN.md §17): with a text-conditioned
        # model every request carries a [1, L, cond_dim+1] token tensor.
        # L varies per request (the encoder's power-of-two length bucket),
        # so prompt conds live on the requests — _conds() stacks a lane
        # group's, and the group key pins one bucket per dispatch.
        self._prompt_mode = bool(cfg.cross_attn)
        # guided lanes: branch-stacked published K/V [slots,2,L,1,N,H,hd]
        # + per-lane cfg_scale; allocated on the first guided submission so
        # CFG-free serving carries no extra state
        self._kshape2 = (slots, 2) + dit.buffer_shape(cfg, 1)
        self._kdt = kdt
        self._gk = self._gv = None
        self._prev_gk = self._prev_gv = None
        self._prev_k = self._prev_v = None
        self._scales = np.zeros(slots, np.float32)
        # displaced patch pipeline (DESIGN.md §11): stage chain + per-lane
        # displaced contexts (only materialized when depth is partitioned)
        self.stages = self.plan.stages
        staged = self.stages is not None and len(self.stages) > 1
        self._ctx_k = jnp.zeros(kshape, kdt) if staged else None
        self._ctx_v = jnp.zeros(kshape, kdt) if staged else None
        # sequence-parallel attention (DESIGN.md §13): seq sharding
        # repartitions WHERE attention runs (device groups + ring hops),
        # never WHAT is computed, so the emulated stepper serves seq-sharded
        # lanes bitwise unchanged — only the lane group key (per-interval
        # ring hop count) and the modeled round cost see the shards.
        self.seq = self.plan.seq
        if self.seq is not None and len(self.seq.segments) < 2:
            self.seq = None
        if self.seq is not None and staged:
            raise ValueError(
                "serving does not compose sequence sharding with a "
                "displaced stage chain; run seq-sharded lanes on the "
                "single-stage 'emulated' backend")
        self._seq_groups = None
        self._seq_seg_pad = 0.0
        if self.seq is not None:
            from repro.core import seqpar
            groups, _ = seqpar.seq_group_speeds(list(config.speeds),
                                                self.seq.n_shards)
            self._seq_groups = groups
            self._seq_seg_pad = max(self.seq.seg_fracs)
        # frame axis (DESIGN.md §16): video lanes. Cross-frame stale-K/V
        # state lives per CLIP (frame f attends the previous frame's
        # published buffers), not per slot, so a video request runs its
        # whole multi-frame schedule in the round it is admitted — a
        # run-to-completion lane cohort. Rounds still admit FIFO into
        # slots and accrue the frame-priced schedule makespan per clip,
        # so queueing delay, SLO verdicts and throughput stats stay
        # meaningful.
        self.frames = self.plan.frames
        if self.frames is not None and self.frames.num_frames < 2:
            self.frames = None
        if self.frames is not None and rebalance_every:
            raise ValueError(
                "the frame grouping is static — engine replanning would "
                "re-deal the frame-group rows; serve video plans with "
                "rebalance_every=0")
        self.policy = comm_lib.get_exchange(config.exchange,
                                            config.exchange_refresh)
        # online replanning (DESIGN.md §7.1 composed with §12/§14): the
        # ground-truth speeds the cluster actually runs at (emulation's
        # stand-in for per-interval timers), the drift profiler, and the
        # replan cadence. With split guidance a replan re-pairs the
        # cond/uncond device groups (the stadi_guidance planner re-runs
        # guidance_groups over the profiled speeds).
        self.measured_speeds = (list(measured_speeds)
                                if measured_speeds is not None
                                else list(config.speeds))
        if len(self.measured_speeds) != config.n_devices:
            raise ValueError(f"measured_speeds has "
                             f"{len(self.measured_speeds)} entries for a "
                             f"{config.n_devices}-device cluster")
        self.rebalance_every = int(rebalance_every)
        self.rebalance_threshold = rebalance_threshold
        self.replans: List[ReplanEvent] = []
        self.preemptions = 0
        self._pending_plan: Optional[Tuple[ExecutionPlan, float]] = None
        self._rounds_since_check = 0
        self.profiler: Optional[hetero.OnlineProfiler] = None
        if self.rebalance_every:
            if staged or self.seq is not None:
                raise ValueError(
                    "engine replanning re-deals patch workers; staged / "
                    "seq-sharded plans pin their device grouping — serve "
                    "them with rebalance_every=0")
            self.profiler = hetero.OnlineProfiler(
                list(config.speeds), alpha=config.profiler_alpha)
            self._baseline = list(config.speeds)
        # kernel-path visibility (DESIGN.md §15): the engine's steppers
        # trace their own programs (not pipeline.generate), so attribute
        # every hit/miss traced after construction to this engine
        from repro.kernels import ops as kops
        self._kernel_stats_base = kops.kernel_stats_snapshot()
        self.queue: List[DiffusionRequest] = []
        self.active: Dict[int, DiffusionRequest] = {}   # slot -> request
        self.completed: List[DiffusionRequest] = []
        self.rounds: List[RoundReport] = []
        self.modeled_clock_s = 0.0
        self._next_uid = 0
        self._install_plan(self.plan)
        if self.rebalance_every and self.stepper.cohort_only:
            raise ValueError("engine replanning rebuilds the lane stepper "
                             "per plan; the cohort-only (spmd) stepper "
                             "compiles one static program — serve it with "
                             "rebalance_every=0")

    def _install_plan(self, plan: ExecutionPlan) -> None:
        """(Re)build every plan-derived piece of engine state: the lane
        stepper, the split-guidance pair map, the per-fine-step boundary
        info, the predictive-extrapolation buffers, and the comm byte
        sizing. Called once at construction and again at every online
        replan (same m_base/m_warmup grid; stages/seq replans are rejected
        up front)."""
        pipeline, config = self.pipeline, self.pipeline.config
        cfg = pipeline.model_cfg
        self.plan = plan
        if self.frames is not None:
            # video lanes (DESIGN.md §16): no batched lane stepper — each
            # clip's schedule runs whole through the configured frame
            # executor in _frames_round. The per-clip modeled cost comes
            # from the SAME frame-priced trace the simulate backend
            # replays, so serving accounting cannot diverge from
            # simulate_trace's.
            self._guide_pairs = None
            self.stepper = None
            self._interval_info = {}
            self._track_prev = False
            trace = sim.build_trace(plan.temporal, plan.patches, cfg,
                                    batch=1, exchange=config.exchange,
                                    exchange_refresh=config.exchange_refresh,
                                    frames=self.frames,
                                    guidance=plan.guidance,
                                    cond_tokens=(config.cond_bucket or None))
            self._latent_bytes = trace.latent_bytes
            self._kv_bytes = trace.kv_bytes_per_worker
            self._act_row_bytes = trace.act_row_bytes
            self._clip_cost_s = sim.simulate_trace(
                trace, self.measured_speeds, self.cm)
            return
        gplan = plan.guidance
        # split-guidance lane cohorts: logical worker i is the device pair
        # (cond_devices[i], uncond_devices[i]) — used for pair-placed round
        # costs and for feeding the profiler both pair members
        self._guide_pairs = (list(zip(gplan.cond_devices,
                                      gplan.uncond_devices))
                             if gplan is not None and gplan.mode == "split"
                             else None)
        self.stepper = get_stepper_factory(config.backend)(
            pipeline, plan, self.slots)
        if (self.default_scale is not None
                and not self.stepper.supports_guidance):
            raise ValueError(f"backend {config.backend!r} has no guided "
                             "serving stepper (guided lanes need "
                             "'emulated' or single-stage 'pipefuse')")
        staged = self.stages is not None and len(self.stages) > 1
        # boundary-exchange policy (DESIGN.md §10): replay the SAME schedule
        # IR every lane follows and precompute, per adaptive-interval start
        # fine step, (read_factor, trail_kind, fill): read_factor is the K/V
        # extrapolation coefficient applied BEFORE the interval (0.0 =
        # fresh/stale reuse), trail_kind the exchange at the boundary AFTER
        # it, fill whether the displaced pipe refills entering it. Lanes are
        # grouped by this info, so one batched dispatch never mixes boundary
        # behaviors.
        self._interval_info: Dict[int, Tuple[float, str, bool, int]] = {}
        read_factor = 0.0
        m_prev: Optional[int] = None
        m_last = plan.temporal.m_warmup - 1   # warmup publish (-1 = boot)
        cur: Optional[int] = None
        fill = False
        seq_hops = 0
        for ev in ir.lower(plan.temporal, plan.patches, self.policy,
                           stages=self.stages if staged else None,
                           seq_shards=self.seq):
            if isinstance(ev, ir.StageShift):
                fill = True
            elif isinstance(ev, ir.SeqShard):
                seq_hops = ev.hops
            elif isinstance(ev, ir.ComputeInterval):
                cur = ev.fine_step
            elif isinstance(ev, ir.Exchange):
                self._interval_info[cur] = (read_factor, ev.kind, fill,
                                            seq_hops)
                fill = False
                seq_hops = 0
                if ev.kind == "full":
                    m_prev, m_last = m_last, ev.fine_step
                    read_factor = 0.0
                elif ev.kind == "skip":
                    read_factor = 0.0            # stale reuse
                elif ev.kind == "predict":
                    read_factor = (buf_lib.extrapolation_factor(
                        m_prev, m_last, ev.fine_step)
                        if m_prev is not None else 0.0)
        # last-but-one published K/V per lane (predictive extrapolation
        # base): these double the per-slot staged-KV footprint and cost a
        # copy per full boundary, so only materialize them when some
        # boundary actually extrapolates — never for staged steppers,
        # whose displaced contexts subsume prediction (predict == skip at
        # S > 1; extrapolated pub buffers would never be attended)
        self._track_prev = (not staged
                            and any(info[0] for info in
                                    self._interval_info.values()))
        if self._track_prev and self._prev_k is None:
            self._prev_k = jnp.zeros(self._kshape, self._kdt)
            self._prev_v = jnp.zeros(self._kshape, self._kdt)
        if self._track_prev and self._gk is not None and self._prev_gk is None:
            self._prev_gk = jnp.zeros(self._kshape2, self._kdt)
            self._prev_gv = jnp.zeros(self._kshape2, self._kdt)
        # per-lane comm sizing: taken from the same trace builder the
        # simulate backend replays, so serving cost accounting cannot
        # diverge from simulate_trace's
        trace = sim.build_trace(plan.temporal, plan.patches, cfg,
                                batch=1, stages=self.stages)
        self._latent_bytes = trace.latent_bytes
        self._kv_bytes = trace.kv_bytes_per_worker
        self._act_row_bytes = trace.act_row_bytes

    # ---------------- submission & admission ----------------

    def submit(self, x_T, cond, *, slo_s: Optional[float] = None,
               uid: Optional[int] = None,
               cfg_scale: Optional[float] = None) -> DiffusionRequest:
        """Queue one request. x_T: [H,W,C] or [1,H,W,C]; cond: int or [1].

        cfg_scale > 0 makes this a GUIDED request (classifier-free
        guidance, DESIGN.md §12); None inherits the pipeline config's
        cfg_scale (0 = unguided). CFG and non-CFG requests mix freely —
        guidance state is per lane.

        With a text-conditioned model (DESIGN.md §17) ``cond`` is a
        prompt-token tensor ``[L, cond_dim+1]`` or ``[1, L, cond_dim+1]``
        from :func:`repro.models.text_encoder.encode`; lane groups are
        keyed by the length bucket L, so one batched dispatch never mixes
        buckets.
        """
        x_T = jnp.asarray(x_T)
        if self.frames is not None:
            # video lane request: one clip = [F,H,W,C] or [1,F,H,W,C]
            if x_T.ndim == 4:
                x_T = x_T[None]
            if x_T.ndim != 5 or x_T.shape[0] != 1:
                raise ValueError(
                    "one request = one clip; video lanes take [F,H,W,C] "
                    f"or [1,F,H,W,C], got shape {tuple(x_T.shape)}")
            if x_T.shape[1] != self.frames.num_frames:
                raise ValueError(
                    f"request carries {x_T.shape[1]} frames, the plan "
                    f"serves {self.frames.num_frames}")
            if cfg_scale is not None and cfg_scale > 0:
                # guided video (DESIGN.md §17): the clip runs its WHOLE
                # schedule through the frame executor under the PLAN's
                # fused guidance — a per-request scale cannot override it
                gplan = self.plan.guidance
                if gplan is None:
                    raise ValueError(
                        "guided video lanes run the plan's fused CFG: "
                        "plan with cfg_scale > 0 (e.g. "
                        "planner='stadi_video') instead of a per-request "
                        "scale")
                if float(cfg_scale) != float(gplan.scale):
                    raise ValueError(
                        "video lanes run whole-clip schedules through the "
                        f"planned executor: per-request cfg_scale="
                        f"{cfg_scale} cannot override the plan's fused "
                        f"scale {gplan.scale}")
        elif x_T.ndim == 3:
            x_T = x_T[None]
        if x_T.shape[0] != 1:
            raise ValueError("one request = one image; got batch "
                             f"{x_T.shape[0]} (submit per image)")
        if self._prompt_mode:
            cond = jnp.asarray(cond, jnp.float32)
            if cond.ndim == 2:
                cond = cond[None]
            if cond.ndim != 3 or cond.shape[0] != 1:
                raise ValueError(
                    "a text-conditioned model takes prompt tokens "
                    "[L, cond_dim+1] or [1, L, cond_dim+1] (see "
                    "repro.models.text_encoder.encode), got shape "
                    f"{tuple(jnp.shape(cond))}")
            mcfg = self.pipeline.model_cfg
            if cond.shape[-1] != mcfg.cond_dim + 1:
                raise ValueError(
                    f"prompt tokens carry cond_dim+1={mcfg.cond_dim + 1} "
                    f"channels (features + validity mask), got "
                    f"{cond.shape[-1]}")
            if not 1 <= cond.shape[1] <= mcfg.cond_seq_len:
                raise ValueError(
                    f"prompt bucket {cond.shape[1]} is outside "
                    f"[1, cond_seq_len={mcfg.cond_seq_len}]")
        else:
            if getattr(np.asarray(cond), "ndim", 0) >= 2:
                raise ValueError(
                    "prompt-token cond needs a text-conditioned model "
                    "(DiTConfig.cross_attn=True, e.g. "
                    "cfg.text_conditioned()); this engine serves class-"
                    "conditional requests")
            cond = jnp.asarray(cond, jnp.int32).reshape((1,))
        if uid is None:
            uid, self._next_uid = self._next_uid, self._next_uid + 1
        else:
            self._next_uid = max(self._next_uid, uid + 1)
        if cfg_scale is None:
            cfg_scale = self.default_scale
        req = DiffusionRequest(uid=uid, x_T=x_T, cond=cond, slo_s=slo_s,
                               cfg_scale=cfg_scale)
        if req.guided and self.frames is None:
            if not self.stepper.supports_guidance:
                raise ValueError(
                    f"backend {self.pipeline.config.backend!r} has no "
                    "guided serving stepper (guided requests need "
                    "'emulated' or single-stage 'pipefuse')")
            if self._gk is None:
                self._gk = jnp.zeros(self._kshape2, self._kdt)
                self._gv = jnp.zeros(self._kshape2, self._kdt)
                if self._track_prev:
                    self._prev_gk = jnp.zeros(self._kshape2, self._kdt)
                    self._prev_gv = jnp.zeros(self._kshape2, self._kdt)
        req.submit_round = len(self.rounds)
        req.submit_clock_s = self.modeled_clock_s
        req._submit_wall = time.perf_counter()
        self.queue.append(req)
        return req

    def _admit(self, report: RoundReport) -> None:
        M_w = self.plan.temporal.m_warmup
        while self.queue and len(self.active) < self.slots:
            req = self.queue.pop(0)
            slot = next(s for s in range(self.slots) if s not in self.active)
            self._x = self._x.at[slot].set(req.x_T)
            if not self._prompt_mode:    # prompt conds live on the request
                self._cond = self._cond.at[slot].set(req.cond)
            self._scales[slot] = req.cfg_scale if req.guided else 0.0
            req.fine_step = 0
            req.admit_round = report.index
            if M_w == 0:
                # run_schedule's buffer bootstrap: one full forward at ts[0]
                # (shares the jit cache with the single-request engine)
                if req.guided:
                    _, _, kvs2 = pp._jit_guided_full_step(
                        self.pipeline.params, self.pipeline.model_cfg,
                        req.x_T, self._ts[0], req.cond, req.cfg_scale)
                    self._gk = self._gk.at[slot].set(kvs2[0])
                    self._gv = self._gv.at[slot].set(kvs2[1])
                else:
                    _, kvs = pp._jit_full_step(self.pipeline.params,
                                               self.pipeline.model_cfg,
                                               req.x_T, self._ts[0],
                                               req.cond)
                    self._pub_k = self._pub_k.at[slot].set(kvs[0])
                    self._pub_v = self._pub_v.at[slot].set(kvs[1])
            self.active[slot] = req
            report.admitted.append((req.uid, slot))

    def preempt(self, uid: int) -> bool:
        """Evict an active request back to the FRONT of the queue (it
        restarts from x_T on readmission — diffusion state is cheap to
        recompute relative to holding a slot past an SLO breach). True if
        the request was active; False if it was queued or already done."""
        for slot, req in list(self.active.items()):
            if req.uid == uid:
                del self.active[slot]
                req.fine_step = 0
                req.preempt_count += 1
                self.preemptions += 1
                self.queue.insert(0, req)
                return True
        return False

    # ---------------- online replanning (DESIGN.md §7.1 + §12/§14) -------

    def _feed_profiler(self) -> None:
        """One adaptive round's synthesized per-device interval timings.
        Under split guidance each logical worker feeds BOTH its pair
        devices, so the profiler sees every device's true speed."""
        temporal = self.plan.temporal
        subs = [0] * len(self.plan.patches)
        for i in temporal.active:
            if self.plan.patches[i] > 0:
                subs[i] = temporal.lcm // temporal.ratios[i]
        hetero.feed_profiler(self.profiler, self.cm, subs, self.plan.patches,
                             self.measured_speeds,
                             device_map=self._guide_pairs)

    def _maybe_replan(self) -> None:
        """Drift check at the rebalance cadence: when the profiled speeds
        left the planned ones behind, re-run the configured planner over
        them (re-pairing cond/uncond device groups under split guidance),
        invalidate the now-stale plan-cache entry, and stage the new plan
        for installation at the next grid-aligned round."""
        drift = self.profiler.drift(self._baseline)
        if drift <= self.rebalance_threshold:
            return
        pipe = self.pipeline
        stale_key = pipe.last_plan_key
        new = pipe.plan(self.profiler.speeds)
        if (pipe.plan_cache is not None and stale_key
                and stale_key != pipe.last_plan_key):
            pipe.plan_cache.invalidate(stale_key)
        self._pending_plan = (new, drift)

    def _try_install_pending(self) -> None:
        """Install a staged replan once every active adaptive lane sits on
        the new plan's interval grid (lanes advance plan.lcm fine steps per
        round, so a misaligned cohort retries next round)."""
        new, drift = self._pending_plan
        M_w = self.plan.temporal.m_warmup
        for req in self.active.values():
            if req.fine_step > M_w and (req.fine_step - M_w) % new.temporal.lcm:
                return
        self._pending_plan = None
        fine = min((r.fine_step for r in self.active.values()), default=M_w)
        self.replans.append(ReplanEvent(fine, drift, list(self._baseline),
                                        list(self.profiler.speeds), new))
        self._baseline = list(self.profiler.speeds)
        self._install_plan(new)

    # ---------------- one scheduling round ----------------

    def step(self) -> List[DiffusionRequest]:
        """One round: admit -> warmup group -> adaptive group(s) -> retire."""
        report = RoundReport(index=len(self.rounds))
        wall0 = time.perf_counter()
        with obs.span("engine.round"):
            if self.frames is not None:
                return self._frames_round(report, wall0)
            if self._pending_plan is not None:
                with obs.span("engine.cost_model"):
                    self._try_install_pending()
            with obs.span("engine.admit"):
                self._admit(report)
            M_w = self.plan.temporal.m_warmup
            warm = sorted(s for s, r in self.active.items()
                          if r.fine_step < M_w)
            adapt = sorted(s for s, r in self.active.items()
                           if r.fine_step >= M_w)
            report.warmup_lanes, report.adaptive_lanes = warm, adapt
            self._warmup_phase(report, warm)
            if adapt:
                self._adaptive_phase(report, adapt)
            self.modeled_clock_s += report.modeled_s
            return self._retire(report, wall0)

    def _warmup_phase(self, report: RoundReport, warm: List[int]) -> None:
        """One synchronous fine step for every warm-up lane, one batched
        dispatch per (guided, bucket) batch."""
        for guided, bucket, lanes in self._by_guided(warm):
            with obs.span("engine.lanes"):
                idx = self._pad(lanes)
                fine = np.asarray([self.active[s].fine_step for s in idx])
                args = (self._x[idx], self._ts[fine], self._ts[fine + 1],
                        self._conds(idx))
                if guided:
                    args += (jnp.asarray(self._scales[idx]),)
            with self._group_span("exec.warmup", lanes, idx):
                out = (self.stepper.warmup_step_guided(*args) if guided
                       else self.stepper.warmup_step(*args))
            if guided:
                xs, k2s, v2s = out
                with obs.span("engine.lanes"):
                    self._x = self._x.at[idx].set(xs)
                    self._gk = self._gk.at[idx].set(k2s)
                    self._gv = self._gv.at[idx].set(v2s)
            else:
                with obs.span("engine.lanes"):
                    self._scatter(idx, *out)
            for s in lanes:
                self.active[s].fine_step += 1
            with obs.span("engine.cost_model"):
                _, cost = self._phase_cost(len(lanes), warm=True,
                                           guided=guided,
                                           cond_tokens=bucket)
            report.modeled_s += cost

    def _adaptive_phase(self, report: RoundReport, adapt: List[int]) -> None:
        """One adaptive interval (plan.lcm fine steps) for every adaptive
        lane, one batched interval per lane group."""
        R = self.plan.temporal.lcm
        placement = None
        wants_ctx = getattr(self.stepper, "wants_ctx", False)
        for group, (read_factor, trail_kind, fill, seq_hops,
                    guided, bucket) in self._groups(adapt):
            merge = trail_kind == "full"
            if guided:               # branch-stacked per-lane CFG state
                with obs.span("engine.lanes"):
                    idx = self._pad(group)
                    fine = np.asarray([self.active[s].fine_step
                                       for s in idx])
                    bk, bv = self._gk[idx], self._gv[idx]
                    if read_factor:
                        bk = buf_lib.extrapolate_arrays(
                            bk, self._prev_gk[idx], read_factor)
                        bv = buf_lib.extrapolate_arrays(
                            bv, self._prev_gv[idx], read_factor)
                    args = (self._x[idx], fine, self._conds(idx),
                            jnp.asarray(self._scales[idx]), bk, bv)
                with self._group_span("exec.interval", group, idx):
                    xs, ks, vs = self.stepper.interval_guided(*args,
                                                              merge=merge)
                with obs.span("engine.lanes"):
                    self._x = self._x.at[idx].set(xs)
                    if merge:
                        if self._track_prev:
                            self._prev_gk = self._prev_gk.at[idx].set(
                                self._gk[idx])
                            self._prev_gv = self._prev_gv.at[idx].set(
                                self._gv[idx])
                        self._gk = self._gk.at[idx].set(ks)
                        self._gv = self._gv.at[idx].set(vs)
                for s in group:
                    self.active[s].fine_step += R
                with obs.span("engine.cost_model"):
                    placement, cost = self._phase_cost(
                        len(group), warm=False, kind=trail_kind, fill=fill,
                        guided=True, seq_hops=seq_hops, cond_tokens=bucket)
                report.modeled_s += cost
                report.exchange_kinds.append(trail_kind)
                continue
            with obs.span("engine.lanes"):
                idx = self._pad(group)
                fine = np.asarray([self.active[s].fine_step for s in idx])
                bk, bv = self._pub_k[idx], self._pub_v[idx]
                # predictive boundary before this group — staged steppers
                # never read the extrapolation (ctx subsumes it), so skip
                if read_factor and not wants_ctx:
                    bk = buf_lib.extrapolate_arrays(bk, self._prev_k[idx],
                                                    read_factor)
                    bv = buf_lib.extrapolate_arrays(bv, self._prev_v[idx],
                                                    read_factor)
                if wants_ctx and fill:   # pipe refill: contexts <- published
                    self._ctx_k = self._ctx_k.at[idx].set(self._pub_k[idx])
                    self._ctx_v = self._ctx_v.at[idx].set(self._pub_v[idx])
                args = (self._x[idx], fine, self._conds(idx), bk, bv)
                if wants_ctx:
                    args += (self._ctx_k[idx], self._ctx_v[idx])
            with self._group_span("exec.interval", group, idx):
                if wants_ctx:
                    xs, ks, vs, ck, cv = self.stepper.interval_ctx(
                        *args, merge=merge)
                else:
                    xs, ks, vs = self.stepper.interval(*args, merge=merge)
            with obs.span("engine.lanes"):
                if wants_ctx:
                    self._ctx_k = self._ctx_k.at[idx].set(ck)
                    self._ctx_v = self._ctx_v.at[idx].set(cv)
                self._x = self._x.at[idx].set(xs)
                if merge:
                    if self._track_prev:
                        # pre-merge buffers become the extrapolation base
                        self._prev_k = self._prev_k.at[idx].set(
                            self._pub_k[idx])
                        self._prev_v = self._prev_v.at[idx].set(
                            self._pub_v[idx])
                    self._pub_k = self._pub_k.at[idx].set(ks)
                    self._pub_v = self._pub_v.at[idx].set(vs)
            for s in group:
                self.active[s].fine_step += R
            with obs.span("engine.cost_model"):
                placement, cost = self._phase_cost(len(group), warm=False,
                                                   kind=trail_kind,
                                                   fill=fill,
                                                   seq_hops=seq_hops,
                                                   cond_tokens=bucket)
            report.modeled_s += cost
            report.exchange_kinds.append(trail_kind)
        report.placement = placement
        if self.profiler is not None:
            with obs.span("engine.cost_model"):
                self._feed_profiler()
                self._rounds_since_check += 1
                if (self._rounds_since_check >= self.rebalance_every
                        and self._pending_plan is None):
                    self._rounds_since_check = 0
                    self._maybe_replan()

    @staticmethod
    def _group_span(name: str, lanes: Sequence[int], idx: np.ndarray):
        """The span of one stepper call over a lane group: ``lanes`` real
        lanes, padded to ``len(idx)`` (the profiler reads the padding)."""
        return obs.span(name, lanes=len(lanes), padded=len(idx) - len(lanes))

    def _retire(self, report: RoundReport,
                wall0: float) -> List[DiffusionRequest]:
        """Hand out the images of lanes that reached the last fine step and
        free their slots; closes the round's report."""
        M_base = self.plan.temporal.m_base
        with obs.span("engine.retire"):
            done_slots = [s for s, r in sorted(self.active.items())
                          if r.fine_step >= M_base]
            if done_slots:       # flush async dispatch BEFORE stamping wall
                jax.block_until_ready(self._x)
            finished = []
            for slot in done_slots:
                req = self.active.pop(slot)
                req.image = self._x[slot]
                req.done = True
                req.finish_round = report.index
                req.modeled_latency_s = (self.modeled_clock_s
                                         - req.submit_clock_s)
                req.wall_latency_s = time.perf_counter() - req._submit_wall
                finished.append(req)
            self.completed.extend(finished)
        report.wall_s = time.perf_counter() - wall0
        self.rounds.append(report)
        return finished

    def _frames_round(self, report: RoundReport,
                      wall0: float) -> List[DiffusionRequest]:
        """One video round (DESIGN.md §16): admit FIFO into free slots,
        then run every admitted clip's full multi-frame schedule
        back-to-back on the cluster through the configured frame executor.
        Each clip accrues the frame-priced schedule makespan (the same
        number ``simulate_trace`` gives the planner), sequentially — the
        cluster serves one clip at a time, so later clips in the round
        see the earlier clips' service time as queueing delay."""
        from repro.core.pipeline import get_executor
        config = self.pipeline.config
        M_base = self.plan.temporal.m_base
        while self.queue and len(self.active) < self.slots:
            req = self.queue.pop(0)
            slot = next(s for s in range(self.slots) if s not in self.active)
            req.fine_step = 0
            req.admit_round = report.index
            self.active[slot] = req
            report.admitted.append((req.uid, slot))
        executor = get_executor(config.backend)
        finished: List[DiffusionRequest] = []
        for slot in sorted(self.active):
            req = self.active.pop(slot)
            image, _ = executor(
                params=self.pipeline.params,
                model_cfg=self.pipeline.model_cfg,
                sched=self.pipeline.sched, x_T=req.x_T, cond=req.cond,
                plan=self.plan, config=config, interval_hook=None)
            image = jax.block_until_ready(image)
            report.modeled_s += self._clip_cost_s
            self.modeled_clock_s += self._clip_cost_s
            req.image = image
            req.fine_step = M_base
            req.done = True
            req.finish_round = report.index
            req.modeled_latency_s = self.modeled_clock_s - req.submit_clock_s
            req.wall_latency_s = time.perf_counter() - req._submit_wall
            finished.append(req)
        self.completed.extend(finished)
        report.wall_s = time.perf_counter() - wall0
        self.rounds.append(report)
        return finished

    def run_to_completion(self, max_rounds: int = 100_000
                          ) -> List[DiffusionRequest]:
        done: List[DiffusionRequest] = []
        rounds = 0
        while (self.queue or self.active) and rounds < max_rounds:
            done.extend(self.step())
            rounds += 1
        if self.queue or self.active:
            raise RuntimeError(f"undrained after {max_rounds} rounds")
        return done

    # ---------------- lane plumbing ----------------

    def _pad(self, lanes: Sequence[int]) -> np.ndarray:
        """Pad a lane group to the full slot count (stable jit shapes) by
        repeating the first lane; duplicate lanes compute duplicate values,
        so the scatter-back is value-identical regardless of write order."""
        return np.asarray(list(lanes)
                          + [lanes[0]] * (self.slots - len(lanes)))

    def _conds(self, idx: np.ndarray) -> jnp.ndarray:
        """Lane-stacked conditioning for a padded lane group: the
        slot-major int buffer for class lanes; in prompt mode (§17) a
        stack of the requests' token tensors [G, 1, L, cond_dim+1] — the
        lane-group key pins one length bucket L per dispatch, so the
        stack is rectangular by construction."""
        if not self._prompt_mode:
            return self._cond[idx]
        return jnp.stack([self.active[s].cond for s in idx])

    def _scatter(self, idx: np.ndarray, xs, ks, vs) -> None:
        # the lanes' K/V comes out in the activation dtype (f32 latents
        # promote a bf16 model's activations); the buffers keep cfg.dtype
        self._x = self._x.at[idx].set(xs)
        self._pub_k = self._pub_k.at[idx].set(ks.astype(self._pub_k.dtype))
        self._pub_v = self._pub_v.at[idx].set(vs.astype(self._pub_v.dtype))

    def _lane_bucket(self, slot: int) -> int:
        """The lane's prompt length bucket (0 for class-conditional
        lanes): prompt-token tensors of different buckets cannot share a
        stacked dispatch, so the bucket joins every lane-group key (§17)."""
        return (self.active[slot].cond.shape[1] if self._prompt_mode
                else 0)

    def _by_guided(self, lanes: List[int]
                   ) -> List[Tuple[bool, int, List[int]]]:
        """Split a lane list into (guided?, bucket, lanes) batches, plain
        first — CFG and non-CFG lanes run different dispatch shapes, and
        prompt lanes of different length buckets different cond shapes."""
        keyed: Dict[Tuple[bool, int], List[int]] = {}
        for s in lanes:
            keyed.setdefault((self.active[s].guided,
                              self._lane_bucket(s)), []).append(s)
        return [(g, b, keyed[(g, b)]) for g, b in sorted(keyed)]

    def _groups(self, lanes: List[int]
                ) -> List[Tuple[List[int],
                                Tuple[float, str, bool, int, bool, int]]]:
        """Batchable lane groups + their (read_factor, trail_kind, fill,
        seq_hops, guided, bucket) info. The vmapped stepper batches every
        lane whose boundary behavior, seq-shard ring identity, guidance
        state AND prompt length bucket match (under "sync" with no CFG
        lanes, no seq sharding and one bucket that is ONE group, as
        before); the cohort-only (spmd) stepper groups by fine-step
        position and bucket, which pins the exchange info automatically
        (it never serves guided lanes)."""
        if not self.stepper.cohort_only:
            keyed: Dict[Tuple[float, str, bool, int, bool, int],
                        List[int]] = {}
            for s in lanes:
                keyed.setdefault(self._lane_info(s), []).append(s)
            return [(keyed[k], k) for k in sorted(keyed)]
        cohorts: Dict[Tuple[int, int], List[int]] = {}
        for s in lanes:
            key = (self.active[s].fine_step, self._lane_bucket(s))
            cohorts.setdefault(key, []).append(s)
        return [(cohorts[k], self._lane_info(cohorts[k][0]))
                for k in sorted(cohorts)]

    def _lane_info(self, slot: int
                   ) -> Tuple[float, str, bool, int, bool, int]:
        info = self._interval_info[self.active[slot].fine_step]
        return info + (self.active[slot].guided, self._lane_bucket(slot))

    # ---------------- modeled cost & placement ----------------

    def _phase_cost(self, group: int, warm: bool, kind: str = "full",
                    fill: bool = False, guided: bool = False,
                    seq_hops: int = 0, cond_tokens: int = 0
                    ) -> Tuple[Tuple[Tuple[int, int], ...], float]:
        """Placement + modeled seconds for one batched phase of a round.

        Mirrors ``simulate.simulate_trace`` with compute scaled by the lane
        count: batching multiplies the per-row work but amortizes t_fixed —
        the modeled reason continuous batching beats sequential serving.
        Latent traffic is the per-worker uneven all-gather (padded slabs),
        and "skip"/"predict" boundaries move no bytes at all. With a stage
        chain (DESIGN.md §11) the placement maps STAGES to devices instead
        of whole-model patch workers. Guided (fused-CFG) phases double the
        per-row work and the staged-K/V payload — both branches ride every
        lane (DESIGN.md §12). Sequence-sharded lanes (DESIGN.md §13) run
        each patch worker on a GROUP of ``seq.n_shards`` devices (placement
        entries map workers to groups, speed = group aggregate) and overlap
        ``seq_hops`` ring K/V hops per substep with compute, exactly as in
        ``simulate._simulate_seq``. Prompt lanes (DESIGN.md §17) add the
        cross-attention read ``t_xattn * cond_tokens`` per row per branch,
        exactly as ``simulate_trace`` prices it.
        """
        if self.stages is not None and len(self.stages) > 1:
            return self._staged_phase_cost(group, warm, kind, fill,
                                           cond_tokens)
        if guided and self._guide_pairs is not None:
            return self._split_phase_cost(group, warm, kind, cond_tokens)
        plan, cm = self.plan, self.cm
        temporal = plan.temporal
        branch = 2 if guided else 1
        t_row_eff = cm.t_row + cm.t_xattn * cond_tokens
        workers = [i for i in temporal.active if plan.patches[i] > 0]
        loads = {}
        for i in workers:
            sub = 1 if warm else temporal.lcm // temporal.ratios[i]
            loads[i] = sub * (cm.t_fixed
                              + t_row_eff * plan.patches[i] * group * branch)
        by_load = sorted(workers, key=lambda i: (-loads[i], i))
        speeds = self.measured_speeds
        if self._seq_groups is not None:
            # each worker = one device group; the group's members split the
            # worker's rows/heads, so its serving throughput is the sum
            speeds = [sum(g) for g in self._seq_groups]
        by_speed = sorted(range(len(speeds)), key=lambda d: (-speeds[d], d))
        placement = tuple(sorted((w, d) for w, d in zip(by_load, by_speed)))
        compute = max(loads[w] / max(speeds[d], 1e-9)
                      for w, d in placement)
        ring_t = 0.0
        if self._seq_groups is not None:
            hops = (self.seq.n_shards - 1) if warm else seq_hops
            if hops:
                for w in workers:
                    sub = 1 if warm else temporal.lcm // temporal.ratios[w]
                    ring_t = max(ring_t, sub * hops * (
                        self._kv_bytes[w] * self._seq_seg_pad * group
                        * branch / cm.link_bw + cm.link_latency))
        if (not warm and kind != "full") or len(workers) <= 1:
            # stale/predict (or lone worker): no gather, but ring hops
            # still serialize against compute
            return placement, max(compute, ring_t)
        rows_total = max(sum(plan.patches), 1)
        row_bytes = self._latent_bytes / rows_total
        gather_rows = comm_lib.uneven_all_gather_rows(
            [plan.patches[i] for i in workers])
        comm_bytes = gather_rows * row_bytes * group
        if warm:
            comm_bytes += sum(self._kv_bytes[w] for w in workers) \
                * group * branch
            async_t = 0.0
        else:
            async_t = max(self._kv_bytes[w] for w, _ in placement) \
                * group * branch / cm.link_bw
        comm = comm_bytes / cm.link_bw + cm.link_latency
        return placement, max(compute, async_t, ring_t) + comm

    def _split_phase_cost(self, group: int, warm: bool, kind: str = "full",
                          cond_tokens: int = 0
                          ) -> Tuple[Tuple[Tuple[int, int], ...], float]:
        """Split-guidance cohort placement + modeled seconds (DESIGN.md
        §12/§14): logical worker i runs BOTH branches concurrently on its
        (cond, uncond) device pair — per-row work is NOT doubled but the
        pair moves at its slower member — and every substep exchanges the
        two branches' epsilons across the pair link before the CFG combine.
        Mirrors ``planners._guided_plan_cost``'s fresh split interval (the
        planner's scoring and the engine's accounting cannot diverge);
        batching scales row work and wire bytes by the lane count.
        Placement entries are (worker, cond_device) — the pairing is the
        plan's, not a per-round search (re-pairing happens at replans).
        """
        plan, cm, g = self.plan, self.cm, self.plan.guidance
        temporal = plan.temporal
        speeds = self.measured_speeds
        workers = [i for i in temporal.active if plan.patches[i] > 0]
        rows_total = max(sum(plan.patches), 1)
        row_bytes = self._latent_bytes / rows_total
        compute, eps_bytes, hops = 0.0, 0.0, 0
        for i in workers:
            sub = 1 if warm else temporal.lcm // temporal.ratios[i]
            rows = plan.patches[i]
            pair_v = min(speeds[g.cond_devices[i]],
                         speeds[g.uncond_devices[i]])
            step_t = cm.t_fixed + (cm.t_row + cm.t_xattn * cond_tokens) \
                * rows * group
            compute = max(compute, sub * step_t / max(pair_v, 1e-9))
            eps_bytes += 2 * sub * rows * row_bytes * group
            hops = max(hops, sub)
        eps_t = eps_bytes / cm.link_bw + hops * cm.link_latency
        placement = tuple(sorted((i, g.cond_devices[i]) for i in workers))
        if (not warm and kind != "full") or len(workers) <= 1:
            return placement, compute + eps_t
        gather_rows = comm_lib.uneven_all_gather_rows(
            [plan.patches[i] for i in workers])
        comm_bytes = gather_rows * row_bytes * group
        if warm:
            # branch factor 1: each branch's staged K/V stays inside its
            # own device group, the two groups broadcast concurrently
            comm_bytes += sum(self._kv_bytes[w] for w in workers) * group
            async_t = 0.0
        else:
            async_t = max(self._kv_bytes[w] for w in workers) \
                * group / cm.link_bw
        comm = comm_bytes / cm.link_bw + cm.link_latency
        return placement, max(compute, async_t) + comm + eps_t

    def _staged_phase_cost(self, group: int, warm: bool, kind: str,
                           fill: bool, cond_tokens: int = 0
                           ) -> Tuple[Tuple[Tuple[int, int], ...], float]:
        """Stage-chain placement + modeled seconds (DESIGN.md §11): stage d
        (chain order, heaviest block share first by construction) runs on
        the d-th fastest device; micro-batches stream through the chain, so
        steady state is bottleneck-stage-bound with point-to-point
        activation handoffs, a fill bubble on refill rounds, and a latent
        ring handoff on draining boundaries. K/V never crosses stages.
        Placement entries are (stage, device)."""
        plan, cm = self.plan, self.cm
        if cond_tokens:
            # fold the cross-attn read into the row rate, exactly as
            # simulate._simulate_staged does (DESIGN.md §17)
            cm = dataclasses.replace(
                cm, t_row=cm.t_row + cm.t_xattn * cond_tokens)
        temporal = plan.temporal
        S = len(self.stages)
        speeds = self.measured_speeds
        by_speed = sorted(range(len(speeds)), key=lambda d: (-speeds[d], d))
        chain = [speeds[d] for d in by_speed[:S]]
        placement = tuple((s, by_speed[s]) for s in range(S))
        if warm:
            return placement, sim.pipefuse_warmup_seconds(
                self.stages, chain, cm, sum(plan.patches) * group,
                self._act_row_bytes)
        workers = [i for i in temporal.active if plan.patches[i] > 0]
        tasks = [(temporal.lcm // temporal.ratios[i],
                  plan.patches[i] * group) for i in workers]
        return placement, sim.pipefuse_interval_seconds(
            self.stages, chain, cm, tasks, fill, kind,
            self._latent_bytes * group, self._act_row_bytes)

    # ---------------- reporting ----------------

    def stats(self) -> Dict:
        """Aggregate + per-request serving statistics (modeled + wall)."""
        from repro.kernels import ops as kops
        done = sorted(self.completed, key=lambda r: r.uid)
        lats = [r.modeled_latency_s for r in done]
        wall = sum(r.wall_s for r in self.rounds)
        slo = [r.slo_met for r in done if r.slo_met is not None]
        cache = self.pipeline.plan_cache
        return {
            "n_completed": len(done),
            "cost_model": ("configured" if self.cm_calibrated
                           else "default-uncalibrated"),
            "rounds": len(self.rounds),
            "replans": len(self.replans),
            "preemptions": self.preemptions,
            "planner_calls": self.pipeline.planner_calls,
            "plan_cache": cache.stats() if cache is not None else None,
            # trace-time Pallas kernel path counters (DESIGN.md §15):
            # answers "did the programs compiled since this engine was
            # built contain the kernels?"
            "kernels": kops.kernel_stats_delta(
                self._kernel_stats_base, kops.kernel_stats_snapshot()),
            "modeled_makespan_s": self.modeled_clock_s,
            "wall_s": wall,
            "throughput_modeled_rps": (len(done) / self.modeled_clock_s
                                       if self.modeled_clock_s else 0.0),
            "throughput_wall_rps": len(done) / wall if wall else 0.0,
            "latency_mean_s": float(np.mean(lats)) if lats else 0.0,
            "latency_p95_s": float(np.percentile(lats, 95)) if lats else 0.0,
            "slo_met_frac": (sum(slo) / len(slo)) if slo else None,
            "requests": [{
                "uid": r.uid,
                "queue_rounds": r.queue_rounds,
                "service_rounds": r.finish_round - r.admit_round + 1,
                "modeled_latency_s": r.modeled_latency_s,
                "wall_latency_s": r.wall_latency_s,
                "slo_s": r.slo_s,
                "slo_met": r.slo_met,
                "preemptions": r.preempt_count,
            } for r in done],
        }
