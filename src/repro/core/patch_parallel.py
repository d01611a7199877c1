"""Patch-parallel diffusion inference engine (DistriFusion + STADI schedules).

Single-process EMULATION with exact numerics: N logical workers each own a
row-slab of the latent; stale-KV semantics follow DESIGN.md §2 (buffers are
carried state; async NCCL broadcast == merge-at-next-sync). The engine is an
*interpreter* of the schedule IR (:mod:`repro.core.events`): one event
stream drives the numerics here, the SPMD backend (core/spmd.py) and the
latency simulator (core/simulate.py), so schedule semantics cannot drift
between them (DESIGN.md §10).

Boundary exchange is a pluggable policy (:mod:`repro.core.comm`):
``sync`` merges fresh K/V at every interval boundary (bitwise-identical to
the pre-policy engine), ``stale_async`` skips the exchange on a cadence and
denoises against staler neighbor slabs, ``predictive`` extrapolates the
remote K/V from the last two exchanged versions.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro import obs
from repro.configs.diffusion import DiTConfig
from repro.core import buffers as buf_lib
from repro.core import comm as comm_lib
from repro.core import events as ir
from repro.core import sampler as sampler_lib
# re-exported for backward compatibility: these trace types now live in the
# IR module (events.py) next to the stream that produces them
from repro.core.events import ExecutionTrace, IntervalEvent  # noqa: F401
from repro.core.sampler import NoiseSchedule
from repro.core.schedule import TemporalPlan, patch_bounds
from repro.models.diffusion import dit


@dataclasses.dataclass
class RunResult:
    image: jnp.ndarray                   # [B,H,W,C] final x_0
    trace: ExecutionTrace


def _slab(x, bounds_rows_latent: Tuple[int, int]):
    return x[:, bounds_rows_latent[0]:bounds_rows_latent[1]]


def _stack_uncond(kv_c: Tuple, published: buf_lib.Published, tok_lo: int,
                  n_tok: int) -> Tuple:
    """Branch-stack a cond-only fresh K/V with the CURRENT published uncond
    rows (a no-op merge for the uncond branch): interleaved reuse intervals
    never recompute — and therefore never republish — a straggler worker's
    uncond branch (DESIGN.md §12). Shared by the emulated and pipefuse
    engines."""
    ku = jax.lax.dynamic_slice_in_dim(published.k[1], tok_lo, n_tok, axis=2)
    vu = jax.lax.dynamic_slice_in_dim(published.v[1], tok_lo, n_tok, axis=2)
    return jnp.stack([kv_c[0], ku]), jnp.stack([kv_c[1], vu])


@functools.partial(jax.jit, static_argnames=("cfg", "row_start"))
def _jit_patch_step(params, cfg, x_loc, t, cond, row_start, bk, bv):
    """Jitted hot loop body (one denoiser eval on a patch with stale KV).
    Keeps the engine's eager dispatch count bounded: thousands of unjitted
    eager ops exhaust the LLVM JIT's mmap budget on long runs."""
    return dit.forward_patch(params, cfg, x_loc, t, cond, row_start,
                             buffers=(bk, bv), return_kv=True)


@functools.partial(jax.jit, static_argnames=("cfg",))
def _jit_full_step(params, cfg, x, t, cond):
    return dit.forward_patch(params, cfg, x, t, cond, 0, buffers=None,
                             return_kv=True)


# ----------------------------------------------------------------------
# classifier-free guidance steps (DESIGN.md §12)
# ----------------------------------------------------------------------
#
# One branch-vmapped dispatch evaluates the conditional and unconditional
# forwards (the fused-batch form); buffers are branch-stacked
# [2, L, B, N, H, hd]. The split/interleaved guidance modes run the SAME
# jitted functions — the placement decision moves work between devices in
# the cost model, never between math — which is why split CFG is bitwise-
# identical to the fused reference under one schedule (tested).

def _cfg_tail(cfg, eps2, scale):
    """(eps_combined, delta) from the branch pair: the fused Pallas CFG
    epilogue when the config routes attention through kernels (one HBM
    pass computes both, DESIGN.md §15), else the two sampler formulas."""
    if cfg.use_pallas_attention:
        from repro.kernels import ops as kops
        return kops.cfg_epilogue(eps2[0], eps2[1], scale)
    return (sampler_lib.cfg_combine(eps2[0], eps2[1], scale),
            sampler_lib.cfg_delta(eps2[0], eps2[1]))


@functools.partial(jax.jit, static_argnames=("cfg",))
def _jit_guided_full_step(params, cfg, x, t, cond, scale):
    """Synchronous CFG step: returns (eps_combined, delta, (k2, v2)) with
    delta the guidance direction eps_c - eps_u (the interleaved cache)."""
    def one(c):
        return dit.forward_patch(params, cfg, x, t, c, 0, buffers=None,
                                 return_kv=True)
    eps2, kvs2 = jax.vmap(one)(dit.guidance_conds(cond))
    return _cfg_tail(cfg, eps2, scale) + (kvs2,)


@functools.partial(jax.jit, static_argnames=("cfg", "row_start"))
def _jit_guided_patch_step(params, cfg, x_loc, t, cond, row_start, bk2, bv2,
                           scale):
    """Guided stale-KV patch step: bk2/bv2 are branch-stacked published
    buffers [2, L, B, N, H, hd]. Returns (eps_combined, delta, (k2, v2))
    with k2/v2 [2, L, B, Nl, H, hd] — delta (= eps_c - eps_u) feeds the
    interleaved-reuse cache, the fresh K/V the per-branch publish."""
    def one(c, bk, bv):
        return dit.forward_patch(params, cfg, x_loc, t, c, row_start,
                                 buffers=(bk, bv), return_kv=True)
    eps2, kvs2 = jax.vmap(one)(dit.guidance_conds(cond), bk2, bv2)
    return _cfg_tail(cfg, eps2, scale) + (kvs2,)


def guided_substep(params, cfg, x_loc, t_from, cond, row_start, read_pub,
                   published, guidance, fresh: bool, ucache: dict, i: int,
                   first: bool):
    """One guided patch substep for worker ``i`` — the ONE home of the
    fresh-vs-straggler-reuse dispatch shared by ``run_schedule`` and the
    single-stage ``pipefuse`` interpreter (their loop orders differ, the
    per-substep CFG contract must not). Returns (eps, kvs) where kvs is
    the branch-stacked publish payload on ``first`` substeps (None
    otherwise for reuse workers); mutates ``ucache`` with the guidance
    delta on fresh evals."""
    tok_lo = row_start * cfg.tokens_per_side
    if fresh or not guidance.worker_reuses(i):
        # fused/split, interleaved refresh intervals, and non-straggler
        # workers (always fresh)
        eps, delta, kvs = _jit_guided_patch_step(
            params, cfg, x_loc, t_from, cond, row_start,
            read_pub.k, read_pub.v, guidance.scale)
        if guidance.mode == "interleaved":   # only reuse ever reads it
            ucache[i] = delta
        return eps, kvs
    # interleaved reuse: the straggler pair's uncond device idles the whole
    # interval — the guidance delta cached at the last refresh interval
    # stands in; only the cond branch runs (against its own branch's
    # buffers), and its first substep publishes with stale uncond rows
    eps_c, kv_c = _jit_patch_step(params, cfg, x_loc, t_from, cond,
                                  row_start, read_pub.k[0], read_pub.v[0])
    eps = sampler_lib.cfg_apply_delta(eps_c, ucache[i], guidance.scale)
    kvs = (_stack_uncond(kv_c, published, tok_lo, kv_c[0].shape[2])
           if first else None)
    return eps, kvs


def run_schedule(params, cfg: DiTConfig, sched: NoiseSchedule, x_T, cond,
                 plan: TemporalPlan, patches: Sequence[int],
                 interval_hook=None, exchange: str = "sync",
                 exchange_refresh: int = 2, guidance=None,
                 seq=None) -> RunResult:
    """Execute Algorithm 1 by interpreting the schedule IR event stream.

    sched: a :class:`~repro.core.sampler.NoiseSchedule` (DDIM updates of
    eps) or a :class:`~repro.core.sampler.FlowSchedule` (Euler updates of
    the velocity an MMDiT predicts; cond is then its ``TextCond``).

    patches: token-rows per worker (sum == cfg.tokens_per_side; 0 = excluded).
    Uniform plan (all ratios 1, equal patches) == DistriFusion patch
    parallelism; plan from Eq. 4/5 == STADI.

    interval_hook: optional ``hook(next_fine_step, event) -> None | (plan,
    patches)`` called after every adaptive interval boundary. Returning a new
    (TemporalPlan, patches) re-allocates the remaining fine steps — the
    online-rebalancing hot path used by :class:`repro.core.pipeline.
    StadiPipeline`. The remaining fine steps must be divisible by the new
    plan's interval LCM.

    exchange / exchange_refresh: boundary-exchange policy name + refresh
    cadence (see :func:`repro.core.comm.get_exchange`). "sync" reproduces
    the pre-policy engine bitwise.

    guidance: optional :class:`repro.core.guidance.GuidancePlan` (DESIGN.md
    §12). Every denoiser eval becomes a branch-vmapped CFG eval against
    branch-stacked published buffers; "fused" and "split" are bitwise-
    identical (placement only differs in the cost model), "interleaved"
    reuses the cached eps_u on non-refresh intervals per the IR's
    :class:`~repro.core.events.GuidanceExchange` verdicts.

    seq: optional :class:`repro.core.seqpar.SeqPlan` (DESIGN.md §13). The
    sequence dimension repartitions WHERE attention runs (Ulysses head
    groups x ring K/V segments), never WHAT it computes, so the emulated
    engine's numerics are shard-count invariant: the IR's
    :class:`~repro.core.events.SeqShard` events are replayed for trace
    provenance (per-interval ring hops) and the trace carries the plan for
    the ring-contention cost model; the head-scattered realization lives
    in ``spmd_seq``.
    """
    p = cfg.patch_size
    P = cfg.tokens_per_side              # token rows of the whole image
    M_base = plan.m_base
    plan0, patches0 = plan, list(patches)  # trace provenance: the initial
    # allocation; per-interval events record what actually executed
    ts = sampler_lib.timesteps(sched, M_base)   # fine grid, len M_base+1
    policy = comm_lib.get_exchange(exchange, exchange_refresh)
    guided = guidance is not None
    if guided:
        if cond is None:
            raise ValueError("guided generation needs a class condition")
        if interval_hook is not None:
            raise ValueError("online rebalancing is not supported with "
                             "guidance (the branch pairing is static)")
    tok_axis = 3 if guided else 2        # buffers gain a leading branch axis
    # an MMDiT dispatch carries the prompt's context tokens besides its rows
    ctx = ({"ctx": int(cond.context.shape[-2])} if cfg.family == "mmdit"
           else {})

    x = x_T
    B = x.shape[0]
    records: List[IntervalEvent] = []

    published: Optional[buf_lib.Published] = None   # last fully-exchanged K/V
    prev_published: Optional[buf_lib.Published] = None
    read_pub: Optional[buf_lib.Published] = None    # what substeps attend to
    pending = {}
    new_slabs = {}
    ucache = {}                          # interleaved: last eps_u per worker
    interval: Optional[ir.ComputeInterval] = None
    fresh = True                         # uncond recomputed this interval?
    seq_hops = 0                         # ring hops of the coming interval

    def _full_step(t):
        if guided:
            eps, _, kvs2 = _jit_guided_full_step(params, cfg, x, t, cond,
                                                 guidance.scale)
            return eps, kvs2
        return _jit_full_step(params, cfg, x, t, cond)

    gen = ir.lower(plan, patches, policy, guidance=guidance, seq_shards=seq)
    send = None
    while True:
        try:
            ev = gen.send(send)
        except StopIteration:
            break
        send = None

        if isinstance(ev, ir.Warmup):
            # synchronous step == exact full forward on every worker
            with obs.span("exec.warmup"):
                with obs.span("exec.buffers"):
                    t = ts[ev.fine_step]
                with obs.span("exec.model", rows=P, **ctx):
                    eps, kvs = _full_step(t)
                with obs.span("exec.buffers"):
                    t_from, t_to = ts[ev.fine_step], ts[ev.fine_step + 1]
                with obs.span("exec.sampler"):
                    x = sampler_lib.step(sched, x, eps, t_from, t_to)
                published = buf_lib.Published(kvs[0], kvs[1], ev.fine_step)
                read_pub = published
                records.append(ir.warmup_record(ev))

        elif isinstance(ev, ir.GuidanceExchange):
            fresh = ev.fresh             # verdict for the coming interval

        elif isinstance(ev, ir.SeqShard):
            # head/segment repartitioning only moves attention across the
            # ring — no numerics here; record the hop count for the trace
            seq_hops = ev.hops

        elif isinstance(ev, ir.ComputeInterval):
            with obs.span("exec.interval"):
                if published is None:    # M_w == 0: bootstrap buffers once
                    with obs.span("exec.buffers"):
                        t = ts[0]
                    with obs.span("exec.model", rows=P, **ctx):
                        _, kvs = _full_step(t)
                    published = buf_lib.Published(kvs[0], kvs[1], -1)
                    read_pub = published
                interval = ev
                bounds_tok = patch_bounds(ev.patches)
                bounds_lat = [(a * p, b * p) for a, b in bounds_tok]
                pending = {}
                new_slabs = {}
                for i in ev.workers:
                    r = ev.ratios[i]
                    with obs.span("exec.buffers"):
                        x_loc = _slab(x, bounds_lat[i])
                    tok_lo = bounds_tok[i][0] * cfg.tokens_per_side
                    for s in range(ev.substeps[i]):
                        with obs.span("exec.buffers"):
                            t_from = ts[ev.fine_step + s * r]
                            t_to = ts[ev.fine_step + (s + 1) * r]
                        with obs.span("exec.model", rows=ev.patches[i],
                                      **ctx):
                            if not guided:
                                eps, kvs = _jit_patch_step(
                                    params, cfg, x_loc, t_from, cond,
                                    bounds_tok[i][0], read_pub.k, read_pub.v)
                            else:
                                eps, kvs = guided_substep(
                                    params, cfg, x_loc, t_from, cond,
                                    bounds_tok[i][0], read_pub, published,
                                    guidance, fresh, ucache, i,
                                    first=(s == 0))
                        with obs.span("exec.sampler"):
                            x_loc = sampler_lib.step(sched, x_loc, eps,
                                                     t_from, t_to)
                        # Alg.1 l.16-17 / l.23: publish at interval start
                        if s == 0:
                            with obs.span("exec.buffers"):
                                buf_lib.publish_local(pending, i, kvs[0],
                                                      kvs[1], tok_lo)
                    new_slabs[i] = x_loc

        elif isinstance(ev, ir.Exchange):
            # every worker's slab write-back is local memory (disjoint rows);
            # the policy only gates the REMOTE traffic: K/V merge + gather
            with obs.span("exec.exchange"):
                bounds_lat = [(a * p, b * p) for a, b in
                              patch_bounds(ev.patches)]
                for i in interval.workers:
                    lat = bounds_lat[i]
                    x = x.at[:, lat[0]:lat[1]].set(new_slabs[i])
                if ev.kind == "full":
                    prev_published = published
                    published = buf_lib.merge(published, pending,
                                              ev.fine_step, axis=tok_axis)
                    read_pub = published
                elif ev.kind == "skip":
                    read_pub = published  # stale: pending never broadcast
                elif ev.kind == "predict":
                    read_pub = buf_lib.extrapolate(prev_published, published,
                                                   ev.fine_step)
                rec = ir.record(interval, ev.kind, uncond_fresh=fresh,
                                seq_hops=seq_hops)
            fresh = True
            records.append(rec)
            if interval_hook is not None and ev.fine_step < M_base:
                upd = interval_hook(ev.fine_step, rec)
                if upd is not None:
                    send = upd           # generator emits Replan + re-lowers

        # ir.Replan events need no numerics: the next ComputeInterval
        # already carries the new patches/ratios

    with obs.span("exec.record"):
        trace = ir.make_trace(records, plan0, patches0, cfg, int(B),
                              guidance=guidance, seq=seq)
    return RunResult(x, trace)


# ----------------------------------------------------------------------
# convenience wrappers
# ----------------------------------------------------------------------

def uniform_plan(n_workers: int, m_base: int, m_warmup: int) -> TemporalPlan:
    return TemporalPlan([m_base] * n_workers, [1] * n_workers,
                        [False] * n_workers, m_base, m_warmup)


def run_distrifusion(params, cfg, sched, x_T, cond, n_workers: int,
                     m_base: int, m_warmup: int) -> RunResult:
    """Patch parallelism baseline: uniform patches, uniform steps."""
    P = cfg.tokens_per_side
    base, rem = divmod(P, n_workers)
    patches = [base + (1 if i < rem else 0) for i in range(n_workers)]
    return run_schedule(params, cfg, sched, x_T, cond,
                        uniform_plan(n_workers, m_base, m_warmup), patches)


def run_origin(params, cfg, sched, x_T, cond, m_base: int) -> jnp.ndarray:
    """Non-distributed exact DDIM ("Origin" in Table II)."""
    eps_fn = lambda x, t: dit.forward(params, cfg, x, t, cond)
    return sampler_lib.ddim_sample(eps_fn, sched, x_T, m_base)


def run_origin_cfg(params, cfg, sched, x_T, cond, m_base: int,
                   scale: float) -> jnp.ndarray:
    """Non-distributed exact guided DDIM: the CFG "Origin" — fused-batch
    classifier-free guidance with no patching or staleness (DESIGN.md §12)."""
    eps_fn = lambda x, t: dit.forward_cfg(params, cfg, x, t, cond, scale)
    return sampler_lib.ddim_sample(eps_fn, sched, x_T, m_base)
