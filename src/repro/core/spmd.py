"""Real SPMD execution of a STADI schedule via ``jax.shard_map``.

Moved out of ``launch/stadi_infer.py`` so it is an execution *backend*
(registered as ``"spmd"`` in :mod:`repro.core.pipeline`) rather than a launch
script. Every device owns one (padded) row-slab; uneven all-gathers use the
padded strategy of :mod:`repro.core.comm`; the mixed-rate schedule runs in
SPMD lockstep with per-device activity masks — a no-op substep costs what it
costs on the slow device, the TPU analogue of the paper's per-GPU step
skipping. Set ``STADI_HOST_DEVICES=N`` (before importing jax) for N CPU host
devices.

The shard_map body is GENERATED from the schedule IR (DESIGN.md §10): the
event stream of :func:`repro.core.events.lower` — the same one the emulated
engine interprets — unrolls statically into the traced program, so the
warmup / interval / merge structure exists in exactly one place. Boundary
exchange follows the event kinds: "full" gathers the latent and merges
fresh K/V, "skip" keeps buffers stale (the gather of disjoint slabs is
numerically transparent and modeled as free), "predict" extrapolates the
published K/V from the last two full exchanges with a static coefficient.
"""
from __future__ import annotations

from typing import Sequence

import numpy as np

from repro.configs.diffusion import DiTConfig
from repro.core import buffers as buf_lib
from repro.core import comm as comm_lib
from repro.core import events as ir
from repro.core.sampler import NoiseSchedule
from repro.core.schedule import TemporalPlan


def _run_substeps(params, cfg: DiTConfig, sched: NoiseSchedule, ts, m_base,
                  R, my_slab, cond, pub_k, pub_v, my_start, my_tok,
                  my_ratio, m0, guidance_scale=None, eps_combine=None,
                  attend_fn=None, frame=None, ctx_tokens=None):
    """R fine steps on this device's padded slab with activity masking: a
    device with interval ratio r only applies every r-th DDIM update (a
    no-op substep costs what it costs — the paper's per-GPU step skipping in
    SPMD lockstep). Publishes the FIRST substep's fresh K/V (Alg. 1).
    ``m0`` (first fine step) may be a python int (run_spmd's statically
    unrolled loop) or a traced scalar (round-granular serving).

    Guidance (DESIGN.md §12): ``guidance_scale`` turns each eval into a
    branch-vmapped fused CFG step against branch-stacked buffers (the
    "spmd" fused path); ``eps_combine`` post-processes the raw local eps —
    the "spmd_guidance" split path passes the cross-branch psum combine
    over the guidance mesh axis.

    ``attend_fn`` (DESIGN.md §13) replaces the buffered attention read in
    ``dit.block_stack`` — the "spmd_seq" path passes the Ulysses
    all-to-all + ring-ppermute read over the sequence mesh axis.

    ``frame`` / ``ctx_tokens`` (DESIGN.md §16): the "spmd_frames" path
    passes the latent frame index (summed into the conditioning) and the
    real-token count of its 2N cross-frame concatenated buffers.
    """
    import jax
    import jax.numpy as jnp

    from repro.core import sampler as sampler_lib
    from repro.models.diffusion import dit

    fresh_k = fresh_v = None
    for s in range(R):
        active = (s % my_ratio) == 0
        t_from = ts[m0 + s]
        t_to = ts[jnp.minimum(m0 + s + my_ratio, m_base)]
        if guidance_scale is not None:        # fused CFG: both branches here
            def one(c, bk, bv):
                return dit.forward_patch(
                    params, cfg, my_slab, t_from, c, my_start,
                    buffers=(bk, bv), return_kv=True, valid_tokens=my_tok)
            eps2, kvs = jax.vmap(one)(dit.guidance_conds(cond), pub_k, pub_v)
            if cfg.use_pallas_attention:   # fused combine: one HBM pass
                from repro.kernels import ops as kops
                eps = kops.cfg_epilogue(eps2[0], eps2[1], guidance_scale,
                                        with_delta=False)
            else:
                eps = sampler_lib.cfg_combine(eps2[0], eps2[1],
                                              guidance_scale)
        else:
            eps, kvs = dit.forward_patch(
                params, cfg, my_slab, t_from, cond, my_start,
                buffers=(pub_k, pub_v), return_kv=True, valid_tokens=my_tok,
                attend_fn=attend_fn, frame=frame, ctx_tokens=ctx_tokens)
        if eps_combine is not None:           # split CFG: eps crosses groups
            eps = eps_combine(eps)
        stepped = sampler_lib.ddim_step(sched, my_slab, eps, t_from, t_to)
        my_slab = jnp.where(active, stepped, my_slab)
        if s == 0:                            # Alg.1: publish first substep
            fresh_k, fresh_v = kvs
    return my_slab, fresh_k, fresh_v


def _gather_and_merge(cfg: DiTConfig, patches, row_starts, my_slab,
                      fresh_k, fresh_v, pub_k, pub_v, merge_kv: bool = True,
                      tok_axis: int = 2):
    """Interval boundary: uneven all-gathers (padded strategy) rebuild the
    full latent; with ``merge_kv`` every device's fresh K/V valid prefix is
    merged into the (scratch-padded) published buffers. ``merge_kv=False``
    is the "skip" exchange kind: slabs are disjoint so the latent gather is
    numerically transparent (and modeled as free), while the K/V buffers
    deliberately stay stale. ``tok_axis`` is the buffers' token axis — 2
    for plain [L,B,N,H,hd], 3 for branch-stacked CFG buffers (§12)."""
    import jax
    import jax.numpy as jnp

    p, wp, N = cfg.patch_size, cfg.tokens_per_side, len(patches)
    slabs = jax.lax.all_gather(my_slab, "dev")        # [N,B,Pmax*p,W,C]
    parts = [slabs[i, :, :patches[i] * p] for i in range(N) if patches[i]]
    x_full = jnp.concatenate(parts, axis=1)
    if not merge_kv:
        return x_full, pub_k, pub_v
    gk = jax.lax.all_gather(fresh_k, "dev")           # [N,(2,)L,B,Nl_max,H,hd]
    gv = jax.lax.all_gather(fresh_v, "dev")
    for i in range(N):                         # static merge, valid prefixes
        sz = patches[i] * wp
        if sz == 0:
            continue
        st = int(row_starts[i]) * wp
        sl = [i] + [slice(None)] * (gk.ndim - 1)
        sl[1 + tok_axis] = slice(0, sz)
        pub_k = jax.lax.dynamic_update_slice_in_dim(
            pub_k, gk[tuple(sl)], st, axis=tok_axis)
        pub_v = jax.lax.dynamic_update_slice_in_dim(
            pub_v, gv[tuple(sl)], st, axis=tok_axis)
    return x_full, pub_k, pub_v


def _static_layout(cfg: DiTConfig, patches: Sequence[int]):
    """Shared static slab layout for the SPMD bodies."""
    import jax.numpy as jnp

    p = cfg.patch_size
    wp = cfg.tokens_per_side
    Pmax = max(patches)
    row_starts = np.concatenate([[0], np.cumsum(patches)[:-1]]).astype(np.int32)
    return dict(p=p, wp=wp, Pmax=Pmax, Nl_max=Pmax * wp,
                row_starts=row_starts,
                rows_arr=jnp.asarray(patches, jnp.int32),
                starts_arr=jnp.asarray(row_starts, jnp.int32))


def make_interval_step(cfg: DiTConfig, sched: NoiseSchedule,
                       plan: TemporalPlan, patches: Sequence[int],
                       exchange_kind: str = "full"):
    """Round-granular SPMD: one jitted shard_map call per adaptive interval.

    Returns ``fn(params, x_full [B,H,W,C], cond [B], pub_k, pub_v
    [L,B,N,H,hd], m0) -> (x_full, pub_k, pub_v)`` executing the R = plan.lcm
    fine steps starting at (traced) fine step ``m0`` with the same per-device
    activity masks, padded-slab all-gathers, and publish-at-first-substep
    buffer semantics as :func:`run_spmd`'s inner loop. Carried state lives on
    the host between calls, so the diffusion serving engine can interleave
    many request cohorts across rounds (DESIGN.md §9); stale-KV buffers are
    scratch-padded on entry and sliced back to ``cfg.n_tokens`` on exit.

    ``exchange_kind`` selects the boundary behavior of this compiled
    variant: "full" merges fresh K/V at the end of the interval, "skip"
    leaves the published buffers untouched (stale-async; the caller decides
    per boundary which variant to invoke — predictive callers extrapolate
    the buffers host-side and invoke the "skip" variant).
    """
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, PartitionSpec as P

    from repro.core import sampler as sampler_lib

    if exchange_kind not in ("full", "skip"):
        raise ValueError(f"make_interval_step compiles 'full' or 'skip' "
                         f"variants, not {exchange_kind!r}")
    devices = jax.devices()
    N = len(patches)
    assert N <= len(devices), (N, len(devices))
    mesh = Mesh(np.asarray(devices[:N]), ("dev",))

    lay = _static_layout(cfg, patches)
    ratios = [r if r else 1 for r in plan.ratios]
    ratios_arr = jnp.asarray(ratios, jnp.int32)
    ts = sampler_lib.ddim_timesteps(sched.T, plan.m_base)
    R = plan.lcm

    def body(params, x_full, cond, pub_k, pub_v, m0):
        idx = jax.lax.axis_index("dev")
        my_rows = lay["rows_arr"][idx]
        my_start = lay["starts_arr"][idx]
        my_ratio = ratios_arr[idx]
        my_tok = my_rows * lay["wp"]
        pad = [(0, 0), (0, 0), (0, lay["Nl_max"]), (0, 0), (0, 0)]
        pub_k = jnp.pad(pub_k, pad)               # scratch-padded buffers
        pub_v = jnp.pad(pub_v, pad)
        x_pad = jnp.pad(x_full, ((0, 0), (0, lay["Pmax"] * lay["p"]),
                                 (0, 0), (0, 0)))
        my_slab = jax.lax.dynamic_slice_in_dim(x_pad, my_start * lay["p"],
                                               lay["Pmax"] * lay["p"], axis=1)
        my_slab, fresh_k, fresh_v = _run_substeps(
            params, cfg, sched, ts, plan.m_base, R, my_slab, cond,
            pub_k, pub_v, my_start, my_tok, my_ratio, m0)
        x_full, pub_k, pub_v = _gather_and_merge(
            cfg, patches, lay["row_starts"], my_slab, fresh_k, fresh_v,
            pub_k, pub_v, merge_kv=(exchange_kind == "full"))
        return x_full, pub_k[:, :, :cfg.n_tokens], pub_v[:, :, :cfg.n_tokens]

    fn = jax.shard_map(body, mesh=mesh, in_specs=(P(),) * 6,
                       out_specs=(P(), P(), P()), check_vma=False)
    return jax.jit(fn)


def run_spmd_pipefuse(params, cfg: DiTConfig, sched: NoiseSchedule, x_T,
                      cond, plan: TemporalPlan, patches: Sequence[int],
                      stages: Sequence[int], exchange: str = "sync",
                      exchange_refresh: int = 2):
    """shard_map displaced patch pipeline: devices are STAGES (DESIGN.md
    §11), not patch owners. Returns the final image [B,H,W,C].

    Mesh axis "stage" holds ``len(stages)`` devices; device ``d`` owns the
    ``stages[d]`` contiguous DiT blocks of its stage (sliced from the
    replicated parameter stack — the memory saving of real pipelining is
    not observable in host emulation) plus the displaced K/V context for
    exactly those blocks, which NEVER crosses devices. Per micro-task the
    hidden state hands off stage-to-stage through
    :func:`repro.core.comm.stage_handoff` (a point-to-point ``ppermute``,
    not a collective) and the final stage's eps is broadcast for the
    replicated DDIM update. The event stream of :func:`repro.core.events.
    lower` — including :class:`~repro.core.events.StageShift` fills —
    unrolls statically into the traced program, exactly as ``run_spmd``
    does for the patch-parallel schedule; numerics follow the same
    displaced contract as :func:`repro.core.pipefuse.run_pipefuse`
    (pipeline overlap is wall-clock, modeled by the simulator)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, PartitionSpec as P

    from repro.core import sampler as sampler_lib
    from repro.core.comm import stage_handoff
    from repro.core.schedule import patch_bounds
    from repro.models.diffusion import dit

    stages = list(stages)
    S = len(stages)
    assert sum(stages) == cfg.n_layers, (stages, cfg.n_layers)
    if S == 1:
        return run_spmd(params, cfg, sched, x_T, cond, plan, patches,
                        exchange=exchange, exchange_refresh=exchange_refresh)
    policy = comm_lib.get_exchange(exchange, exchange_refresh)
    evs = list(ir.lower(plan, patches, policy, stages=stages))

    devices = jax.devices()
    assert S <= len(devices), (S, len(devices))
    mesh = Mesh(np.asarray(devices[:S]), ("stage",))

    p = cfg.patch_size
    wp = cfg.tokens_per_side
    max_blk = max(stages)
    lo_list = np.concatenate([[0], np.cumsum(stages)[:-1]]).astype(np.int32)
    bounds_tok = patch_bounds(patches)
    ts = sampler_lib.ddim_timesteps(sched.T, plan.m_base)

    def body(params, x_full, cond):
        idx = jax.lax.axis_index("stage")
        lo_arr = jnp.asarray(lo_list)
        nblk_arr = jnp.asarray(stages, jnp.int32)
        my_lo = lo_arr[idx]
        my_nblk = nblk_arr[idx]
        enable = jnp.arange(max_blk) < my_nblk
        # my stage's contiguous block slice, padded to the max stage depth
        # (disabled tail blocks are exact identities in block_stack)
        my_blocks = jax.tree.map(
            lambda a: jax.lax.dynamic_slice_in_dim(
                jnp.pad(a, [(0, max_blk)] + [(0, 0)] * (a.ndim - 1)),
                my_lo, max_blk, axis=0),
            params["blocks"])

        my_ctx_k = my_ctx_v = None       # displaced context, my blocks only
        my_pub_k = my_pub_v = None       # last published K/V, my blocks only
        pend = {}                        # worker -> (k, v) at substep 0

        def my_layer_slice(kvs_full):
            return jax.lax.dynamic_slice_in_dim(
                jnp.pad(kvs_full, [(0, max_blk)] + [(0, 0)] * (kvs_full.ndim - 1)),
                my_lo, max_blk, axis=0)

        def micro_task(x_loc, t, row_start, ctx_k, ctx_v):
            """One slab through the whole chain: embed (replicated) ->
            masked stage compute + p2p handoff -> broadcast eps."""
            h, c = dit.embed_patch(params, cfg, x_loc, t, cond, row_start)
            rows_tok = x_loc.shape[1] // p
            tok_start = row_start * wp
            k_mine = v_mine = None
            for s in range(S):
                h_out, (k, v) = dit.block_stack(
                    my_blocks, cfg, h, c, tok_start,
                    buffers=(ctx_k, ctx_v), enable=enable)
                on = (idx == s)
                ctx_k = jnp.where(on, ctx_k.at[:, :, tok_start:tok_start
                                               + rows_tok * wp].set(
                    k.astype(ctx_k.dtype)), ctx_k)
                ctx_v = jnp.where(on, ctx_v.at[:, :, tok_start:tok_start
                                               + rows_tok * wp].set(
                    v.astype(ctx_v.dtype)), ctx_v)
                if k_mine is None:
                    k_mine = jnp.where(on, k, jnp.zeros_like(k))
                    v_mine = jnp.where(on, v, jnp.zeros_like(v))
                else:
                    k_mine = jnp.where(on, k, k_mine)
                    v_mine = jnp.where(on, v, v_mine)
                if s < S - 1:            # point-to-point: stage s -> s + 1
                    h = stage_handoff(h_out, "stage", S)
                else:
                    last = (idx == S - 1)
                    h = jax.lax.psum(jnp.where(last, h_out,
                                               jnp.zeros_like(h_out)),
                                     "stage")
            eps = dit.final_head(params, cfg, h, c, rows_tok)
            return eps, k_mine, v_mine, ctx_k, ctx_v

        for ev in evs:
            if isinstance(ev, ir.Warmup):
                # synchronous: exact full-depth forward (redundant per
                # device — the chain handoffs of a sync step are exact)
                eps, kvs = dit.forward_patch(
                    params, cfg, x_full, ts[ev.fine_step], cond, 0,
                    buffers=None, return_kv=True)
                x_full = sampler_lib.ddim_step(sched, x_full, eps,
                                               ts[ev.fine_step],
                                               ts[ev.fine_step + 1])
                my_pub_k = my_layer_slice(kvs[0])
                my_pub_v = my_layer_slice(kvs[1])

            elif isinstance(ev, ir.StageShift):
                if my_pub_k is None:      # M_w == 0: bootstrap once
                    _, kvs = dit.forward_patch(
                        params, cfg, x_full, ts[0], cond, 0,
                        buffers=None, return_kv=True)
                    my_pub_k = my_layer_slice(kvs[0])
                    my_pub_v = my_layer_slice(kvs[1])
                my_ctx_k, my_ctx_v = my_pub_k, my_pub_v

            elif isinstance(ev, ir.ComputeInterval):
                pend = {}
                for f in range(ev.length):
                    for i in ev.workers:
                        r = ev.ratios[i]
                        if f % r:
                            continue
                        a, b = bounds_tok[i]
                        x_loc = x_full[:, a * p:b * p]
                        t_from = ts[ev.fine_step + f]
                        t_to = ts[ev.fine_step + f + r]
                        eps, k_mine, v_mine, my_ctx_k, my_ctx_v = micro_task(
                            x_loc, t_from, a, my_ctx_k, my_ctx_v)
                        x_loc = sampler_lib.ddim_step(sched, x_loc, eps,
                                                      t_from, t_to)
                        x_full = jax.lax.dynamic_update_slice_in_dim(
                            x_full, x_loc, a * p, axis=1)
                        if f == 0:
                            pend[i] = (k_mine, v_mine, a * wp)

            elif isinstance(ev, ir.Exchange):
                if ev.kind == "full":    # merge substep-0 K/V, my blocks
                    for i in sorted(pend):
                        kl, vl, start = pend[i]
                        my_pub_k = jax.lax.dynamic_update_slice_in_dim(
                            my_pub_k, kl.astype(my_pub_k.dtype), start,
                            axis=2)
                        my_pub_v = jax.lax.dynamic_update_slice_in_dim(
                            my_pub_v, vl.astype(my_pub_v.dtype), start,
                            axis=2)
                # skip/predict: the pipe stays full; context persists
        return x_full

    fn = jax.shard_map(body, mesh=mesh, in_specs=(P(), P(), P()),
                       out_specs=P(), check_vma=False)
    return jax.jit(fn)(params, x_T, cond)


def run_spmd(params, cfg: DiTConfig, sched: NoiseSchedule, x_T, cond,
             plan: TemporalPlan, patches: Sequence[int],
             exchange: str = "sync", exchange_refresh: int = 2,
             guidance=None):
    """shard_map STADI across jax.devices(). Returns final image [B,H,W,C].

    The body is generated by statically unrolling the schedule IR event
    stream — one :class:`~repro.core.events.Warmup` per synchronous step,
    one ``_run_substeps`` per :class:`~repro.core.events.ComputeInterval`,
    and per :class:`~repro.core.events.Exchange` a boundary whose collective
    traffic follows the event's kind.

    ``guidance`` (DESIGN.md §12): a FUSED GuidancePlan turns every eval
    into a branch-vmapped CFG step (buffers branch-stacked per device);
    split/interleaved placement needs the guidance mesh axis — use
    :func:`run_spmd_guidance` (the "spmd_guidance" backend).
    """
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, PartitionSpec as P

    from repro.core import sampler as sampler_lib
    from repro.models.diffusion import dit

    if guidance is not None and guidance.mode != "fused":
        raise ValueError(
            f"run_spmd executes fused guidance only; {guidance.mode!r} "
            "placement needs the guidance mesh axis of run_spmd_guidance "
            "(backend 'spmd_guidance')")
    guided = guidance is not None
    scale = guidance.scale if guided else None
    tok_axis = 3 if guided else 2
    policy = comm_lib.get_exchange(exchange, exchange_refresh)
    evs = list(ir.lower(plan, patches, policy, guidance=guidance))

    devices = jax.devices()
    N = len(patches)
    assert N <= len(devices), (N, len(devices))
    mesh = Mesh(np.asarray(devices[:N]), ("dev",))

    lay = _static_layout(cfg, patches)
    ratios = [r if r else 1 for r in plan.ratios]
    ratios_arr = jnp.asarray(ratios, jnp.int32)
    ts = sampler_lib.ddim_timesteps(sched.T, plan.m_base)
    buf_pad = [(0, 0)] * tok_axis + [(0, lay["Nl_max"])] + [(0, 0), (0, 0)]

    def _reslice(x_full, my_start):
        x_pad = jnp.pad(x_full, ((0, 0), (0, lay["Pmax"] * lay["p"]),
                                 (0, 0), (0, 0)))
        return jax.lax.dynamic_slice_in_dim(x_pad, my_start * lay["p"],
                                            lay["Pmax"] * lay["p"], axis=1)

    def body(params, x_full, cond):
        idx = jax.lax.axis_index("dev")
        my_rows = lay["rows_arr"][idx]
        my_start = lay["starts_arr"][idx]
        my_ratio = ratios_arr[idx]
        my_tok = my_rows * lay["wp"]

        def _full_forward(x, t):
            """Synchronous full-image eval (guided => fused CFG)."""
            if guided:
                def one(c):
                    return dit.forward_patch(params, cfg, x, t, c, 0,
                                             buffers=None, return_kv=True)
                eps2, kvs = jax.vmap(one)(dit.guidance_conds(cond))
                if cfg.use_pallas_attention:
                    from repro.kernels import ops as kops
                    return kops.cfg_epilogue(eps2[0], eps2[1], scale,
                                             with_delta=False), kvs
                return sampler_lib.cfg_combine(eps2[0], eps2[1], scale), kvs
            return dit.forward_patch(params, cfg, x, t, cond, 0,
                                     buffers=None, return_kv=True)

        pub_k = pub_v = None          # last fully-exchanged K/V (padded)
        prev_k = prev_v = None        # the exchange before that (predictive)
        read_k = read_v = None        # what the substeps attend to
        my_slab = fresh_k = fresh_v = None
        m_prev, m_last = None, None   # static fine steps of those exchanges

        for ev in evs:
            if isinstance(ev, ir.Warmup):
                # synchronous == full-image forward on every device
                eps, kvs = _full_forward(x_full, ts[ev.fine_step])
                x_full = sampler_lib.ddim_step(sched, x_full, eps,
                                               ts[ev.fine_step],
                                               ts[ev.fine_step + 1])
                pub_k, pub_v = kvs
                m_last = ev.fine_step

            elif isinstance(ev, ir.ComputeInterval):
                if my_slab is None:   # entering the adaptive phase
                    if pub_k is None:             # M_w == 0: bootstrap once
                        _, kvs = _full_forward(x_full, ts[0])
                        pub_k, pub_v = kvs
                        m_last = -1
                    pub_k = jnp.pad(pub_k, buf_pad)   # scratch-padded
                    pub_v = jnp.pad(pub_v, buf_pad)
                    read_k, read_v = pub_k, pub_v
                    my_slab = _reslice(x_full, my_start)
                my_slab, fresh_k, fresh_v = _run_substeps(
                    params, cfg, sched, ts, plan.m_base, ev.length, my_slab,
                    cond, read_k, read_v, my_start, my_tok, my_ratio,
                    ev.fine_step, guidance_scale=scale)

            elif isinstance(ev, ir.Exchange):
                if ev.kind == "full":
                    prev_k, prev_v = pub_k, pub_v
                    m_prev, m_last = m_last, ev.fine_step
                    x_full, pub_k, pub_v = _gather_and_merge(
                        cfg, patches, lay["row_starts"], my_slab,
                        fresh_k, fresh_v, pub_k, pub_v, tok_axis=tok_axis)
                    read_k, read_v = pub_k, pub_v
                    my_slab = _reslice(x_full, my_start)
                elif ev.kind == "skip":
                    read_k, read_v = pub_k, pub_v     # stay stale
                elif ev.kind == "predict":
                    f = (buf_lib.extrapolation_factor(m_prev, m_last,
                                                      ev.fine_step)
                         if m_prev is not None else 0.0)
                    if f:
                        read_k = buf_lib.extrapolate_arrays(pub_k, prev_k, f)
                        read_v = buf_lib.extrapolate_arrays(pub_v, prev_v, f)
                    else:             # fewer than two exchanges: stale reuse
                        read_k, read_v = pub_k, pub_v
        return x_full

    fn = jax.shard_map(body, mesh=mesh, in_specs=(P(), P(), P()),
                       out_specs=P(), check_vma=False)
    return jax.jit(fn)(params, x_T, cond)


def run_spmd_seq(params, cfg: DiTConfig, sched: NoiseSchedule, x_T, cond,
                 plan: TemporalPlan, patches: Sequence[int], seq,
                 exchange: str = "ring", exchange_refresh: int = 2):
    """Sequence-parallel SPMD (DESIGN.md §13): shard_map over a
    ``("seq", "dev")`` mesh — axis "dev" holds the ``len(patches)`` patch
    workers, axis "seq" the ``seq.n_shards`` sequence members of each
    worker group.

    Each seq slice runs the IDENTICAL statically-unrolled schedule body as
    :func:`run_spmd` — including the IR's :class:`~repro.core.events.
    SeqShard` events, which carry no numerics — but every buffered
    attention read routes through the sequence axis:

      1. RING: each member holds ONE token segment of the
         freshness-blended whole-image K/V; segments rotate via
         ``n_shards - 1`` ``ppermute`` hops while per-hop flash-style
         partials (normalized output + log-sum-exp) stream through an
         online softmax merge — the full context is never materialized
         on any member (O(segment) K/V memory, DESIGN.md §15). Segments
         carry exactly the fresh-local ⊕ policy-stale-remote values the
         dense read uses.
      2. ULYSSES: one ``all_to_all`` scatters query head groups over
         "seq", each member attends its ``n_heads / n_shards`` heads over
         the rotating segments, and the reverse ``all_to_all`` regathers
         heads.

    Head groups are independent under softmax and the log-sum-exp merge
    is exact, so the sharded read equals the dense ``layers.attend`` up
    to reduction order (tested <= 1e-5 vs the emulated reference). Requires ``n_heads % n_shards == 0`` (the
    all-to-all's even head split; speed-proportional uneven heads are the
    cost model's planning view) and ``n_shards * len(patches)`` devices.
    As with the other SPMD backends, the wall-clock benefit of the ring
    overlap is modeled by the simulator; this backend proves the
    collective mechanics and the numerics. Returns the final image.
    """
    import math

    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, PartitionSpec as P

    from repro.core import sampler as sampler_lib
    from repro.kernels import ops as kops
    from repro.models.diffusion import dit

    if seq is None or len(seq.segments) < 2:
        return run_spmd(params, cfg, sched, x_T, cond, plan, patches,
                        exchange=exchange, exchange_refresh=exchange_refresh)
    S = len(seq.segments)
    if cfg.n_heads % S:
        raise ValueError(
            f"spmd_seq needs n_heads divisible by seq_shards for the "
            f"all-to-all head scatter: {cfg.n_heads} % {S} != 0")
    policy = comm_lib.get_exchange(exchange, exchange_refresh)
    evs = list(ir.lower(plan, patches, policy, seq_shards=seq))

    devices = jax.devices()
    N = len(patches)
    if S * N > len(devices):
        raise ValueError(
            f"seq_shards={S} over {N} patch workers needs {S * N} devices, "
            f"have {len(devices)} (set STADI_HOST_DEVICES)")
    mesh = Mesh(np.asarray(devices[:S * N]).reshape(S, N), ("seq", "dev"))

    lay = _static_layout(cfg, patches)
    ratios = [r if r else 1 for r in plan.ratios]
    ratios_arr = jnp.asarray(ratios, jnp.int32)
    ts = sampler_lib.ddim_timesteps(sched.T, plan.m_base)
    buf_pad = [(0, 0), (0, 0), (0, lay["Nl_max"]), (0, 0), (0, 0)]
    Hs = cfg.n_heads // S
    ring_perm = [(s, (s + 1) % S) for s in range(S)]

    def _segment_partial(q_g, k_h, v_h, valid_here):
        """Normalized attention of q_g over ONE ring segment plus its
        log-sum-exp: the flash-style partial the cross-hop merge combines.
        Routed through the Pallas LSE kernel when the config asks for it."""
        if cfg.use_pallas_attention:
            kops.record_kernel_hit("ring.lse")
            return kops.lse_attention(q_g, k_h, v_h, valid_here)
        hd = q_g.shape[-1]
        s = (jnp.einsum("bshd,bthd->bhst", q_g, k_h).astype(jnp.float32)
             / math.sqrt(hd))
        seg_mask = jnp.arange(k_h.shape[1]) < valid_here
        s = jnp.where(seg_mask[None, None, None, :], s, -1e30)
        m = jnp.max(s, axis=-1)
        p = jnp.exp(s - m[..., None])
        l = jnp.sum(p, axis=-1)
        out = jnp.einsum("bhst,bthd->bshd", p / jnp.maximum(l, 1e-30)[..., None],
                         v_h.astype(jnp.float32)).astype(q_g.dtype)
        lse = m + jnp.log(jnp.maximum(l, 1e-30))
        return out, jnp.moveaxis(lse, 1, 2)          # [B,S,H]

    def attend_fn(q, full_k, full_v, key_mask):
        """Flash-style ring read: instead of reassembling the whole-image
        K/V on every member (O(n_tokens) memory) and attending once, each
        member holds ONE token segment, attends its Ulysses head group over
        it, and streams the per-hop (out, lse) partials through an online
        log-sum-exp merge while segments rotate via ``ppermute`` —
        O(segment) K/V memory, S-1 hops, numerically the dense softmax up
        to reduction order. A fully scratch segment contributes lse ~= -inf
        and therefore exactly zero merge weight."""
        j = jax.lax.axis_index("seq")
        n_real = cfg.n_tokens if key_mask is not None else full_k.shape[1]
        # Ulysses: scatter query head groups over "seq" (head group j of
        # every member lands on member j, token blocks concatenated)
        q_g = jax.lax.all_to_all(q, "seq", split_axis=2, concat_axis=1,
                                 tiled=True)
        cpad = -full_k.shape[1] % S
        pad4 = ((0, 0), (0, cpad), (0, 0), (0, 0))
        cseg = (full_k.shape[1] + cpad) // S
        hold_k = jax.lax.dynamic_slice_in_dim(jnp.pad(full_k, pad4),
                                              j * cseg, cseg, axis=1)
        hold_v = jax.lax.dynamic_slice_in_dim(jnp.pad(full_v, pad4),
                                              j * cseg, cseg, axis=1)
        num = den = run_m = None
        for h in range(S):
            src = (j - h) % S                 # segment id this hop holds
            valid_here = jnp.clip(n_real - src * cseg, 0, cseg)
            k_h = jax.lax.dynamic_slice_in_dim(hold_k, j * Hs, Hs, axis=2)
            v_h = jax.lax.dynamic_slice_in_dim(hold_v, j * Hs, Hs, axis=2)
            out_s, lse_s = _segment_partial(q_g, k_h, v_h, valid_here)
            out_s = out_s.astype(jnp.float32)
            if num is None:
                num, den, run_m = out_s, jnp.ones_like(lse_s), lse_s
            else:
                m_new = jnp.maximum(run_m, lse_s)
                corr = jnp.exp(run_m - m_new)
                w = jnp.exp(lse_s - m_new)
                num = num * corr[..., None] + out_s * w[..., None]
                den = den * corr + w
                run_m = m_new
            if h < S - 1:
                hold_k = jax.lax.ppermute(hold_k, "seq", ring_perm)
                hold_v = jax.lax.ppermute(hold_v, "seq", ring_perm)
        att_g = (num / jnp.maximum(den, 1e-30)[..., None]).astype(q.dtype)
        # regather: head group j returns from member j
        return jax.lax.all_to_all(att_g, "seq", split_axis=1, concat_axis=2,
                                  tiled=True)

    def _reslice(x_full, my_start):
        x_pad = jnp.pad(x_full, ((0, 0), (0, lay["Pmax"] * lay["p"]),
                                 (0, 0), (0, 0)))
        return jax.lax.dynamic_slice_in_dim(x_pad, my_start * lay["p"],
                                            lay["Pmax"] * lay["p"], axis=1)

    def body(params, x_full, cond):
        idx = jax.lax.axis_index("dev")
        my_rows = lay["rows_arr"][idx]
        my_start = lay["starts_arr"][idx]
        my_ratio = ratios_arr[idx]
        my_tok = my_rows * lay["wp"]

        pub_k = pub_v = None
        prev_k = prev_v = None
        read_k = read_v = None
        my_slab = fresh_k = fresh_v = None
        m_prev, m_last = None, None

        for ev in evs:
            if isinstance(ev, ir.Warmup):
                # synchronous == full-image forward on every device (the
                # local-only attention of an unbuffered full forward is
                # exact; no ring needed)
                eps, kvs = dit.forward_patch(
                    params, cfg, x_full, ts[ev.fine_step], cond, 0,
                    buffers=None, return_kv=True)
                x_full = sampler_lib.ddim_step(sched, x_full, eps,
                                               ts[ev.fine_step],
                                               ts[ev.fine_step + 1])
                pub_k, pub_v = kvs
                m_last = ev.fine_step

            elif isinstance(ev, ir.SeqShard):
                pass                     # repartitioning carries no numerics

            elif isinstance(ev, ir.ComputeInterval):
                if my_slab is None:
                    if pub_k is None:             # M_w == 0: bootstrap once
                        _, kvs = dit.forward_patch(
                            params, cfg, x_full, ts[0], cond, 0,
                            buffers=None, return_kv=True)
                        pub_k, pub_v = kvs
                        m_last = -1
                    pub_k = jnp.pad(pub_k, buf_pad)
                    pub_v = jnp.pad(pub_v, buf_pad)
                    read_k, read_v = pub_k, pub_v
                    my_slab = _reslice(x_full, my_start)
                my_slab, fresh_k, fresh_v = _run_substeps(
                    params, cfg, sched, ts, plan.m_base, ev.length, my_slab,
                    cond, read_k, read_v, my_start, my_tok, my_ratio,
                    ev.fine_step, attend_fn=attend_fn)

            elif isinstance(ev, ir.Exchange):
                if ev.kind == "full":
                    prev_k, prev_v = pub_k, pub_v
                    m_prev, m_last = m_last, ev.fine_step
                    # per-seq-slice gather/merge: "dev"-axis collectives
                    # run inside each seq row; published K/V stays
                    # replicated over "seq" (every member computes the
                    # identical merge)
                    x_full, pub_k, pub_v = _gather_and_merge(
                        cfg, patches, lay["row_starts"], my_slab,
                        fresh_k, fresh_v, pub_k, pub_v)
                    read_k, read_v = pub_k, pub_v
                    my_slab = _reslice(x_full, my_start)
                elif ev.kind == "skip":
                    read_k, read_v = pub_k, pub_v
                elif ev.kind == "predict":
                    f = (buf_lib.extrapolation_factor(m_prev, m_last,
                                                      ev.fine_step)
                         if m_prev is not None else 0.0)
                    if f:
                        read_k = buf_lib.extrapolate_arrays(pub_k, prev_k, f)
                        read_v = buf_lib.extrapolate_arrays(pub_v, prev_v, f)
                    else:
                        read_k, read_v = pub_k, pub_v
        return x_full

    fn = jax.shard_map(body, mesh=mesh, in_specs=(P(), P(), P()),
                       out_specs=P(), check_vma=False)
    return jax.jit(fn)(params, x_T, cond)


def run_spmd_frames(params, cfg: DiTConfig, sched: NoiseSchedule, x_T,
                    cond, plan: TemporalPlan, patches: Sequence[int],
                    frames, exchange: str = "sync",
                    exchange_refresh: int = 2):
    """Multi-frame SPMD (DESIGN.md §16): shard_map over a
    ``("frame", "dev")`` mesh — axis "dev" holds the ``len(patches)``
    patch-worker COLUMNS every member row shares, axis "frame" the
    ``frames.n_groups`` member rows, row ``g`` owning the contiguous
    frame chunk ``frames.bounds[g]``.

    Each column runs the IDENTICAL statically-unrolled schedule body as
    :func:`run_spmd` — including the IR's :class:`~repro.core.events.
    FrameShard` events, which carry no numerics — once per frame, under
    the snapshot semantics of :func:`repro.core.frames.run_frames`:
    every substep of frame f > 0 attends over the 2N-token
    (own ⊕ previous frame) published context of the LAST boundary, with
    the fresh own-slab overwrite landing in the first N tokens
    (``ctx_tokens`` keeps the scratch mask honest about the doubled
    context). Ownership is enforced, not just asserted: a frame's
    carried state is zero-masked off its member row, so the one
    previous-frame K/V that crosses each row boundary (the chunks are
    contiguous) must arrive through a masked ``psum`` over "frame" —
    miswired mesh axes fail the parity test instead of silently
    replicating. SPMD lockstep means every row traces every frame's
    step (a non-owned step costs what it costs, like the no-op substeps
    of the activity masks); the wall-clock benefit of frame parallelism
    is modeled by the simulator — this backend proves the mesh
    mechanics and the numerics. Needs ``n_groups * len(patches)``
    devices. Returns the final video [B,F,H,W,C].

    ``frames=None`` or a single-frame plan delegates to
    :func:`run_spmd` (a leading frame axis of 1 is squeezed in and
    restored on the way out) — bitwise the image path.
    """
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, PartitionSpec as P

    from repro.core import sampler as sampler_lib
    from repro.models.diffusion import dit

    if frames is None or frames.num_frames == 1:
        img = x_T[:, 0] if x_T.ndim == 5 else x_T
        out = run_spmd(params, cfg, sched, img, cond, plan, patches,
                       exchange=exchange, exchange_refresh=exchange_refresh)
        return out[:, None] if x_T.ndim == 5 else out

    from repro.core import frames as frames_lib
    frames_lib.validate_frames(frames, x_T)
    F = frames.num_frames
    G = frames.n_groups
    row_of: list = []
    for g, (lo, hi) in enumerate(frames.bounds):
        row_of += [g] * (hi - lo)
    policy = comm_lib.get_exchange(exchange, exchange_refresh)
    evs = list(ir.lower(plan, patches, policy, frames=frames))

    devices = jax.devices()
    W = len(patches)
    if G * W > len(devices):
        raise ValueError(
            f"frame_groups={G} over {W} patch workers needs {G * W} "
            f"devices, have {len(devices)} (set STADI_HOST_DEVICES)")
    mesh = Mesh(np.asarray(devices[:G * W]).reshape(G, W), ("frame", "dev"))

    lay = _static_layout(cfg, patches)
    ratios = [r if r else 1 for r in plan.ratios]
    ratios_arr = jnp.asarray(ratios, jnp.int32)
    ts = sampler_lib.ddim_timesteps(sched.T, plan.m_base)
    N = cfg.n_tokens
    buf_pad = [(0, 0), (0, 0), (0, lay["Nl_max"]), (0, 0), (0, 0)]

    def _reslice(x_full, my_start):
        x_pad = jnp.pad(x_full, ((0, 0), (0, lay["Pmax"] * lay["p"]),
                                 (0, 0), (0, 0)))
        return jax.lax.dynamic_slice_in_dim(x_pad, my_start * lay["p"],
                                            lay["Pmax"] * lay["p"], axis=1)

    def body(params, x_stack, cond):
        fidx = jax.lax.axis_index("frame")
        idx = jax.lax.axis_index("dev")
        my_start = lay["starts_arr"][idx]
        my_ratio = ratios_arr[idx]
        my_tok = lay["rows_arr"][idx] * lay["wp"]
        fids = [jnp.float32(f) for f in range(F)]

        def mask_own(f, val):
            """Frame f's state is valid ONLY on its member row; other rows
            carry zeros, so cross-row reads MUST use ``from_row``."""
            return jnp.where(fidx == row_of[f], val, jnp.zeros_like(val))

        def from_row(g, val):
            """Broadcast row g's value over "frame": a psum of the masked
            lanes — only row g contributes."""
            return jax.lax.psum(
                jnp.where(fidx == g, val, jnp.zeros_like(val)), "frame")

        def prev_kv(state, f):
            """Frame f-1's (k, v) as seen by frame f's owner row — crosses
            the mesh row boundary when f-1 lives on the previous row
            (exactly one handoff per boundary: chunks are contiguous)."""
            k, v = state[f - 1]
            if row_of[f] != row_of[f - 1]:
                k = from_row(row_of[f - 1], k)
                v = from_row(row_of[f - 1], v)
            return k, v

        def _full_forward(f, x, t):
            return dit.forward_patch(
                params, cfg, x, t, cond, 0, buffers=None, return_kv=True,
                frame=(None if f == 0 else fids[f]))

        xs = [mask_own(f, x_stack[:, f]) for f in range(F)]
        pubs = [None] * F         # last fully-exchanged K/V per frame
        prevs = [None] * F        # the exchange before that (predictive)
        reads = [None] * F        # what the substeps attend to
        slabs = [None] * F
        freshs = [None] * F
        m_prev, m_last = None, None

        for ev in evs:
            if isinstance(ev, ir.Warmup):
                # one synchronous fine step of EVERY frame under snapshot
                # semantics: all frames read the previous step's published
                # K/V, then every frame's fresh K/V publishes at once
                kv_new = []
                for f in range(F):
                    if f == 0 or pubs[f] is None:
                        eps, kvs = _full_forward(f, xs[f], ts[ev.fine_step])
                    else:
                        qk, qv = prev_kv(pubs, f)
                        eps, kvs = dit.forward_patch(
                            params, cfg, xs[f], ts[ev.fine_step], cond, 0,
                            buffers=(jnp.concatenate([pubs[f][0], qk], axis=2),
                                     jnp.concatenate([pubs[f][1], qv], axis=2)),
                            return_kv=True, frame=fids[f])
                    xs[f] = mask_own(f, sampler_lib.ddim_step(
                        sched, xs[f], eps, ts[ev.fine_step],
                        ts[ev.fine_step + 1]))
                    kv_new.append(kvs)
                for f in range(F):
                    pubs[f] = (mask_own(f, kv_new[f][0]),
                               mask_own(f, kv_new[f][1]))
                m_last = ev.fine_step

            elif isinstance(ev, ir.FrameShard):
                pass                 # placement only; numerics are invariant

            elif isinstance(ev, ir.ComputeInterval):
                if slabs[0] is None:  # entering the adaptive phase
                    if pubs[0] is None:          # M_w == 0: bootstrap once
                        for f in range(F):
                            _, kvs = _full_forward(f, xs[f], ts[0])
                            pubs[f] = (mask_own(f, kvs[0]),
                                       mask_own(f, kvs[1]))
                        m_last = -1
                    for f in range(F):
                        pubs[f] = (jnp.pad(pubs[f][0], buf_pad),
                                   jnp.pad(pubs[f][1], buf_pad))
                        reads[f] = pubs[f]
                        slabs[f] = _reslice(xs[f], my_start)
                for f in range(F):
                    if f == 0:       # the image path, bitwise run_spmd
                        slabs[0], fk, fv = _run_substeps(
                            params, cfg, sched, ts, plan.m_base, ev.length,
                            slabs[0], cond, reads[0][0], reads[0][1],
                            my_start, my_tok, my_ratio, ev.fine_step)
                    else:
                        qk, qv = prev_kv(reads, f)
                        bk = jnp.pad(jnp.concatenate(
                            [reads[f][0][:, :, :N], qk[:, :, :N]], axis=2),
                            buf_pad)
                        bv = jnp.pad(jnp.concatenate(
                            [reads[f][1][:, :, :N], qv[:, :, :N]], axis=2),
                            buf_pad)
                        slabs[f], fk, fv = _run_substeps(
                            params, cfg, sched, ts, plan.m_base, ev.length,
                            slabs[f], cond, bk, bv, my_start, my_tok,
                            my_ratio, ev.fine_step, frame=fids[f],
                            ctx_tokens=2 * N)
                    slabs[f] = mask_own(f, slabs[f])
                    freshs[f] = (fk, fv)

            elif isinstance(ev, ir.Exchange):
                for f in range(F):
                    if ev.kind == "full":
                        prevs[f] = pubs[f]
                        x_full, pk, pv = _gather_and_merge(
                            cfg, patches, lay["row_starts"], slabs[f],
                            freshs[f][0], freshs[f][1],
                            pubs[f][0], pubs[f][1])
                        pubs[f] = (mask_own(f, pk), mask_own(f, pv))
                        reads[f] = pubs[f]
                        xs[f] = mask_own(f, x_full)
                        slabs[f] = mask_own(f, _reslice(x_full, my_start))
                    elif ev.kind == "skip":
                        reads[f] = pubs[f]      # stay stale
                    elif ev.kind == "predict":
                        fac = (buf_lib.extrapolation_factor(
                            m_prev, m_last, ev.fine_step)
                            if m_prev is not None else 0.0)
                        if fac:
                            reads[f] = (
                                buf_lib.extrapolate_arrays(
                                    pubs[f][0], prevs[f][0], fac),
                                buf_lib.extrapolate_arrays(
                                    pubs[f][1], prevs[f][1], fac))
                        else:       # fewer than two exchanges: stale reuse
                            reads[f] = pubs[f]
                if ev.kind == "full":
                    m_prev, m_last = m_last, ev.fine_step
        # every frame's final latent returns from its member row
        return jnp.stack([from_row(row_of[f], xs[f]) for f in range(F)],
                         axis=1)

    fn = jax.shard_map(body, mesh=mesh, in_specs=(P(), P(), P()),
                       out_specs=P(), check_vma=False)
    return jax.jit(fn)(params, x_T, cond)


def run_spmd_guidance(params, cfg: DiTConfig, sched: NoiseSchedule, x_T,
                      cond, plan: TemporalPlan, patches: Sequence[int],
                      guidance, exchange: str = "sync",
                      exchange_refresh: int = 2):
    """Split-guidance SPMD (DESIGN.md §12): shard_map over a
    ``("guide", "dev")`` mesh — axis "guide" (size 2) holds the cond/uncond
    branch groups, axis "dev" the ``n_pairs`` patch workers of each group.

    Each guide slice runs the IDENTICAL statically-unrolled schedule body
    as :func:`run_spmd` for its branch (cond ids on slice 0, the reserved
    NULL_COND on slice 1), with per-branch published K/V that never crosses
    the guide axis. The only cross-branch traffic is the per-substep
    epsilon combine, a single ``psum`` of ``coeff * eps`` over "guide" with
    ``coeff = (w, 1 - w)`` — algebraically ``eps_u + w*(eps_c - eps_u)``.
    Needs ``2 * n_pairs`` devices. Returns the final image [B,H,W,C].
    """
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, PartitionSpec as P

    from repro.core import sampler as sampler_lib
    from repro.models.diffusion import dit

    if guidance is None or guidance.mode not in ("split", "interleaved"):
        raise ValueError("run_spmd_guidance needs a split/interleaved "
                         f"GuidancePlan, got {guidance!r}")
    if guidance.mode == "interleaved":
        raise ValueError("interleaved uncond reuse is not implemented on "
                         "the SPMD backend; use 'emulated'/'pipefuse' for "
                         "interleaved numerics")
    scale = guidance.scale
    policy = comm_lib.get_exchange(exchange, exchange_refresh)
    evs = list(ir.lower(plan, patches, policy, guidance=guidance))

    devices = jax.devices()
    N = len(patches)                     # logical workers = device pairs
    if 2 * N > len(devices):
        raise ValueError(
            f"split guidance over {N} pairs needs {2 * N} devices, have "
            f"{len(devices)} (set STADI_HOST_DEVICES)")
    mesh = Mesh(np.asarray(devices[:2 * N]).reshape(2, N), ("guide", "dev"))

    lay = _static_layout(cfg, patches)
    ratios = [r if r else 1 for r in plan.ratios]
    ratios_arr = jnp.asarray(ratios, jnp.int32)
    ts = sampler_lib.ddim_timesteps(sched.T, plan.m_base)
    buf_pad = [(0, 0), (0, 0), (0, lay["Nl_max"]), (0, 0), (0, 0)]

    def _reslice(x_full, my_start):
        x_pad = jnp.pad(x_full, ((0, 0), (0, lay["Pmax"] * lay["p"]),
                                 (0, 0), (0, 0)))
        return jax.lax.dynamic_slice_in_dim(x_pad, my_start * lay["p"],
                                            lay["Pmax"] * lay["p"], axis=1)

    def body(params, x_full, cond):
        guide = jax.lax.axis_index("guide")
        idx = jax.lax.axis_index("dev")
        my_rows = lay["rows_arr"][idx]
        my_start = lay["starts_arr"][idx]
        my_ratio = ratios_arr[idx]
        my_tok = my_rows * lay["wp"]
        # my branch: slice 0 evaluates the conditioning (class ids or
        # prompt tokens), slice 1 the null (NULL_COND / zero tokens, §17)
        my_cond = jnp.where(guide == 0, cond, dit.null_like(cond))
        coeff = jnp.where(guide == 0, scale, 1.0 - scale)

        def eps_combine(eps):
            return jax.lax.psum(coeff * eps.astype(jnp.float32),
                                "guide").astype(eps.dtype)

        pub_k = pub_v = None
        prev_k = prev_v = None
        read_k = read_v = None
        my_slab = fresh_k = fresh_v = None
        m_prev, m_last = None, None

        for ev in evs:
            if isinstance(ev, ir.Warmup):
                eps, kvs = dit.forward_patch(
                    params, cfg, x_full, ts[ev.fine_step], my_cond, 0,
                    buffers=None, return_kv=True)
                eps = eps_combine(eps)
                x_full = sampler_lib.ddim_step(sched, x_full, eps,
                                               ts[ev.fine_step],
                                               ts[ev.fine_step + 1])
                pub_k, pub_v = kvs
                m_last = ev.fine_step

            elif isinstance(ev, ir.ComputeInterval):
                if my_slab is None:
                    if pub_k is None:             # M_w == 0: bootstrap once
                        _, kvs = dit.forward_patch(
                            params, cfg, x_full, ts[0], my_cond, 0,
                            buffers=None, return_kv=True)
                        pub_k, pub_v = kvs
                        m_last = -1
                    pub_k = jnp.pad(pub_k, buf_pad)
                    pub_v = jnp.pad(pub_v, buf_pad)
                    read_k, read_v = pub_k, pub_v
                    my_slab = _reslice(x_full, my_start)
                my_slab, fresh_k, fresh_v = _run_substeps(
                    params, cfg, sched, ts, plan.m_base, ev.length, my_slab,
                    my_cond, read_k, read_v, my_start, my_tok, my_ratio,
                    ev.fine_step, eps_combine=eps_combine)

            elif isinstance(ev, ir.Exchange):
                if ev.kind == "full":
                    prev_k, prev_v = pub_k, pub_v
                    m_prev, m_last = m_last, ev.fine_step
                    # per-branch gather/merge: "dev"-axis collectives run
                    # inside each guide slice; K/V never crosses "guide"
                    x_full, pub_k, pub_v = _gather_and_merge(
                        cfg, patches, lay["row_starts"], my_slab,
                        fresh_k, fresh_v, pub_k, pub_v)
                    read_k, read_v = pub_k, pub_v
                    my_slab = _reslice(x_full, my_start)
                elif ev.kind == "skip":
                    read_k, read_v = pub_k, pub_v
                elif ev.kind == "predict":
                    f = (buf_lib.extrapolation_factor(m_prev, m_last,
                                                      ev.fine_step)
                         if m_prev is not None else 0.0)
                    if f:
                        read_k = buf_lib.extrapolate_arrays(pub_k, prev_k, f)
                        read_v = buf_lib.extrapolate_arrays(pub_v, prev_v, f)
                    else:
                        read_k, read_v = pub_k, pub_v
        return x_full

    fn = jax.shard_map(body, mesh=mesh, in_specs=(P(), P(), P()),
                       out_specs=P(), check_vma=False)
    return jax.jit(fn)(params, x_T, cond)
