"""STADI inference driver — the paper's system (launchable).

Thin CLI over :class:`repro.core.pipeline.StadiPipeline`; strategy selection
is ``--planner`` (uniform / spatial / temporal / stadi / makespan) and
``--backend`` (emulated / spmd / simulate). ``--spmd`` is kept as an alias
for ``--backend spmd``:

  emulated (default): exact-numerics logical-worker engine + calibrated
      latency simulator (core/patch_parallel.py + core/simulate.py).
  spmd: REAL distributed execution via shard_map over the available devices
      (set STADI_HOST_DEVICES=8 for CPU host devices); see core/spmd.py.

Usage:
  STADI_HOST_DEVICES=4 PYTHONPATH=src python -m repro.launch.stadi_infer \
      --spmd --occupancies 0.0,0.5 --m-base 16 --m-warmup 4
  PYTHONPATH=src python -m repro.launch.stadi_infer --arch sdxl-dit \
      --use-pallas --m-base 20 --m-warmup 4 --verbose   # full width, TPU
"""
from repro.hostenv import force_host_devices, use_compile_cache
force_host_devices()
use_compile_cache()                 # both before jax is imported

import argparse
import dataclasses
import json
import time


def run_spmd(params, cfg, sched, x_T, cond, plan, patches):
    """Deprecated location — moved to repro.core.spmd.run_spmd."""
    from repro.core.spmd import run_spmd as _run_spmd
    return _run_spmd(params, cfg, sched, x_T, cond, plan, patches)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--occupancies", default="0.0,0.6")
    ap.add_argument("--capabilities", default=None)
    ap.add_argument("--m-base", type=int, default=16)
    ap.add_argument("--m-warmup", type=int, default=4)
    ap.add_argument("--a", type=float, default=0.75)
    ap.add_argument("--b", type=float, default=0.25)
    ap.add_argument("--arch", default="tiny-dit")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=1)
    ap.add_argument("--planner", default="stadi",
                    choices=["uniform", "spatial", "temporal", "stadi",
                             "makespan", "stadi_pipefuse", "stadi_guidance",
                             "stadi_seq", "stadi_video"])
    ap.add_argument("--backend", default="emulated",
                    choices=["emulated", "spmd", "simulate", "pipefuse",
                             "spmd_pipefuse", "spmd_guidance", "spmd_seq",
                             "spmd_frames"])
    ap.add_argument("--spmd", action="store_true",
                    help="alias for --backend spmd")
    ap.add_argument("--num-stages", type=int, default=1,
                    help="displaced patch pipeline (DESIGN.md §11): depth "
                         "stages for the pipefuse backends (1 = pure patch "
                         "parallelism, 0 = let stadi_pipefuse search)")
    ap.add_argument("--micro-patches", type=int, default=0,
                    help="micro-batches streaming through the stage chain "
                         "(0 = auto)")
    ap.add_argument("--cfg-scale", type=float, default=0.0,
                    help="classifier-free guidance weight w (DESIGN.md "
                         "§12): 0 = unguided; > 0 runs CFG "
                         "(eps_u + w*(eps_c - eps_u))")
    ap.add_argument("--guidance", default="none",
                    choices=["none", "fused", "split", "interleaved"],
                    help="CFG placement: fused-batch on every worker, "
                         "split cond/uncond device groups, or interleaved "
                         "uncond reuse; split/interleaved need "
                         "--planner stadi_guidance ('none' + --cfg-scale "
                         "lets stadi_guidance auto-search)")
    ap.add_argument("--uncond-refresh", type=int, default=2,
                    help="interleaved guidance: recompute the uncond "
                         "branch every E adaptive intervals")
    ap.add_argument("--seq-shards", type=int, default=1,
                    help="sequence-parallel attention (DESIGN.md §13): "
                         "Ulysses/ring shards per patch worker (1 = "
                         "attention-unsharded, 0 = let stadi_seq search; "
                         "spmd_seq needs seq_shards * workers host devices)")
    ap.add_argument("--num-frames", type=int, default=1,
                    help="video / multi-frame diffusion (DESIGN.md §16): "
                         "latent frames denoised jointly (1 = image; > 1 "
                         "needs a frame backend — emulated / simulate / "
                         "spmd_frames)")
    ap.add_argument("--frame-groups", type=int, default=0,
                    help="frame placement: 1 = frame-sequential, > 1 = "
                         "frame-parallel member rows (needs --planner "
                         "stadi_video; spmd_frames needs groups * workers "
                         "host devices), 0 = let stadi_video search")
    cond_group = ap.add_mutually_exclusive_group()
    cond_group.add_argument("--cond", type=int, default=None,
                            help="class id to condition on (default 0; "
                                 "mutually exclusive with --prompt / "
                                 "--cond-tokens)")
    cond_group.add_argument("--prompt", default=None,
                            help="text prompt (DESIGN.md §17): encodes "
                                 "through the frozen text encoder and runs "
                                 "the cross-attention path (the model is "
                                 "built text-conditioned)")
    cond_group.add_argument("--cond-tokens", type=int, default=None,
                            metavar="L",
                            help="run the prompt path with L random-normal "
                                 "conditioning tokens instead of an encoded "
                                 "prompt (planner/perf runs that don't care "
                                 "about the text)")
    ap.add_argument("--cond-seq-len", type=int, default=32,
                    help="text-conditioned models: the max prompt bucket "
                         "(DiTConfig.cond_seq_len)")
    ap.add_argument("--rebalance-every", type=int, default=0)
    ap.add_argument("--exchange", default="sync",
                    choices=["sync", "stale_async", "predictive", "ring"],
                    help="boundary-exchange policy (DESIGN.md §10; 'ring' "
                         "is the per-hop-staged seq-parallel variant, "
                         "DESIGN.md §13)")
    ap.add_argument("--exchange-refresh", type=int, default=2,
                    help="full refresh every E boundaries (stale/predictive)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--check-vs-emulation", action="store_true")
    ap.add_argument("--use-pallas", action="store_true",
                    help="route attention + CFG epilogue through the Pallas "
                         "kernels (DESIGN.md §15; interpret mode off-TPU)")
    ap.add_argument("--verbose", action="store_true",
                    help="print the trace-time kernel path hit/miss "
                         "counters after the run")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs import get_config
    from repro.core import sampler as sampler_lib
    from repro.core.pipeline import StadiConfig, StadiPipeline
    from repro.models.diffusion import dit

    occ = [float(x) for x in args.occupancies.split(",")]
    caps = ([float(x) for x in args.capabilities.split(",")]
            if args.capabilities else None)
    backend = "spmd" if args.spmd else args.backend

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    text_mode = args.prompt is not None or args.cond_tokens is not None
    if text_mode:
        cfg = cfg.text_conditioned(cond_seq_len=args.cond_seq_len)
    params = dit.init_params(jax.random.PRNGKey(args.seed), cfg)
    sched = sampler_lib.linear_schedule(T=1000)
    shape = (args.batch, cfg.latent_size, cfg.latent_size, cfg.channels)
    if args.num_frames > 1:          # video latent: [B, F, H, W, C]
        shape = shape[:1] + (args.num_frames,) + shape[1:]
    x_T = jax.random.normal(jax.random.PRNGKey(args.seed + 1), shape)
    if args.prompt is not None:
        from repro.models import text_encoder
        tok = text_encoder.encode([args.prompt], cfg)
        cond = jnp.broadcast_to(tok, (args.batch,) + tok.shape[1:])
        print(f"prompt bucket={tok.shape[1]} (of {cfg.cond_seq_len})")
    elif args.cond_tokens is not None:
        from repro.models import text_encoder
        L = text_encoder.bucket_length(args.cond_tokens, cfg.cond_seq_len)
        feats = jax.random.normal(jax.random.PRNGKey(args.seed + 2),
                                  (args.batch, L, cfg.cond_dim))
        mask = (jnp.arange(L) < args.cond_tokens).astype(jnp.float32)
        mask = jnp.broadcast_to(mask[None, :, None], (args.batch, L, 1))
        cond = jnp.concatenate([feats * mask, mask], axis=-1)
        print(f"cond tokens={args.cond_tokens} bucket={L}")
    else:
        cond = jnp.full((args.batch,), (args.cond or 0) % cfg.n_classes,
                        jnp.int32)

    knobs = {}
    if backend == "simulate":
        # nominal per-step cost model; calibrate for real numbers with
        # benchmarks/common.calibrate_cost_model
        from repro.core.simulate import CostModel
        knobs["cost_model"] = CostModel(t_fixed=1e-3, t_row=5e-4)
    if args.planner == "makespan":
        knobs["tiers"] = (1, 2, 4)        # generalized ratios (DESIGN.md §7)
    config = StadiConfig.from_occupancies(
        occ, caps, m_base=args.m_base, m_warmup=args.m_warmup,
        a=args.a, b=args.b, planner=args.planner, backend=backend,
        rebalance_every=args.rebalance_every, exchange=args.exchange,
        exchange_refresh=args.exchange_refresh,
        num_stages=args.num_stages, micro_patches=args.micro_patches,
        guidance=args.guidance, cfg_scale=args.cfg_scale,
        uncond_refresh=args.uncond_refresh,
        seq_shards=args.seq_shards,
        num_frames=args.num_frames, frame_groups=args.frame_groups,
        use_pallas_attention=args.use_pallas,
        **knobs)
    pipe = StadiPipeline(cfg, params, sched, config)
    plan = pipe.plan()
    print(f"speeds={config.speeds} steps={plan.temporal.steps} "
          f"ratios={plan.temporal.ratios} patches={plan.patches} "
          f"stages={plan.stages} "
          f"guidance={plan.guidance} "
          f"seq={plan.seq} "
          f"frames={plan.frames}")

    t0 = time.time()
    res = pipe.generate(x_T, cond)
    if res.image is None:                  # trace-only backend
        print(f"{backend} run: modeled latency {res.latency_s:.3f}s")
        print(json.dumps({"patches": plan.patches, "steps": plan.temporal.steps,
                          "planner": args.planner, "backend": backend,
                          "latency_s": res.latency_s}))
        return
    img = np.asarray(res.image)
    print(f"{backend} run ({len(jax.devices())} devices): "
          f"{time.time()-t0:.2f}s image {img.shape} "
          f"finite={np.all(np.isfinite(img))}")
    if args.verbose:
        # trace-time counters: which kernel bodies the compiled program
        # contains, and why any layout refused the kernel (DESIGN.md §15)
        print(f"kernel_stats={json.dumps(res.kernel_stats, sort_keys=True)}")
    if (backend in ("spmd", "spmd_guidance", "spmd_seq", "spmd_frames")
            and args.check_vs_emulation):
        emu = StadiPipeline(cfg, params, sched,
                            dataclasses.replace(config, backend="emulated"))
        ref = np.asarray(emu.generate(x_T, cond).image)
        err = float(np.linalg.norm(img - ref) / np.linalg.norm(ref))
        print(f"rel_err_vs_emulation={err:.3e}")
        assert err < 1e-3, err
    print(json.dumps({"patches": plan.patches, "steps": plan.temporal.steps,
                      "planner": args.planner, "backend": backend,
                      "finite": bool(np.all(np.isfinite(img)))}))


if __name__ == "__main__":
    main()
