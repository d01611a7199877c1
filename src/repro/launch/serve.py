"""Serving driver: batched LLM requests through the ServingEngine, or a
diffusion request queue through the continuous-batching
:class:`~repro.serving.diffusion_engine.DiffusionServingEngine`.

  PYTHONPATH=src python -m repro.launch.serve --arch gemma-2b --requests 8
  PYTHONPATH=src python -m repro.launch.serve --diffusion --arch tiny-dit \
      --occupancies 0.0,0.6 --requests 8 --slots 4 --slo-ms 200
  PYTHONPATH=src python -m repro.launch.serve --diffusion --arch sdxl-dit \
      --use-pallas --m-base 20 --m-warmup 4 --slots 2 --requests 3
  STADI_HOST_DEVICES=2 PYTHONPATH=src python -m repro.launch.serve \
      --diffusion --backend spmd --requests 4
"""
from __future__ import annotations

from repro.hostenv import force_host_devices, use_compile_cache
force_host_devices()                        # --backend spmd on CPU hosts
use_compile_cache()

import argparse
import time

import jax
import numpy as np

from repro.configs import get_config
from repro.models import build_model
from repro.serving import Request, ServingEngine


def serve(arch: str, *, n_requests: int = 8, slots: int = 4,
          prompt_len: int = 16, max_new: int = 12, reduced: bool = True,
          window: int = 0, seed: int = 0):
    cfg = get_config(arch)
    if reduced:
        cfg = cfg.reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(seed))
    engine = ServingEngine(model, params, slots=slots,
                           max_len=prompt_len + max_new + 8,
                           window=window or cfg.sliding_window)
    rng = np.random.default_rng(seed)
    t0 = time.time()
    for uid in range(n_requests):
        prompt = rng.integers(0, cfg.vocab, prompt_len).astype(np.int32)
        engine.submit(Request(uid=uid, prompt=prompt, max_new_tokens=max_new))
    done = engine.run_to_completion()
    dt = time.time() - t0
    tok = sum(len(r.out_tokens) for r in done)
    print(f"served {len(done)}/{n_requests} requests, {tok} tokens in "
          f"{dt:.2f}s ({tok/dt:.1f} tok/s)")
    return done


def serve_diffusion(arch: str = "tiny-dit", *, occupancies=(0.0, 0.6),
                    n_requests: int = 4, slots: int = 4, m_base: int = 16,
                    m_warmup: int = 4, planner: str = "stadi",
                    backend: str = "emulated", reduced: bool = False,
                    slo_s: float = None, seed: int = 0,
                    exchange: str = "sync", exchange_refresh: int = 2,
                    num_stages: int = 1, cfg_scale: float = 0.0,
                    seq_shards: int = 1, num_frames: int = 1,
                    frame_groups: int = 0, plan_cache_dir: str = None,
                    prompt: str = None, cond_tokens: int = None,
                    cond_seq_len: int = 32,
                    use_pallas_attention: bool = False, params=None):
    """Continuous batching on a heterogeneous cluster: requests enter a FIFO
    queue, the :class:`DiffusionServingEngine` admits them into ``slots``
    concurrent lanes and drains the queue with batched denoise rounds.
    ``cfg_scale > 0`` makes every other request a classifier-free-guidance
    one (DESIGN.md §12) — the mixed CFG / non-CFG workload the engine's
    per-lane guidance state exists for. ``params`` are the denoiser weights
    for the (text-conditioned, if a prompt is given) model config; None
    draws ``dit.init_params`` from ``seed``. Returns the drained engine
    (``engine.completed`` holds the requests, ``engine.pipeline`` the
    pipeline that served them)."""
    from repro.core import sampler as sampler_lib
    from repro.core.pipeline import StadiConfig, StadiPipeline
    from repro.models.diffusion import dit
    from repro.serving import DiffusionServingEngine

    cfg = get_config(arch)
    if reduced:
        cfg = cfg.reduced()
    text_mode = prompt is not None or cond_tokens is not None
    if text_mode:                          # prompt lanes (DESIGN.md §17)
        cfg = cfg.text_conditioned(cond_seq_len=cond_seq_len)
    if params is None:
        params = dit.init_params(jax.random.PRNGKey(seed), cfg)
    sched = sampler_lib.linear_schedule(T=1000)
    config = StadiConfig.from_occupancies(list(occupancies), m_base=m_base,
                                          m_warmup=m_warmup, planner=planner,
                                          backend=backend, exchange=exchange,
                                          exchange_refresh=exchange_refresh,
                                          num_stages=num_stages,
                                          seq_shards=seq_shards,
                                          num_frames=num_frames,
                                          frame_groups=frame_groups,
                                          plan_cache_dir=plan_cache_dir,
                                          use_pallas_attention=(
                                              use_pallas_attention))
    pipe = StadiPipeline(cfg, params, sched, config)
    engine = DiffusionServingEngine(pipe, slots=slots)
    rng = np.random.default_rng(seed)
    t0 = time.time()
    n_guided = 0
    shape = (1, cfg.latent_size, cfg.latent_size, cfg.channels)
    if num_frames > 1:                     # video lanes: one clip per request
        shape = shape[:1] + (num_frames,) + shape[1:]
    for uid in range(n_requests):
        x_T = jax.random.normal(jax.random.PRNGKey(seed + 1 + uid), shape)
        scale = cfg_scale if (cfg_scale > 0 and uid % 2 == 0) else None
        n_guided += scale is not None
        if prompt is not None:
            from repro.models import text_encoder
            cond = text_encoder.encode([f"{prompt} #{uid}"], cfg)[0]
        elif cond_tokens is not None:
            # vary the token count per request so the engine's
            # length-bucketed lane groups actually get exercised
            import jax.numpy as jnp
            from repro.models import text_encoder
            n_tok = 1 + (uid % cond_tokens)
            L = text_encoder.bucket_length(n_tok, cfg.cond_seq_len)
            feats = jax.random.normal(jax.random.PRNGKey(seed + 7 + uid),
                                      (L, cfg.cond_dim))
            mask = (jnp.arange(L) < n_tok).astype(jnp.float32)[:, None]
            cond = jnp.concatenate([feats * mask, mask], axis=-1)
        else:
            cond = int(rng.integers(0, cfg.n_classes))
        engine.submit(x_T, cond, slo_s=slo_s, cfg_scale=scale)
    done = engine.run_to_completion()
    dt = time.time() - t0
    for req in done:
        assert np.all(np.isfinite(np.asarray(req.image)))
    stats = engine.stats()
    note = ("" if stats["cost_model"] == "configured"
            else " [default-uncalibrated cost model]")
    print(f"served {stats['n_completed']}/{n_requests} generation requests "
          f"({n_guided} CFG) in {dt:.2f}s ({stats['n_completed']/dt:.2f} "
          f"img/s wall, {stats['throughput_modeled_rps']:.2f} img/s "
          f"modeled{note}) planner={planner} backend={backend} "
          f"slots={slots} rounds={stats['rounds']} "
          f"patches={engine.plan.patches} stages={engine.stages} "
          f"seq={engine.seq} frames={engine.frames}")
    if use_pallas_attention:
        # trace-time kernel path counters (DESIGN.md §15): did the lane
        # programs this engine compiled contain the kernels?
        print(f"  kernel_stats={stats['kernels']}")
    if stats["plan_cache"] is not None:
        c = stats["plan_cache"]
        print(f"  plan cache: {c['hits']} hits / {c['misses']} misses "
              f"(hit rate {c['hit_rate']:.0%}), "
              f"{c['invalidations']} invalidated — a warm cache skips "
              "planner search on restart")
    for r in stats["requests"]:
        slo = "" if r["slo_met"] is None else f" slo_met={r['slo_met']}"
        print(f"  req {r['uid']}: queued {r['queue_rounds']} rounds, "
              f"served {r['service_rounds']} rounds, modeled latency "
              f"{r['modeled_latency_s']*1e3:.1f} ms{slo}")
    return engine


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gemma-2b")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--max-new", type=int, default=12)
    ap.add_argument("--diffusion", action="store_true",
                    help="serve diffusion requests via StadiPipeline")
    ap.add_argument("--occupancies", default="0.0,0.6")
    ap.add_argument("--planner", default="stadi",
                    help="allocation planner (diffusion only): uniform / "
                         "spatial / temporal / stadi / makespan / "
                         "stadi_pipefuse (joint step+patch+stage search)")
    ap.add_argument("--backend", default="emulated",
                    choices=["emulated", "spmd", "pipefuse"],
                    help="serving needs images; 'pipefuse' runs the "
                         "displaced patch pipeline (DESIGN.md §11) — the "
                         "engine places stage chains instead of "
                         "whole-model workers")
    ap.add_argument("--m-base", type=int, default=16)
    ap.add_argument("--m-warmup", type=int, default=4)
    ap.add_argument("--slo-ms", type=float, default=None,
                    help="per-request modeled-latency SLO (diffusion only)")
    ap.add_argument("--exchange", default="sync",
                    choices=["sync", "stale_async", "predictive", "ring"],
                    help="boundary-exchange policy (diffusion only, "
                         "DESIGN.md §10; 'ring' = per-hop-staged seq-"
                         "parallel variant, DESIGN.md §13)")
    ap.add_argument("--exchange-refresh", type=int, default=2,
                    help="full refresh every E boundaries (stale/predictive)")
    ap.add_argument("--num-stages", type=int, default=1,
                    help="depth stages for --backend pipefuse (diffusion "
                         "only, DESIGN.md §11): DiT blocks are split over a "
                         "speed-proportional stage chain; 1 = pure patch "
                         "parallelism, 0 = let stadi_pipefuse search")
    ap.add_argument("--cfg-scale", type=float, default=0.0,
                    help="classifier-free guidance weight (diffusion only, "
                         "DESIGN.md §12): > 0 submits every other request "
                         "as a CFG request — a mixed guided/unguided batch")
    ap.add_argument("--plan-cache", default=None, metavar="DIR",
                    help="persistent plan-cache directory (diffusion only, "
                         "DESIGN.md §14): planner outputs are keyed by "
                         "(cluster, model, workload) and reused across "
                         "restarts; e.g. results/plan_cache")
    ap.add_argument("--seq-shards", type=int, default=1,
                    help="sequence-parallel attention (diffusion only, "
                         "DESIGN.md §13): Ulysses/ring shards per patch "
                         "worker; lanes batch by ring-hop identity (1 = "
                         "attention-unsharded, 0 = let stadi_seq search)")
    ap.add_argument("--num-frames", type=int, default=1,
                    help="video serving lanes (diffusion only, DESIGN.md "
                         "§16): latent frames per request (1 = image; > 1 "
                         "serves one clip per request, run-to-completion "
                         "in its admission round)")
    ap.add_argument("--frame-groups", type=int, default=0,
                    help="frame placement (diffusion only): 1 = frame-"
                         "sequential, > 1 = frame-parallel member rows "
                         "(needs --planner stadi_video), 0 = auto search")
    cond_group = ap.add_mutually_exclusive_group()
    cond_group.add_argument("--prompt", default=None,
                            help="text prompt (diffusion only, DESIGN.md "
                                 "§17): the model is built text-conditioned "
                                 "and every request carries encoded prompt "
                                 "tokens (suffixed per uid for variety)")
    cond_group.add_argument("--cond-tokens", type=int, default=None,
                            metavar="L",
                            help="prompt lanes with up to L random-normal "
                                 "conditioning tokens per request (lengths "
                                 "vary per uid to exercise the engine's "
                                 "length-bucketed lane groups)")
    ap.add_argument("--cond-seq-len", type=int, default=32,
                    help="text-conditioned models: max prompt bucket "
                         "(DiTConfig.cond_seq_len)")
    ap.add_argument("--reduced", action="store_true",
                    help="diffusion only: serve the reduced-width model "
                         "(default: the config's published widths)")
    ap.add_argument("--use-pallas", action="store_true",
                    help="diffusion only: route attention + CFG epilogue "
                         "through the Pallas kernels (DESIGN.md §15; "
                         "interpret mode off-TPU)")
    args = ap.parse_args()
    if args.diffusion:
        if args.arch == ap.get_default("arch"):
            args.arch = "tiny-dit"       # LLM default doesn't apply here
        elif "dit" not in args.arch:
            ap.error(f"--diffusion serves DiT archs, not {args.arch!r}")
        serve_diffusion(args.arch,
                        occupancies=[float(x) for x in
                                     args.occupancies.split(",")],
                        n_requests=args.requests, slots=args.slots,
                        m_base=args.m_base, m_warmup=args.m_warmup,
                        planner=args.planner, backend=args.backend,
                        slo_s=(args.slo_ms / 1e3
                               if args.slo_ms is not None else None),
                        exchange=args.exchange,
                        exchange_refresh=args.exchange_refresh,
                        num_stages=args.num_stages,
                        cfg_scale=args.cfg_scale,
                        seq_shards=args.seq_shards,
                        num_frames=args.num_frames,
                        frame_groups=args.frame_groups,
                        plan_cache_dir=args.plan_cache,
                        prompt=args.prompt, cond_tokens=args.cond_tokens,
                        cond_seq_len=args.cond_seq_len,
                        reduced=args.reduced,
                        use_pallas_attention=args.use_pallas)
    else:
        if args.prompt is not None or args.cond_tokens is not None:
            ap.error("--prompt/--cond-tokens are diffusion-only "
                     "(use --diffusion)")
        serve(args.arch, n_requests=args.requests, slots=args.slots,
              prompt_len=args.prompt_len, max_new=args.max_new)


if __name__ == "__main__":
    main()
