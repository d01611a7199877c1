import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=512")

"""§Perf hillclimbing: lower one (arch x shape) under named optimization
variants, re-derive the roofline terms, and log hypothesis -> before ->
after (EXPERIMENTS.md §Perf reads results/perf/*.json).

Variants (composable, comma-separated):
  chunked     attn_impl=chunked — flash-style online softmax; kills the
              materialized S x T score matrices (memory term)
  seqpar      shard the sequence dim of batch inputs over 'model'
              (sequence parallelism for prefill — the paper's patch
              parallelism mapped onto an LM request)
  embed_dp    embedding/vocab tables sharded vocab x 'model' -> d_model-only
              ('data') — trades the decode all-gather of logits for
              replicated vocab weights
  remat       jax.checkpoint over the layer body (memory term, train)

  PYTHONPATH=src python -m repro.launch.perf --arch llama3-405b \
      --shape train_4k --variants chunked
"""

import argparse
import json
import time

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                           "results", "perf")


def run_variant(arch: str, shape_name: str, variants: str,
                multi_pod: bool = False) -> dict:
    import jax

    from repro.configs import get_config
    from repro.launch import roofline as rl
    from repro.launch.mesh import make_production_mesh
    from repro.launch.shapes import SHAPES, build_lowerable, _dryrun_cfg
    from repro.sharding import specs as sh

    vset = set(v for v in variants.split(",") if v)
    cfg = _dryrun_cfg(arch)
    if "chunked" in vset:
        cfg = cfg.replace(attn_impl="chunked", attn_chunk=2048)
    if "actbatch" in vset:
        cfg = cfg.replace(act_shard="batch")
    if "actseq" in vset:
        cfg = cfg.replace(act_shard="seqpar")
    mesh = make_production_mesh(multi_pod=multi_pod)
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"

    old_rules = dict(sh._RULES)
    old_cache = sh.cache_specs
    if "embed_dp" in vset:
        sh._RULES["embed"] = (None, "data")
        sh._RULES["head"] = ("data", None)
    if "cache_nosplit" in vset:
        # kv caches: batch-sharded only (no T-over-model fallback that makes
        # GSPMD emit grouped partial-sum all-reduces on the kv path)
        from jax.sharding import PartitionSpec as P

        def cache_specs_nosplit(cache, mesh_):
            import numpy as np
            ba = sh.batch_axes(mesh_)

            def spec(leaf):
                shape = np.shape(leaf)
                if len(shape) == 5:
                    b_ax = ba if sh._div(shape[1], mesh_, ba) else None
                    return P(None, b_ax, None, None, None)
                if len(shape) == 0:
                    return P()
                return P(*([None] * len(shape)))
            import jax as _jax
            return _jax.tree.map(spec, cache)

        sh.cache_specs = cache_specs_nosplit

    fn, args, shardings = build_lowerable(arch, shape_name, cfg=cfg)
    in_sh = shardings(mesh)

    if "seqpar" in vset:
        # re-spec batch leaves: dim1 (sequence) over 'model'
        from jax.sharding import NamedSharding, PartitionSpec as P

        def reseq(ns):
            spec = ns.spec
            if len(spec) >= 2 and spec[1] is None:
                parts = list(spec)
                parts[1] = "model"
                return NamedSharding(mesh, P(*parts))
            return ns
        # batch structs are the last element for train/prefill
        idx = 2 if SHAPES[shape_name].kind == "train" else 1
        lst = list(in_sh)
        lst[idx] = jax.tree.map(reseq, lst[idx])
        in_sh = tuple(lst)

    t0 = time.time()
    with mesh:
        lowered = jax.jit(fn, in_shardings=in_sh).lower(*args)
        compiled = lowered.compile()
        cost = compiled.cost_analysis() or {}
        coll = rl.collective_bytes(compiled.as_text())
        mem = compiled.memory_analysis()
    sh._RULES.clear()
    sh._RULES.update(old_rules)
    sh.cache_specs = old_cache

    roof = rl.build(arch, shape_name, mesh_name, mesh.devices.size, cost,
                    coll, flash="chunked" in vset)
    report = {
        "arch": arch, "shape": shape_name, "mesh": mesh_name,
        "variants": sorted(vset) or ["baseline"],
        "compile_s": round(time.time() - t0, 1),
        "temp_bytes_per_dev": getattr(mem, "temp_size_in_bytes", None),
        "collective_bytes": {k: v for k, v in coll.items() if k != "_counts"},
        "roofline": roof.to_dict(),
    }
    os.makedirs(RESULTS_DIR, exist_ok=True)
    tag = "-".join(sorted(vset)) or "baseline"
    out = os.path.join(RESULTS_DIR, f"{arch}__{shape_name}__{tag}.json")
    with open(out, "w") as f:
        json.dump(report, f, indent=2)
    r = roof
    print(f"[{arch} x {shape_name} | {tag}] compute={r.compute_s:.4g}s "
          f"memory={r.memory_s:.4g}s collective={r.collective_s:.4g}s "
          f"dom={r.dominant} temp={report['temp_bytes_per_dev']/1e9:.1f}GB "
          f"(compile {report['compile_s']}s)", flush=True)
    return report


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", required=True)
    ap.add_argument("--variants", default="")
    ap.add_argument("--multi-pod", action="store_true")
    args = ap.parse_args()
    run_variant(args.arch, args.shape, args.variants, args.multi_pod)


if __name__ == "__main__":
    main()
