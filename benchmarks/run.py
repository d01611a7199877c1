"""Benchmark registry — one module per paper table/figure (DESIGN.md §8).

  PYTHONPATH=src python -m benchmarks.run [--smoke] [names...]

Prints ``name,us_per_call,derived`` CSV rows (also written to
results/bench.csv). ``--smoke`` exports STADI_BENCH_SMOKE=1 so benches run
shrunk workloads (the CI bench-smoke job)."""
from __future__ import annotations

import os
import sys
import time
import traceback

from repro.hostenv import use_compile_cache
use_compile_cache()                         # before anything imports jax

from benchmarks import common

REGISTRY = [
    ("kernels", "benchmarks.bench_kernels", "kernel micro vs oracles"),
    ("latency", "benchmarks.bench_latency", "paper Fig. 8"),
    ("ablation", "benchmarks.bench_ablation", "paper Table III"),
    ("patch_ratio", "benchmarks.bench_patch_ratio", "paper Fig. 9"),
    ("quality", "benchmarks.bench_quality", "paper Table II"),
    ("redundancy", "benchmarks.bench_redundancy", "paper Thm. 1/2"),
    ("beyond", "benchmarks.bench_beyond", "beyond-paper: tiers + reprofiling"),
    ("exchange", "benchmarks.bench_exchange", "boundary-exchange modes, DESIGN §10"),
    ("pipefuse", "benchmarks.bench_pipefuse", "displaced patch pipeline, DESIGN §11"),
    ("guidance", "benchmarks.bench_guidance", "CFG guidance placement, DESIGN §12"),
    ("seqpar", "benchmarks.bench_seqpar", "sequence-parallel attention, DESIGN §13"),
    ("video", "benchmarks.bench_video", "multi-frame diffusion, DESIGN §16"),
    ("textcond", "benchmarks.bench_textcond", "prompt conditioning, DESIGN §17"),
    ("roofline", "benchmarks.bench_roofline", "deliverable g"),
    ("serving", "benchmarks.bench_serving", "continuous batching, DESIGN §9"),
    ("load", "benchmarks.bench_load", "load generator + plan cache, DESIGN §14"),
]


def main() -> None:
    argv = sys.argv[1:]
    if "--smoke" in argv:
        argv = [a for a in argv if a != "--smoke"]
        os.environ["STADI_BENCH_SMOKE"] = "1"
    want = set(argv)
    failures = []
    for name, module, what in REGISTRY:
        if want and name not in want:
            continue
        print(f"## {name}  ({what})", flush=True)
        t0 = time.time()
        try:
            mod = __import__(module, fromlist=["main"])
            mod.main()
            print(f"## {name} done in {time.time()-t0:.1f}s\n", flush=True)
        except Exception:
            traceback.print_exc()
            failures.append(name)
    common.flush_csv()
    if failures:
        print(f"FAILED benches: {failures}")
        raise SystemExit(1)
    print("all benchmarks OK")


if __name__ == "__main__":
    main()
