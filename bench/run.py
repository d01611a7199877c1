"""Run one benchmark cell and print its result as the last line.

    python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Cells, metrics and bounds are in ``BENCHMARK.json`` at the checkout's root;
``bench/harness.py`` says where everything else is found. Without an
accelerator, or with fewer chips than the cell asks for, the run exits
non-zero and prints no result.
"""
import time

T_START = time.perf_counter()       # set-up is timed from here

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bench import harness  # noqa: E402  (JAX-free)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cache = harness.prepare_environment()
    try:
        cell = harness.cell_spec(args.workload)
        print(f"[bench] {args.workload} seed {args.seed}, compile cache "
              f"{cache}", file=sys.stderr, flush=True)
        result = harness.run_cell(cell, args.seed, args.seconds,
                                  bool(args.trace), T_START)
    except (harness.BenchError, ImportError, OSError) as e:
        print(f"[bench] no result: {e}", file=sys.stderr, flush=True)
        return 2
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
