"""Find the knee of a serving cell: the highest offered rate that the
engine completes as it is offered, with a backlog that does not grow.

    python bench/sweep.py --workload <serve cell> --seconds <s> \\
        --rates <r> [<r> ...] --seeds <n> [<n> ...]

One process. For each rate and seed a fresh set-up of the cell with that
rate (programs compiled once, then reused) and a window with no drain.
Prints one JSON line per window: images due and completed in it, the
completion rate against the offered one, the backlog (due, not finished)
at each quarter of the window, serve_p90_s's arithmetic and the mean
round. In-flight work keeps the backlog near the slots' worth at any
rate; above capacity it grows from quarter to quarter. Run it to fix the
serve cells' rates; the benchmark's own runs do not run this.
"""
import argparse
import copy
import json
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bench import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", default=[12345])
    args = ap.parse_args(argv)
    harness.prepare_environment()
    base = harness.cell_spec(args.workload)
    harness.check_devices(base["chips"])
    log = harness.CompileLog()
    for rate in args.rates:
        for seed in args.seeds:
            cell = copy.deepcopy(base)
            cell["traffic"].update(rate_per_s=rate, drain_s=0.0)
            run = harness.start(cell, seed, log)
            d = run.driver
            d.window(args.seconds)
            e2e = d.end_to_end()
            quarters = [args.seconds * q / 4 for q in (1, 2, 3, 4)]
            backlog = [int(np.sum(d.due <= t) - np.sum(d.ready <= t))
                       for t in quarters]
            print(json.dumps({
                "rate_per_s": rate, "seed": seed, "due": len(d.due),
                "completed": d.images_in_window(),
                "completed_per_offered": e2e["serve_images_per_s"] / rate,
                "backlog_at_quarters": backlog,
                "images_per_s": e2e["serve_images_per_s"],
                "p90_s_lower_bound": e2e["serve_p90_s"],
                "round_ms": 1e3 * sum(d.round_s) / max(1, len(d.round_s))}),
                flush=True)
            d.release()
    return 0


if __name__ == "__main__":
    sys.exit(main())
