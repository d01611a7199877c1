"""Readings that the correctness limits are set from, in one process.

    python bench/calibrate.py --workload <name> --seconds <s> \\
        --seeds <n> [<n> ...] [--control 3] [--fault <name> ...] \\
        [--fault-seeds 3]

For each seed: the cell's set-up and a short window at the cell's own load
through the timed path, then the plain reference over the run's sample;
for the first ``--control`` seeds also the control (the reference at fp8
in the program's place). Then, for each ``--fault`` (a name in
``bench/faults.py`` for the cell's driver), the same on the first
``--fault-seeds`` seeds with that fault planted under the timed path. One
JSON line per run on standard output. Set-up compiles once; later seeds
reuse the programs. The benchmark's own runs do not run this.
"""
import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bench import faults, harness  # noqa: E402


def reading(cell, seed, seconds, log, control=False, fault=None) -> dict:
    planted = faults.Planted()
    if fault:
        faults.FAULTS[cell["traffic"]["driver"]][fault](planted.setattr)
    try:
        t0 = time.perf_counter()
        run = harness.start(cell, seed, log)
        run.driver.window(seconds)
        outputs = run.driver.outputs()
        e2e = run.driver.end_to_end()
        run.driver.release()
    finally:
        planted.undo()
    t1 = time.perf_counter()
    nums = harness.compare(run, outputs, control=control)
    return {"seed": seed, "fault": fault, **nums, "outputs": len(outputs),
            "end_to_end": e2e, "run_s": t1 - t0,
            "reference_s": time.perf_counter() - t1}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control", type=int, default=3)
    ap.add_argument("--fault", action="append", default=[])
    ap.add_argument("--fault-seeds", type=int, default=3)
    args = ap.parse_args(argv)
    harness.prepare_environment()
    cell = harness.cell_spec(args.workload)
    harness.check_devices(cell["chips"])
    log = harness.CompileLog()
    runs = [(seed, i < args.control, None) for i, seed in enumerate(args.seeds)]
    runs += [(seed, False, f) for f in args.fault
             for seed in args.seeds[:args.fault_seeds]]
    for seed, control, fault in runs:
        print(json.dumps(reading(cell, seed, args.seconds, log, control,
                                 fault)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
