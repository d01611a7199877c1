import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=512")

"""Multi-pod dry-run (deliverable e).

For every (architecture x input-shape x mesh):
    jax.jit(step, in_shardings=...).lower(**input_specs).compile()
on the 16x16 single-pod mesh and the 2x16x16 multi-pod mesh, printing
memory_analysis (fits?) and cost_analysis (roofline feed). Results land in
results/dryrun/<arch>__<shape>__<mesh>.json for EXPERIMENTS.md §Dry-run and
benchmarks/bench_roofline.py.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch llama3-405b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod] [--skip-done]
"""

import argparse
import json
import time
import traceback

import jax

from repro.configs import ASSIGNED
from repro.launch import roofline as rl
from repro.launch.mesh import make_production_mesh
from repro.launch.shapes import SHAPES, build_lowerable

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                           "results", "dryrun")


def _out_path(arch: str, shape: str, mesh_name: str) -> str:
    os.makedirs(RESULTS_DIR, exist_ok=True)
    return os.path.join(RESULTS_DIR, f"{arch}__{shape}__{mesh_name}.json")


def applicable(arch: str, shape: str) -> bool:
    """DESIGN.md §4 carve-outs (none skipped: sliding-window variant covers
    long_500k on full-attention archs)."""
    return True


def run_one(arch: str, shape: str, multi_pod: bool, verbose: bool = True) -> dict:
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    t0 = time.time()
    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = mesh.devices.size
    fn, args, shardings = build_lowerable(arch, shape)
    in_sh = shardings(mesh)
    with mesh:
        lowered = jax.jit(fn, in_shardings=in_sh).lower(*args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower
        mem = compiled.memory_analysis()
        cost = compiled.cost_analysis() or {}
        hlo = compiled.as_text()
    coll = rl.collective_bytes(hlo)
    roof = rl.build(arch, shape, mesh_name, chips, cost, coll)
    mem_d = {}
    for attr in ("generated_code_size_in_bytes", "argument_size_in_bytes",
                 "output_size_in_bytes", "temp_size_in_bytes",
                 "alias_size_in_bytes"):
        mem_d[attr] = getattr(mem, attr, None)
    report = {
        "arch": arch, "shape": shape, "mesh": mesh_name, "chips": chips,
        "ok": True,
        "lower_s": round(t_lower, 2), "compile_s": round(t_compile, 2),
        "memory_analysis": mem_d,
        "cost_analysis": {k: cost.get(k) for k in
                          ("flops", "bytes accessed", "transcendentals")},
        "collective_bytes": {k: v for k, v in coll.items() if k != "_counts"},
        "collective_counts": coll.get("_counts"),
        "roofline": roof.to_dict(),
    }
    if verbose:
        print(f"[{arch} x {shape} x {mesh_name}] OK "
              f"lower={t_lower:.1f}s compile={t_compile:.1f}s")
        print(f"  memory_analysis: {mem_d}")
        print(f"  cost_analysis:   flops={cost.get('flops'):.3e} "
              f"bytes={cost.get('bytes accessed'):.3e}" if cost.get("flops")
              else f"  cost_analysis:   {cost}")
        print(f"  collectives:     {report['collective_bytes']}")
        print(f"  roofline:        compute={roof.compute_s:.4f}s "
              f"memory={roof.memory_s:.4f}s collective={roof.collective_s:.4f}s "
              f"dominant={roof.dominant}")
    with open(_out_path(arch, shape, mesh_name), "w") as f:
        json.dump(report, f, indent=2)
    return report


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None, help="arch id (default: all)")
    ap.add_argument("--shape", default=None, choices=list(SHAPES) + [None])
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--skip-done", action="store_true")
    args = ap.parse_args()

    archs = [args.arch] if args.arch else ASSIGNED
    shapes = [args.shape] if args.shape else list(SHAPES)
    meshes = [False, True] if args.both_meshes else [args.multi_pod]

    failures = []
    for arch in archs:
        for shape in shapes:
            if not applicable(arch, shape):
                continue
            for mp in meshes:
                mesh_name = "pod2x16x16" if mp else "pod16x16"
                path = _out_path(arch, shape, mesh_name)
                if args.skip_done and os.path.exists(path):
                    print(f"[{arch} x {shape} x {mesh_name}] cached, skipping")
                    continue
                try:
                    run_one(arch, shape, mp)
                except Exception as e:  # noqa: BLE001
                    failures.append((arch, shape, mesh_name, repr(e)))
                    traceback.print_exc()
                    with open(path, "w") as f:
                        json.dump({"arch": arch, "shape": shape,
                                   "mesh": mesh_name, "ok": False,
                                   "error": repr(e)}, f, indent=2)
    if failures:
        print(f"\n{len(failures)} FAILURES:")
        for f4 in failures:
            print("  ", f4)
        raise SystemExit(1)
    print("\nall dry-runs OK")


if __name__ == "__main__":
    main()
