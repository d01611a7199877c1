"""The benchmark harness: one run of one cell, driven by data.

A cell is an entry of ``workloads`` in ``BENCHMARK.json``. The harness finds
everything else by name: the model configuration in
``bench/configs/<config>.json`` (with its plain reference in
``bench/reference/<reference>.py``), the traffic mix in
``bench/traffic/<traffic>.json`` (which names its driver in
``bench/drivers/<driver>.py``), the cell's correctness limits in
``bench/workloads/<cell>.json``, each per-layer metric's reader in
``bench/metrics/<metric>.py`` and the chip's peaks in ``bench/peaks.json``.
Adding a cell, a configuration, a traffic mix or a metric adds files; it
edits none.

A driver module defines ``Driver(run)`` with: ``SPANS`` (the names of the
harness spans it writes), ``weights_sharding()``,
``setup()`` (builds the system under test and warms every shape the window
uses), ``window(seconds)``, ``notes()`` (lines for standard error),
``plan()`` (the executed (m_base, m_warmup, ratios, rows)),
``end_to_end()`` ({metric: value}), ``images_in_window()``, ``window_s()``,
``outputs()`` ([{"k": request id, "image": array}]), ``attempted()``,
``failed()`` and ``release()`` (drops the program's state). A metric
reader defines ``read(run)``, returning a number or None when it finds
nothing to read.

This module never imports JAX at import time: ``run.py`` points the
persistent compilation cache first.
"""
from __future__ import annotations

import gc
import importlib.util
import json
import math
import os
import shutil
import sys
import time
from pathlib import Path
from typing import Dict, List, Optional

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
TRACE_DIR = ROOT / ".bench_trace"
HARNESS_SPANS = ("window", "plan")      # the harness's own; drivers add theirs


class BenchError(RuntimeError):
    """A run that cannot give a result: no accelerator, bad data files."""


# ---------------------------------------------------------------- data

def load_json(path: Path):
    with open(path) as f:
        return json.load(f)


def load_module(path: Path):
    """Import a file by path (metric readers are named like ``mfu.gen.py``,
    which ``import`` cannot spell)."""
    if not path.is_file():
        raise BenchError(f"missing {path.relative_to(ROOT)}")
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def benchmark() -> dict:
    return load_json(ROOT / "BENCHMARK.json")


def cell_spec(name: str, bench: Optional[dict] = None) -> dict:
    """Everything one cell needs, gathered from the data files."""
    bench = bench or benchmark()
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise BenchError(f"unknown workload {name!r}; one of {sorted(cells)}")
    w = cells[name]
    config = load_json(BENCH / "configs" / f"{w['config']}.json")
    traffic = load_json(BENCH / "traffic" / f"{w['traffic']}.json")
    check = load_json(BENCH / "workloads" / f"{name}.json")

    def applies(metric):
        return name in metric.get("workloads", [name])
    return {"name": name, "chips": w["chips"], "config": config,
            "traffic": traffic, "check": check,
            "end_to_end": [m for m in bench["end_to_end"] if applies(m)],
            "per_layer": [m for m in bench["per_layer"] if applies(m)]}


def seed_key(seed: int, stream: int):
    """A JAX key from any whole seed (beyond 32 bits too)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    state = np.random.SeedSequence([seed, stream]).generate_state(2)
    return jax.random.wrap_key_data(jnp.asarray(state, jnp.uint32))


def request_input(sizes: dict, seed: int, k: int):
    """Request ``k`` of a run: (x_T [1, H, W, C] float32, class id), drawn
    on the host from (seed, k) alone."""
    import numpy as np
    rng = np.random.default_rng([seed, 2, k])
    H, C = sizes["latent_size"], sizes["channels"]
    x = rng.standard_normal((1, H, H, C), dtype=np.float32)
    return x, int(rng.integers(sizes["n_classes"]))


# ---------------------------------------------------------------- timing

class CompileLog:
    """Counts the XLA backend compiles JAX's monitoring events report (a
    persistent-cache hit reports its load as one) and the persistent-cache
    hits. Registered once per process."""

    COMPILE = "/jax/core/compile/backend_compile_duration"
    CACHE_HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        import jax
        self.compiles = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        self.compiles += event == self.COMPILE

    def _event(self, event, **_):
        self.cache_hits += event == self.CACHE_HIT


# ---------------------------------------------------------------- a run

class Run:
    """State of one run, shared with the driver and the metric readers."""

    def __init__(self, cell: dict, seed: int,
                 log: Optional[CompileLog] = None):
        self.cell, self.seed = cell, seed
        self.sizes = cell["config"]["sizes"]
        self.traffic = cell["traffic"]
        self.log = log or CompileLog()
        self.reference = load_module(
            BENCH / "reference" / f"{cell['config']['reference']}.py")
        self.driver = None
        self.trace_summary = None
        self.peak = None

    def program_config(self):
        """The system under test's model config for these sizes."""
        from repro.configs.diffusion import DiTConfig
        return DiTConfig(arch_id=self.cell["config"]["name"], **self.sizes)

    def make_weights(self, sharding=None):
        """Weights from the seed, made on the device in one program, in the
        parameter dtype; checked against the layout the program reads."""
        import jax
        from repro.models.diffusion import dit
        ref = self.reference
        key = tuple(sorted(self.sizes.items()))
        make = jax.jit(lambda k: ref.make_weights(k, dict(key)),
                       out_shardings=sharding)
        weights = make(seed_key(self.seed, 1))
        cfg = self.program_config()
        want = jax.eval_shape(lambda k: dit.init_params(k, cfg),
                              jax.random.PRNGKey(0))
        got = jax.tree.map(lambda a: (a.shape, a.dtype), weights)
        if jax.tree.map(lambda a: (a.shape, a.dtype), want) != got:
            raise BenchError("reference weight layout differs from the "
                             "program's parameters")
        return weights

    def request(self, k: int):
        return request_input(self.sizes, self.seed, k)

    @staticmethod
    def spans(name: str):
        """A harness span: written into the profiler's trace, where
        ``trace_reduce`` attributes the device's idle time to it."""
        import jax
        return jax.profiler.TraceAnnotation(name)

    def stadi_config(self):
        """The STADI knobs the traffic mix pins (what is computed); how it
        is computed (kernels) is left to the program."""
        from repro.core.pipeline import StadiConfig
        s = dict(self.traffic["stadi"])
        occ = s.pop("occupancies")
        return StadiConfig.from_occupancies(occ, **s)


def _device_info(devices, chips: int) -> dict:
    import jax
    used = devices[:chips]
    peak = 0
    for d in used:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": used[0].platform, "kind": used[0].device_kind,
            "count": len(jax.devices()), "memory_peak_bytes": peak}


def check_devices(chips: int):
    """The chips this cell needs, or BenchError: there is no CPU fallback."""
    import jax
    devices = jax.devices()
    if devices[0].platform == "cpu":
        raise BenchError("no accelerator: JAX found only the CPU")
    if len(devices) < chips:
        raise BenchError(f"the cell needs {chips} chips, JAX found "
                         f"{len(devices)}")
    return devices


def peaks_for(kind: str) -> dict:
    table = load_json(BENCH / "peaks.json")["devices"]
    if kind not in table:
        raise BenchError(f"no peaks for device_kind {kind!r} in "
                         "bench/peaks.json")
    return table[kind]


def rel_l2(a, b) -> float:
    import numpy as np
    a = np.asarray(a, np.float64).ravel()
    b = np.asarray(b, np.float64).ravel()
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


def compare(run: Run, outputs: List[dict], control: bool = False) -> dict:
    """Run the plain reference over a seeded sample of the outputs the
    window produced and return the numbers compared: the worst relative L2
    gap of an image to the reference's, and whether the executed plan
    differs from the reference's. ``control`` also puts the reference at
    fp8 in the program's place and reports its worst gap."""
    import numpy as np
    check = run.cell["check"]
    ref = run.reference
    stadi = run.traffic["stadi"]
    M, Mw = stadi["m_base"], stadi["m_warmup"]
    wp = run.sizes["latent_size"] // run.sizes["patch_size"]
    _, ratios, rows = ref.stadi_plan([1.0 - o for o in stadi["occupancies"]],
                                     M, Mw, wp)
    plan_mismatch = int(run.driver.plan() != (M, Mw, list(ratios), list(rows)))
    rng = np.random.default_rng([run.seed, 3])
    n = min(check["sample"], len(outputs))
    pick = sorted(rng.choice(len(outputs), size=n, replace=False)) if n else []
    gap = lambda a, b: rel_l2(a, b) if np.all(np.isfinite(a)) else math.inf
    worst = math.inf if not pick else 0.0
    worst_control = worst
    for i in pick:
        x_T, cls = run.request(outputs[i]["k"])
        want = np.asarray(ref.generate(run.weights, run.sizes, stadi, x_T, cls))
        got = np.asarray(outputs[i]["image"], np.float32).reshape(want.shape)
        worst = max(worst, gap(got, want))
        if control:
            low = np.asarray(ref.generate(run.weights, run.sizes, stadi, x_T,
                                          cls, precision="fp8"))
            worst_control = max(worst_control, gap(low, want))
    out = {"image_rel_l2": worst, "plan_mismatch": plan_mismatch,
           "compared": n}
    if control:
        out["control_rel_l2"] = worst_control
    return out


def start(cell: dict, seed: int, log: Optional[CompileLog] = None) -> Run:
    """A run up to its window: the cell's driver, the weights from the seed
    and the driver's set-up (the system under test built, every shape the
    window uses warmed)."""
    run = Run(cell, seed, log)
    driver = load_module(BENCH / "drivers" / f"{cell['traffic']['driver']}.py")
    run.driver = driver.Driver(run)
    run.weights = run.make_weights(run.driver.weights_sharding())
    run.driver.setup()
    return run


def run_cell(cell: dict, seed: int, seconds: float, trace: bool,
             t_start: float, check_device: bool = True) -> dict:
    """One run: set-up, the measured window, per-layer metrics (traced
    runs), the correctness check. Returns the result line as a dict."""
    import jax
    if check_device:
        devices = check_devices(cell["chips"])
        peaks_for(devices[0].device_kind)
    else:
        devices = jax.devices()
    run = start(cell, seed)
    compiles0 = run.log.compiles
    setup_s = time.perf_counter() - t_start
    print(f"[bench] set-up: {compiles0} backend compiles or cache loads, "
          f"{run.log.cache_hits} persistent-cache hits", file=sys.stderr,
          flush=True)
    if trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        jax.profiler.start_trace(str(TRACE_DIR),
                                 profiler_options=_profile_options())
    with run.spans("window"):
        run.driver.window(seconds)
    if trace:
        jax.profiler.stop_trace()
    compiles = run.log.compiles - compiles0
    print(f"[bench] compiles inside the window: {compiles}", file=sys.stderr,
          flush=True)
    for line in run.driver.notes():
        print(f"[bench] {line}", file=sys.stderr, flush=True)
    device = _device_info(devices, cell["chips"])

    metrics: Dict[str, dict] = {}
    breakdown = None
    if trace:
        from bench import trace_reduce
        run.peak = peaks_for(device["kind"]) if check_device else None
        run.trace_summary = trace_reduce.reduce_xplane(
            _newest_xplane(), HARNESS_SPANS + tuple(run.driver.SPANS))
        device["busy_s"] = run.trace_summary["busy_s"]
        device["window_s"] = run.trace_summary["window_s"]
        breakdown = {"device_ops": run.trace_summary["device_ops"],
                     "idle_gaps": run.trace_summary["idle_gaps"]}
        for m in cell["per_layer"]:
            value = load_module(BENCH / "metrics" / f"{m['name']}.py").read(run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        e2e = run.driver.end_to_end()
        for m in cell["end_to_end"]:
            value = setup_s if m["name"] == "setup_s" else e2e[m["name"]]
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    outputs = run.driver.outputs()
    attempted, failed = run.driver.attempted(), run.driver.failed()
    run.driver.release()
    gc.collect()
    t_ref = time.perf_counter()
    numbers = compare(run, outputs)
    print(f"[bench] reference over {numbers['compared']} outputs took "
          f"{time.perf_counter() - t_ref:.1f}s", file=sys.stderr, flush=True)
    limits = cell["check"]["limits"]
    correct = numbers["compared"] > 0 and all(numbers[k] <= limits[k]
                                              for k in limits)
    checks = {k: {"value": numbers[k] if math.isfinite(numbers[k]) else None,
                  "limit": limits[k]} for k in limits}
    for k, c in checks.items():
        print(f"check {k} = {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr, flush=True)
    out = {"correct": correct, "attempted": attempted, "failed": failed,
           "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = checks
    return out


def _profile_options():
    """Device operations and the harness's annotations; no Python call
    tracing and no HLO protos, which would make the trace many times
    larger and slow the host."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    opts.enable_hlo_proto = False
    return opts


def _newest_xplane() -> str:
    files = sorted(TRACE_DIR.glob("plugins/profile/*/*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not files:
        raise BenchError("the profiler wrote no trace")
    return str(files[-1])


def prepare_environment() -> str:
    """Before JAX is imported: the persistent compilation cache (an outside
    ``JAX_COMPILATION_CACHE_DIR`` is kept, else ``<checkout>/.jax_cache``),
    with every program cached so that a second run compiles nothing."""
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "0")
    sys.path.insert(0, str(ROOT / "src"))
    return os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                                 str(ROOT / ".jax_cache"))
