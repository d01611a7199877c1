"""The benchmark's data files: every name resolves to a file, every cell
reports what its metrics move, names and units use the allowed characters,
and the command gives no result without an accelerator."""
import json
import math
import os
import re
import subprocess
import sys

import pytest

from bench import harness

ROOT = harness.ROOT
BENCH = harness.BENCH
B = harness.benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
CELLS = [w["name"] for w in B["workloads"]]


def test_top_level_keys():
    assert set(B) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert B["command"][1] == "bench/run.py"
    for p in B["paths"]:
        assert (ROOT / p).is_dir() and len(p) <= 200
    assert 1 <= B["run_seconds"] <= 51


def test_check_fits_with_24_cells():
    runs = 2 + 14 * 24
    assert runs * (B["run_seconds"] + 60) + 24 * 2 * 90 + 1200 <= 43200


@pytest.mark.parametrize("cell", CELLS)
def test_cell_files_resolve(cell):
    w = next(x for x in B["workloads"] if x["name"] == cell)
    spec = harness.cell_spec(cell)
    assert (BENCH / "drivers" / f"{spec['traffic']['driver']}.py").is_file()
    assert (BENCH / "reference"
            / f"{spec['config']['reference']}.py").is_file()
    assert w["config"] in {c["name"] for c in B["configs"]}
    assert w["chips"] in (1, 4)
    assert spec["check"]["sample"] >= 1
    assert set(spec["check"]["limits"]) == {"image_rel_l2", "plan_mismatch"}
    assert spec["check"]["limits"]["plan_mismatch"] == 0
    e2e = {m["name"] for m in spec["end_to_end"]}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert spec["per_layer"]


def test_configs_are_files_under_paths():
    files = [c["file"] for c in B["configs"]]
    assert len(set(files)) == len(files)
    for c in B["configs"]:
        data = harness.load_json(ROOT / c["file"])
        assert data["name"] == c["name"]
        assert data["source"] == c["source"]
        assert data["reduced"] == c["reduced"]
        assert any(c["name"] == w["config"] for w in B["workloads"])


def test_sdxl_dit_is_the_repo_config_as_it_is():
    from repro.configs import get_config
    repo = get_config("sdxl-dit")
    for name in ("sdxl-dit", "dit-xl-2-256"):
        sizes = harness.load_json(BENCH / "configs" / f"{name}.json")["sizes"]
        for k, v in sizes.items():
            if not (name == "dit-xl-2-256" and k == "latent_size"):
                assert getattr(repo, k) == v, (name, k)
    assert harness.load_json(
        BENCH / "configs" / "dit-xl-2-256.json")["sizes"]["latent_size"] == 32


def test_every_metric_cell_reports_what_it_moves():
    for m in B["per_layer"]:
        assert (BENCH / "metrics" / f"{m['name']}.py").is_file()
        moves = next(e for e in B["end_to_end"] if e["name"] == m["moves"])
        for cell in m["workloads"]:
            assert cell in CELLS
            assert cell in moves.get("workloads", CELLS), (m["name"], cell)
    for e in B["end_to_end"]:
        assert e["source"] in ("host_clock", "device_trace")
        assert 0.01 <= e["bound"] <= 0.25
    assert any(e["name"] == "setup_s" for e in B["end_to_end"])


def test_names_and_units():
    names = [c["name"] for c in B["configs"]] + CELLS \
        + [m["name"] for m in B["end_to_end"] + B["per_layer"]] \
        + [w["traffic"] for w in B["workloads"]]
    for c in B["configs"]:
        names += c["reduced"]
    for n in names:
        assert NAME.match(n), n
    for kind in ("configs", "workloads", "end_to_end", "per_layer"):
        ns = [x["name"] for x in B[kind]]
        assert len(set(ns)) == len(ns)
    for m in B["end_to_end"] + B["per_layer"]:
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
    for m in B["per_layer"]:
        assert len(m["layer"]) <= 200 and "\n" not in m["layer"]
    for text in [w["why"] for w in B["workloads"]] \
            + [c["why"] for c in B["configs"]] + B["command"]:
        assert 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text
    assert len(json.dumps(B)) <= 64 * 1024


def test_peaks_are_keyed_by_device_kind():
    assert harness.peaks_for("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(harness.BenchError):
        harness.peaks_for("some other chip")


def test_readers_find_nothing_without_a_trace():
    class Run:
        trace_summary = None
        peak = None
    for name in ("device_idle.gen", "device_idle.serve", "mfu.gen",
                 "mfu.serve"):
        mod = harness.load_module(BENCH / "metrics" / f"{name}.py")
        assert mod.read(Run()) is None


def test_serve_arrivals_offer_the_same_load_for_every_seed():
    from bench.drivers import serve
    a, b = serve.arrivals(6.0, 30.0, 1), serve.arrivals(6.0, 30.0, 2**40 + 3)
    assert len(a) == len(b) == 180
    assert list(a) != list(b)
    assert all(0.0 <= t < 30.0 for t in list(a) + list(b))
    # the same gaps in another order (each run leaves out its last gap)
    gaps = lambda t: {round(g, 9) for g in (t[1:] - t[:-1]).tolist()}
    assert len(gaps(a) & gaps(b)) >= len(a) - 3
    assert math.isclose(sum(gaps(a)) / len(a), 1 / 6.0, rel_tol=0.1)


def test_run_gives_no_result_without_an_accelerator(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path))
    p = subprocess.run([sys.executable, str(BENCH / "run.py"), "--workload",
                        CELLS[0], "--seed", str(2**40 + 1), "--seconds", "1",
                        "--trace", "0"], env=env, cwd=tmp_path,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert '"correct"' not in p.stdout
