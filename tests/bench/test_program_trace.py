"""bench/program_trace.py and the five readers built on it: on hand-made
events, on the recorded chip trace without program spans
(data/spans.xplane.pb) and on a TPU v5e trace with them
(data/program_spans.xplane.pb.xz, xz-compressed, written by
bench/record_trace.py: one ``generate`` of a tiny DiT, m_base 6, and three
serving rounds of three requests in four slots)."""
import lzma
import os

import pytest

from bench import harness, program_trace

DATA = os.path.join(os.path.dirname(__file__), "data")
NO_SPANS = os.path.join(DATA, "spans.xplane.pb")
SPANS = os.path.join(DATA, "program_spans.xplane.pb.xz")
READERS = ("dispatches_per_image.gen", "exec_idle_ms.gen",
           "dispatches_per_round.serve", "engine_idle_ms.serve",
           "padded_row_share.serve")


class Run:
    def __init__(self, summary):
        self.program_trace = summary


def _read(name, run):
    return harness.load_module(
        harness.BENCH / "metrics" / f"{name}.py").read(run)


def _events():
    """A window of 1000 ns: one request (a model dispatch, then DDIM) and
    one round (a lane group of 3 real lanes and 1 pad, its model dispatch,
    then retirement); the device clock runs 10 ns ahead (the larger of the
    two enqueue-to-start gaps)."""
    return {
        "ops": {0: [(150, 290, "op"), (690, 790, "op")]},
        "programs": {0: [(150, 1), (700, 2)]},
        "enqueues": {(0, 1): 158, (0, 2): 710},
        "spans": [(0, 1000, "window", {}),
                  (100, 600, "stadi.generate", {}),
                  (150, 300, "exec.model", {"rows": 4}),
                  (300, 500, "exec.sampler", {}),
                  (650, 950, "engine.round", {}),
                  (680, 800, "exec.interval", {"lanes": 3, "padded": 1}),
                  (700, 800, "exec.model", {"rows": 2}),
                  (800, 900, "engine.retire", {})],
        "launches": [120, 160, 310, 320, 660, 710, 960],
    }


def test_outermost_launch_counts_each_call_once():
    assert program_trace._outermost([(30, 40), (10, 20), (11, 19),
                                     (12, 18)]) == [10, 30]


def test_hand_made_events():
    s = program_trace.summarize(_events())
    assert (s["shift_ns"], s["pairs"]) == (10, 2)
    # device busy [160, 300] and [700, 800] on the host's clock
    assert s["busy_s"] == pytest.approx(240e-9)
    assert s["idle_s"] == pytest.approx({
        "window": 200e-9,
        "stadi.generate/stadi.generate": 150e-9,
        "stadi.generate/exec.model": 10e-9,
        "stadi.generate/exec.sampler": 200e-9,
        "engine.round/engine.round": 80e-9,
        "engine.round/exec.interval": 20e-9,
        "engine.round/engine.retire": 100e-9})
    assert s["busy_s"] + sum(s["idle_s"].values()) \
        == pytest.approx(s["window_s"]) == pytest.approx(1000e-9)
    assert s["launches"] == {"stadi.generate/stadi.generate": 1,
                             "stadi.generate/exec.model": 1,
                             "stadi.generate/exec.sampler": 2,
                             "engine.round/engine.round": 1,
                             "engine.round/exec.model": 1, "window": 1}
    assert s["roots"] == {"stadi.generate": 1, "engine.round": 1}
    assert s["programs"] == (2, 0)
    run = Run(s)
    got = {name: _read(name, run) for name in READERS}
    assert got == pytest.approx({
        "dispatches_per_image.gen": 4.0,
        "exec_idle_ms.gen": 350e-6,
        "dispatches_per_round.serve": 2.0,
        "engine_idle_ms.serve": 100e-6,
        "padded_row_share.serve": 100.0 * 2 / 12})


def test_needs_one_window():
    ev = _events()
    ev["spans"] = ev["spans"][1:]
    with pytest.raises(ValueError):
        program_trace.summarize(ev)


def test_no_pairs_no_shift():
    ev = _events()
    ev["enqueues"] = {}
    assert program_trace.clock_shift(ev) == (0, 0)
    assert "not shifted" in program_trace.report(
        program_trace.summarize(ev))[0]


def _check_alignment(ev):
    shift, pairs = program_trace.clock_shift(ev)
    assert pairs > 0 and 0 < shift <= 5_000_000
    for device, progs in ev["programs"].items():
        for start, run_id in progs:
            t = ev["enqueues"].get((device, run_id))
            assert t is None or start + shift >= t
    return shift


def test_recorded_trace_without_program_spans():
    ev = program_trace.read(NO_SPANS)
    # device program run_id 60 starts 1.48 ms before its enqueue
    assert _check_alignment(ev) == pytest.approx(1.48e6, abs=0.01e6)
    s = program_trace.summarize(ev)
    assert s["roots"] == {} and s["rows"] == (0, 0)
    assert s["launches"] == {"window": 5} and s["programs"] == (5, 0)
    run = Run(s)
    assert all(_read(name, run) is None for name in READERS)


def test_recorded_trace_with_program_spans(tmp_path):
    assert os.path.getsize(SPANS) < 1 << 20
    path = tmp_path / "program_spans.xplane.pb"
    with lzma.open(SPANS) as f:
        path.write_bytes(f.read())
    ev = program_trace.read(str(path))
    _check_alignment(ev)
    s = program_trace.summarize(ev)
    assert s["roots"] == {"stadi.generate": 1, "engine.round": 3}
    assert s["busy_s"] + sum(s["idle_s"].values()) \
        == pytest.approx(s["window_s"])
    # every launch ran as one device program, all inside the window
    assert sum(s["launches"].values()) == s["programs"][0] == 1759
    assert s["launches"].get("window", 0) == 0
    run = Run(s)
    got = {name: _read(name, run) for name in READERS}
    # generate: 2 warm-up steps and 2 intervals of 3 substeps; the rounds:
    # 2 warm-up rounds, one adaptive
    assert got["dispatches_per_image.gen"] == 955
    assert got["dispatches_per_round.serve"] == 268
    assert 0 < got["engine_idle_ms.serve"] < got["exec_idle_ms.gen"]
    # engine lane groups hold 3 real lanes and 1 pad; generate's dispatches
    # one lane: padded rows 8 + 8 + 5 + 5 + 3 of 4 x 29 engine and 42
    # generate
    assert got["padded_row_share.serve"] == pytest.approx(100 * 29 / 158)
