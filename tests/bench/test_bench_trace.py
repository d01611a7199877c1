"""bench/trace_reduce.py on hand-made intervals, and on a trace recorded on
a TPU v5e chip (data/spans.xplane.pb): inside the harness span ``window``,
three calls of a small jitted DiT forward with 2 ms host sleeps between
them in ``generate``, a 5 ms sleep in ``submit``, and two calls of a jitted
elementwise program with 1 ms sleeps in ``engine.step``."""
import os

import pytest

from bench import harness, trace_reduce

RECORDED = os.path.join(os.path.dirname(__file__), "data",
                        "spans.xplane.pb")


def test_busy_idle_and_gaps_by_span():
    ops = {"/device:TPU:0": [(10, 20, "dot.1"), (15, 30, "fusion.2"),
                             (50, 60, "dot.1"), (95, 120, "fusion.2")]}
    spans = [(0, 100, "window"), (5, 40, "generate"), (40, 100, "submit"),
             (45, 55, "plan")]
    r = trace_reduce.reduce_events(ops, spans)
    assert r["window_s"] == pytest.approx(100e-9)
    # union: [10, 30] + [50, 60] + [95, 100] (clipped to the window)
    assert r["busy_s"] == pytest.approx(35e-9)
    assert dict(r["device_ops"]) == pytest.approx(
        {"dot.1": 20e-9, "fusion.2": 20e-9})
    # gaps split by the innermost span: [0,5] in none, [5,10] generate,
    # [30,40] generate, [40,45] submit, [45,50] plan, [60,95] submit
    assert dict(r["idle_gaps"]) == pytest.approx(
        {"window": 5e-9, "generate": 15e-9, "submit": 40e-9, "plan": 5e-9})


def test_needs_one_window_and_some_device_work():
    with pytest.raises(ValueError):
        trace_reduce.reduce_events({"/device:TPU:0": [(0, 1, "a")]}, [])
    with pytest.raises(ValueError):
        trace_reduce.reduce_events({}, [(0, 10, "window")])


def test_recorded_chip_trace():
    names = harness.HARNESS_SPANS + tuple(
        n for d in ("generate", "serve")
        for n in harness.load_module(
            harness.BENCH / "drivers" / f"{d}.py").Driver.SPANS)
    ops, spans = trace_reduce.read_xplane(RECORDED, names)
    assert list(ops) == ["/device:TPU:0"]
    assert sorted(n for _, _, n in spans) == [
        "engine.step", "generate", "submit", "window"]
    r = trace_reduce.reduce_events(ops, spans)
    assert 0 < r["busy_s"] < r["window_s"]
    assert all(s > 0 for _, s in r["device_ops"] + r["idle_gaps"])
    assert all("/%" in n and not n.startswith("?") for n, _ in r["device_ops"])
    idle = dict(r["idle_gaps"])
    # the host slept 2 x 2 ms (+ the last) in generate, 5 ms in submit and
    # 2 x 1 ms in engine.step; the device idled at least that long there
    assert idle["submit"] >= 0.005 and idle["generate"] >= 0.006
    assert idle["engine.step"] >= 0.002
    assert r["busy_s"] + sum(idle.values()) == pytest.approx(r["window_s"])
