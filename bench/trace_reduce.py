"""Reduce a profiler trace (``*.xplane.pb``) to device busy time, the device
operations that took most time, and the device's idle gaps attributed to
the host span the harness was in.

Device planes are those named ``/device:<PLATFORM>:<n>``; their operations
are the events of the line ``XLA Ops``, named by the program of the line
``XLA Modules`` they ran in. Host spans are the
``jax.profiler.TraceAnnotation`` events on ``/host:CPU``. The traced window
is the host span ``window``; everything is clipped to it. An idle gap is
split over the spans the host was in during it. The device's clock in the
trace is not the host's to the millisecond (the recorded test trace shows
device operations starting about 1-3 ms before the host span that issued
them), so attributions finer than that mean nothing.
"""
from __future__ import annotations

import bisect
import re
from typing import Dict, List, Tuple

WINDOW_SPAN = "window"
_DEVICE_PLANE = re.compile(r"^/device:[A-Z]+:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def _merge(intervals: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[Tuple[int, int]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out


def _segments(spans: List[Tuple[int, int, str]]):
    """The host timeline as (start times, names): from ``times[k]`` to
    ``times[k + 1]`` the innermost open harness span is ``names[k]``.
    Spans nest (they are context managers on one thread), so a sweep over
    their edges with a stack gives the innermost span of every segment."""
    edges = sorted([(a, 1, i) for i, (a, _, _) in enumerate(spans)]
                   + [(b, 0, i) for i, (_, b, _) in enumerate(spans)])
    stack, times, names = [], [], []
    for t, opens, i in edges:
        if opens:
            stack.append(i)
        else:
            stack.remove(i)
        times.append(t)
        names.append(spans[stack[-1]][2] if stack else WINDOW_SPAN)
    return times, names


def _split(times, names, g0: int, g1: int, into: Dict[str, int]) -> None:
    """Add the interval [g0, g1] to ``into``, split by the span the host
    was in over each part of it."""
    k = bisect.bisect_right(times, g0) - 1
    t = g0
    while t < g1:
        end = times[k + 1] if k + 1 < len(times) else g1
        name = names[k] if k >= 0 else WINDOW_SPAN
        part = min(end, g1) - t
        if part > 0:
            into[name] = into.get(name, 0) + part
        t = max(t, min(end, g1))
        k += 1


def reduce_events(device_ops: Dict[str, List[Tuple[int, int, str]]],
                  host_spans: List[Tuple[int, int, str]], top: int = 10
                  ) -> dict:
    """``device_ops``: per device, (start_ns, end_ns, name) of each
    operation; ``host_spans``: (start_ns, end_ns, name) of harness spans,
    one of them ``window``. Returns busy_s (mean over devices), window_s,
    device_ops [[name, seconds]] and idle_gaps [[span, seconds]], each the
    ``top`` largest, the seconds summed over devices."""
    windows = [(a, b) for a, b, n in host_spans if n == WINDOW_SPAN]
    if len(windows) != 1:
        raise ValueError(f"expected one '{WINDOW_SPAN}' span, found "
                         f"{len(windows)}")
    w0, w1 = windows[0]
    times, names = _segments([s for s in host_spans if s[2] != WINDOW_SPAN])
    busy, per_op, per_span = [], {}, {}
    for events in device_ops.values():
        clipped = [(max(a, w0), min(b, w1), n) for a, b, n in events
                   if b > w0 and a < w1]
        for a, b, n in clipped:
            per_op[n] = per_op.get(n, 0) + (b - a)
        merged = _merge([(a, b) for a, b, _ in clipped])
        busy.append(sum(b - a for a, b in merged))
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        for g0, g1 in zip(edges[::2], edges[1::2]):
            _split(times, names, g0, g1, per_span)
    if not busy:
        raise ValueError("no device operations in the trace")
    rank = lambda d: [[k, v / 1e9] for k, v in
                      sorted(d.items(), key=lambda kv: -kv[1])[:top]]
    return {"busy_s": sum(busy) / len(busy) / 1e9, "window_s": (w1 - w0) / 1e9,
            "device_ops": rank(per_op), "idle_gaps": rank(per_span)}


def _op_names(modules, ops):
    """Name each operation ``<program>/<instruction>``: the XLA program
    (``XLA Modules`` event, its fingerprint dropped) that was running when
    it started, and the instruction's name before ``" = "`` in the HLO text
    the trace gives. Instruction names repeat across programs."""
    starts = [m[0] for m in modules]
    out = []
    for a, b, text in ops:
        k = bisect.bisect_right(starts, a) - 1
        prog = modules[k][2] if k >= 0 and a < modules[k][1] else "?"
        out.append((a, b, f"{prog}/{text.split(' = ', 1)[0]}"))
    return out


def read_xplane(path: str, span_names) -> tuple:
    """(device_ops, host_spans) from an xplane file, for ``reduce_events``."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    device_ops, spans = {}, []
    names = set(span_names) | {WINDOW_SPAN}
    for plane in data.planes:
        if _DEVICE_PLANE.match(plane.name):
            lines = {line.name: [(int(e.start_ns), int(e.end_ns), e.name)
                                 for e in line.events]
                     for line in plane.lines
                     if line.name in (OPS_LINE, MODULES_LINE)}
            modules = sorted((a, b, n.split("(", 1)[0])
                             for a, b, n in lines.get(MODULES_LINE, []))
            if lines.get(OPS_LINE):
                device_ops[plane.name] = _op_names(modules, lines[OPS_LINE])
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                spans += [(int(e.start_ns), int(e.end_ns), e.name)
                          for e in line.events if e.name in names]
    return device_ops, spans


def reduce_xplane(path: str, span_names) -> dict:
    return reduce_events(*read_xplane(path, span_names))


def idle_percent(run):
    """Share of the traced window in which no operation ran on the chip,
    in %: 1 - (union of device-op intervals / window). None untraced."""
    t = run.trace_summary
    if not t or t["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
