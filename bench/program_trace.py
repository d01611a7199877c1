"""The program's own spans (``repro.obs``) on the device trace's clock.

A traced run's xplane holds the program's spans and the JAX launches on the
host's clock, and the device's operations on the device's clock, which
runs 1-3 ms ahead of it (the recorded chip trace shows device programs
starting before the host enqueued them). The trace carries its own
correction: the host's ``DoEnqueueProgram`` event and the device's
``XLA Modules`` event of one program execution share a ``run_id``. No
program starts before it is enqueued, so the device timeline is shifted by
the largest (enqueue start - module start) over the pairs; with no pair
the shift is 0.

On that clock, inside the harness span ``window``, each device idle gap is
attributed to the innermost program span the host was in, and each launch
to the innermost program span it was made in. A launch is one call of a
jitted function or eager operation: a ``PjitFunction(...)`` event on the
Python thread, counted once where the trace nests a second one inside it.
Every key is ``<root>/<span>``: the outermost program span (the request,
``stadi.generate``, or the round, ``engine.round``) and the innermost one;
time and launches outside every program span go under ``window``.

A program without ``repro.obs`` writes no program spans; then every reader
of this module returns None.
"""
from __future__ import annotations

import bisect
import sys
import time
from collections import Counter
from typing import Dict, List, Tuple

from bench import trace_reduce

WINDOW = trace_reduce.WINDOW_SPAN
LAUNCH = "PjitFunction"
ENQUEUE = "DoEnqueueProgram"
HOST_PLANE = "/host:CPU"


def span_names() -> Tuple[str, ...]:
    try:
        from repro import obs
    except ImportError:                  # a program that writes no spans
        return ()
    return tuple(obs.SPANS)


def _outermost(events: List[Tuple[int, int]]) -> List[int]:
    """Start times of the events no other event of the list encloses."""
    starts, end = [], None
    for a, b in sorted(events):
        if end is None or a >= end:
            starts.append(a)
            end = b
        else:
            end = max(end, b)
    return starts


def read(path: str) -> dict:
    """The events the summary needs, from one xplane file: per device the
    operations [(start, end, name)] and programs [(start, run_id)]; the
    host's enqueues {(device, run_id): start}; and, from the Python thread
    that holds the ``window`` span, the program spans [(start, end, name,
    args)] (``window`` among them) and the launch start times."""
    from jax.profiler import ProfileData
    names = set(span_names()) | {WINDOW}
    ops, programs, enqueues = {}, {}, {}
    spans, launches = [], []
    for plane in ProfileData.from_file(path).planes:
        if trace_reduce._DEVICE_PLANE.match(plane.name):
            device = int(plane.name.rsplit(":", 1)[1])
            for line in plane.lines:
                if line.name == trace_reduce.OPS_LINE:
                    ops[device] = [(int(e.start_ns), int(e.end_ns), "op")
                                   for e in line.events]
                elif line.name == trace_reduce.MODULES_LINE:
                    programs[device] = [
                        (int(e.start_ns), dict(e.stats).get("run_id"))
                        for e in line.events]
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                mine, calls = [], []
                for e in line.events:
                    name = e.name
                    if name in names:
                        mine.append((int(e.start_ns), int(e.end_ns), name,
                                     dict(e.stats)))
                    elif name.startswith(LAUNCH):
                        calls.append((int(e.start_ns), int(e.end_ns)))
                    elif name == ENQUEUE:
                        st = dict(e.stats)
                        enqueues[(st.get("device_ordinal", 0),
                                  st.get("run_id"))] = int(e.start_ns)
                if any(s[2] == WINDOW for s in mine):
                    spans, launches = mine, _outermost(calls)
    return {"ops": ops, "programs": programs, "enqueues": enqueues,
            "spans": spans, "launches": launches}


def clock_shift(events: dict) -> Tuple[int, int]:
    """(ns to add to device times, number of enqueue/program pairs)."""
    gaps = [t - a for device, progs in events["programs"].items()
            for a, run_id in progs
            for t in [events["enqueues"].get((device, run_id))]
            if t is not None]
    return (max(gaps) if gaps else 0), len(gaps)


def _rooted(spans) -> List[Tuple[int, int, str]]:
    """Each program span renamed ``<outermost>/<own name>``."""
    out, stack = [], []
    for a, b, name, _ in sorted(spans, key=lambda s: (s[0], -s[1])):
        while stack and stack[-1][1] <= a:
            stack.pop()
        root = stack[0][2] if stack else name
        stack.append((a, b, root))
        out.append((a, b, f"{root}/{name}"))
    return out


def _lane_rows(spans) -> Tuple[int, int]:
    """(padded, all) lane rows of the ``exec.model`` spans: each span's
    ``rows`` times the ``padded`` and the ``lanes`` + ``padded`` lanes of
    the innermost enclosing span that counts lanes (the engine's lane
    group); one real lane where no span encloses it (``generate``)."""
    padded = total = 0
    stack = []                           # (end, args) of enclosing spans
    for a, b, name, args in sorted(spans, key=lambda s: (s[0], -s[1])):
        while stack and stack[-1][0] <= a:
            stack.pop()
        if name == "exec.model":
            group = next((g for _, g in reversed(stack) if "lanes" in g),
                         {"lanes": 1, "padded": 0})
            rows = args.get("rows", 0)
            padded += group["padded"] * rows
            total += (group["lanes"] + group["padded"]) * rows
        stack.append((b, args))
    return padded, total


def summarize(events: dict) -> dict:
    """Clock shift, per-key device idle seconds and launches, the number of
    each root span, device programs against launches, and the padded rows
    of ``exec.model`` spans, all inside the one ``window`` span."""
    windows = [(a, b) for a, b, n, _ in events["spans"] if n == WINDOW]
    if len(windows) != 1:
        raise ValueError(f"expected one '{WINDOW}' span, found "
                         f"{len(windows)}")
    w0, w1 = windows[0]
    shift, pairs = clock_shift(events)
    ops = {d: [(a + shift, b + shift, n) for a, b, n in v]
           for d, v in events["ops"].items()}
    spans = [s for s in events["spans"] if s[2] != WINDOW and w0 <= s[0] < w1]
    rooted = _rooted(spans)
    reduced = trace_reduce.reduce_events(
        ops, rooted + [(w0, w1, WINDOW)], top=len(rooted) + 1)
    times, keys = trace_reduce._segments(rooted)
    launches: Dict[str, int] = {}
    for t in events["launches"]:
        if w0 <= t < w1:
            k = bisect.bisect_right(times, t) - 1
            key = keys[k] if k >= 0 else WINDOW
            launches[key] = launches.get(key, 0) + 1
    roots = Counter(n.split("/")[0] for _, _, n in rooted
                    if n.split("/")[0] == n.split("/")[1])
    starts = [a + shift for progs in events["programs"].values()
              for a, _ in progs if a + shift >= w0]
    return {"shift_ns": shift, "pairs": pairs, "window_s": (w1 - w0) / 1e9,
            "busy_s": reduced["busy_s"], "idle_s": dict(reduced["idle_gaps"]),
            "launches": launches,
            "roots": dict(roots),
            "programs": (sum(1 for a in starts if a < w1),
                         sum(1 for a in starts if a >= w1)),
            "rows": _lane_rows(spans)}


def report(s: dict) -> List[str]:
    """The ``[bench]`` lines of a summary: the shift, the per-key table
    (most idle first) and launches against device programs."""
    if s["pairs"]:
        lines = [f"program trace: device clock shifted by "
                 f"{s['shift_ns'] / 1e6:+.3f} ms, the largest enqueue-to-"
                 f"start gap of {s['pairs']} enqueue/program pairs"]
    else:
        lines = ["program trace: no enqueue/program pairs in the trace, so "
                 "the device clock is not shifted"]
    idle, launches = s["idle_s"], s["launches"]
    for k in sorted(set(idle) | set(launches),
                    key=lambda k: -idle.get(k, 0.0)):
        lines.append(f"span {k}: device idle {idle.get(k, 0.0) * 1e3:.3f} "
                     f"ms, {launches.get(k, 0)} launches")
    inside = sum(v for k, v in s["launches"].items() if k != WINDOW)
    outside = s["launches"].get(WINDOW, 0)
    lines.append(f"launches: {inside} in program spans + {outside} outside "
                 f"= {inside + outside}; device programs: {s['programs'][0]} "
                 f"in the window + {s['programs'][1]} after its close")
    return lines


def summary(run) -> dict:
    """The summary of a traced run's newest xplane, read once and kept on
    the run; its ``[bench]`` lines go to standard error."""
    if getattr(run, "program_trace", None) is None:
        from bench import harness
        t0 = time.perf_counter()
        run.program_trace = summarize(read(harness._newest_xplane()))
        lines = report(run.program_trace)
        lines[0] += f" (read in {time.perf_counter() - t0:.1f}s)"
        for line in lines:
            print(f"[bench] {line}", file=sys.stderr, flush=True)
    return run.program_trace


def _per_root(s: dict, table: str, root: str, skip=()) -> float:
    return sum(v for k, v in s[table].items()
               if k.split("/")[0] == root and k.split("/")[1] not in skip)


def launches_per(run, root: str):
    """Launches inside ``root`` spans, over their number; None without."""
    s = summary(run)
    n = s["roots"].get(root, 0)
    return _per_root(s, "launches", root) / n if n else None


def idle_ms_per(run, root: str, skip=()):
    """Aligned device idle inside ``root`` spans whose innermost span is
    not in ``skip``, in ms over their number; None without."""
    s = summary(run)
    n = s["roots"].get(root, 0)
    return _per_root(s, "idle_s", root, skip) * 1e3 / n if n else None


def padded_row_percent(run):
    """Padded lane rows over all lane rows of ``exec.model`` dispatches, in
    %; None without such spans."""
    padded, total = summary(run)["rows"]
    return 100.0 * padded / total if total else None
