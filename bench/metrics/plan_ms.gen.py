"""Planner: milliseconds of one ``StadiPipeline.plan()`` at the cell's
config, the call ``generate`` makes each time. Timed over repeated calls
for at least a quarter of a second, inside the harness span ``plan``."""
import time

MIN_SPAN_S = 0.25


def read(run):
    pipe = run.driver.pipe
    n = 0
    with run.spans("plan"):
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < MIN_SPAN_S:
            pipe.plan()
            n += 1
        t1 = time.perf_counter()
    return (t1 - t0) / n * 1e3
