"""Serving engine above capacity: serve_p90_s's arithmetic (due to ready,
an unfinished request counted from due to the run's stop). Recorded, not
judged: above the knee it swings with the backlog."""
from bench.drivers import serve


def read(run):
    return float(serve.p90(run.driver._latencies()))
