"""Serving engine: wall milliseconds per ``engine.step()`` round, the
harness's spans summed over the window's rounds and divided by their
count."""


def read(run):
    rounds = run.driver.round_s
    if not rounds:
        return None
    return sum(rounds) / len(rounds) * 1e3
