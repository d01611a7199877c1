"""Serving engine: programs launched per round, the launches (jitted calls
and eager operations) the host made inside ``engine.round`` spans in the
traced window over the number of those spans."""
from bench import program_trace


def read(run):
    return program_trace.launches_per(run, "engine.round")
