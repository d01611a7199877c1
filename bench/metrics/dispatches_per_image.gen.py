"""Executors: programs launched per image, the launches (jitted calls and
eager operations) the host made inside ``stadi.generate`` spans in the
traced window over the number of those spans."""
from bench import program_trace


def read(run):
    return program_trace.launches_per(run, "stadi.generate")
