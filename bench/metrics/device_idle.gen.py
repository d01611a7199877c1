"""Device: share of the traced window in which no operation ran on the
chip, in %: 1 - (union of device-op intervals / window), over the closed-loop generate
cells."""
from bench import trace_reduce


def read(run):
    return trace_reduce.idle_percent(run)
