"""Serving engine: share of the token rows the denoiser computed for lanes
that only pad a lane group to the slot count, in %: over the ``exec.model``
spans in the traced window, the sum of rows x padded lanes over the sum of
rows x (real + padded) lanes, the lanes counted by the engine's enclosing
``exec.warmup`` or ``exec.interval`` span of the lane group."""
from bench import program_trace


def read(run):
    return program_trace.padded_row_percent(run)
