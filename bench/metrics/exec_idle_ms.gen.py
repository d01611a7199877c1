"""Executors: milliseconds per image in which the chip sat idle while the
host was inside ``stadi.generate`` but not dispatching the denoiser
(innermost program span other than ``exec.model``), on the device trace's
clock aligned to the host's."""
from bench import program_trace


def read(run):
    return program_trace.idle_ms_per(run, "stadi.generate",
                                     skip=("exec.model",))
