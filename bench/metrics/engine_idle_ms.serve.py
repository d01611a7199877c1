"""Serving engine: milliseconds per round in which the chip sat idle while
the host was inside ``engine.round`` but neither dispatching the denoiser
(``exec.model``) nor waiting for finished lanes (``engine.retire``), on the
device trace's clock aligned to the host's."""
from bench import program_trace


def read(run):
    return program_trace.idle_ms_per(run, "engine.round",
                                     skip=("exec.model", "engine.retire"))
