"""Profiler spans inside the program.

``span(name, **counts)`` is a ``jax.profiler.TraceAnnotation``: while a
profiler trace is being taken it writes a host event named ``name`` whose
keyword arguments (host-side ints or strings) become the event's stats;
otherwise it costs about a microsecond and records nothing. The profiler
keeps the events in memory and writes them when the trace stops, so the
device trace and these spans share one file (``bench/program_trace.py``
reads them).

Call sites follow three rules: spans open on the host, never inside a
jitted function; their arguments are values the host already holds (never
a device value, which would wait for the device); and a span never moves a
dispatch, so results are bitwise the same with the profiler on or off.
The only arguments are those the benchmark reads: ``rows`` on
``exec.model`` (and ``ctx``, the context tokens of an MMDiT dispatch), and
``lanes`` and ``padded`` on the engine's ``exec.warmup`` and
``exec.interval``.
"""
from __future__ import annotations

import jax

SPANS = (
    "stadi.generate",    # one StadiPipeline.generate call, plan to image
    "stadi.plan",        # StadiPipeline.plan
    "exec.warmup",       # one synchronous warm-up fine step; in the engine
                         # of a lane group: lanes (real), padded (pad lanes)
    "exec.interval",     # one adaptive interval over all workers; in the
                         # engine of a lane group: lanes, padded
    "exec.model",        # one jitted denoiser dispatch; rows (token rows),
                         # MMDiT: ctx (context tokens)
    "exec.sampler",      # one DDIM or flow update (one compiled program)
    "exec.buffers",      # slab slices and write-backs, timestep reads, K/V
    "exec.exchange",     # an interval boundary: slab write-back, K/V merge
    "exec.record",       # the execution trace built at the end of a run
    "engine.round",      # one DiffusionServingEngine.step
    "engine.admit",      # queued requests placed into free slots
    "engine.lanes",      # lane-group gathers, pads and scatters of state
    "engine.cost_model",  # modeled round cost, profiler feed, replanning
    "engine.retire",     # wait for finished lanes and hand out their images
)


def span(name: str, **counts):
    """A profiler span named ``name`` (one of ``SPANS``) carrying
    ``counts`` as its arguments."""
    return jax.profiler.TraceAnnotation(name, **counts)
