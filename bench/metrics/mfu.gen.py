"""Model step: useful FLOPs of the executed plan times the images window completed,
over the window times the chip's bf16 peak, in %."""
from bench import flops


def read(run):
    return flops.mfu_percent(run)
