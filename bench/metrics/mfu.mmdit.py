"""Model step: useful FLOPs of the executed plan (``bench/flops_mmdit.py``:
image rows, the context stream in every evaluation, joint attention
against image and context keys) times the images the window completed,
over the window times the chip's bf16 peak, in %."""
from bench import flops_mmdit


def read(run):
    return flops_mmdit.mfu_percent(run)
