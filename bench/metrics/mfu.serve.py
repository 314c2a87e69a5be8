"""Model FLOPs of every prompt and output token processed in the window,
over the window times the chip's bf16 peak, in the closed-loop cells
(where the load does not fix it)."""
from lib.readers import window_mfu


def read(record):
    return window_mfu(record, "closed")
