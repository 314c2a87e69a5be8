"""Least time of the window's prefills (the larger of FLOPs over peak
FLOP/s and bytes over peak bytes/s, summed per prefill) over the device
time of the ``jit_prefill`` module in the trace, in the closed-loop cells."""
from lib.readers import roofline


def read(record):
    return roofline(record, "prefill", "closed")
