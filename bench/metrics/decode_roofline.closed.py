"""Least time of the window's decode steps over the device time of the
``jit_decode`` module in the trace, in the closed-loop cells."""
from lib.readers import roofline


def read(record):
    return roofline(record, "decode", "closed")
