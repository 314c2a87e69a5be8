"""Arithmetic shared by the per-layer metric readers in
``bench/metrics/<metric>.py``.  Each reader takes the run's record (see
``lib.harness.layer_record``) and returns a number, or ``None`` where
the record holds nothing to read: then the metric is left out of the
line.  A share of a roofline or of a peak is never given as 0 for want
of data.
"""
from __future__ import annotations

from typing import Any, Dict, Optional

Record = Dict[str, Any]


def span_ms(rec: Record, span: str, loop: str) -> Optional[float]:
    """Mean milliseconds of a harness span in the window."""
    s = rec["spans"].get(span)
    if rec["loop"] != loop or not s or not s["count"]:
        return None
    return 1e3 * s["total_s"] / s["count"]


def roofline(rec: Record, kind: str, loop: str) -> Optional[float]:
    """Least time of the window's ``kind`` calls (prefill or decode)
    over the device time of their XLA module (``jit_<kind>``), in %."""
    trace, work = rec.get("trace"), rec["work"].get(kind)
    if rec["loop"] != loop or not trace or not work or not work["calls"]:
        return None
    device_s = trace["modules"].get(f"jit_{kind}", 0.0)
    if device_s <= 0:
        return None
    return 100.0 * work["least_s"] / device_s


def window_mfu(rec: Record, loop: str) -> Optional[float]:
    """Model FLOPs of every prompt and output token processed in the
    window, over the window times the peak, in %."""
    flops = sum(w["flops"] for w in rec["work"].values())
    if rec["loop"] != loop or flops <= 0:
        return None
    return 100.0 * flops / (rec["window_s"] * rec["peaks"]["flops_bf16"])
