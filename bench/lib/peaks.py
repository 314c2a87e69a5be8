"""Published peaks per chip, keyed by JAX's ``device_kind``.

TPU v5e (``TPU v5 lite``): Google Cloud documentation, "TPU v5e":
197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s.  The same
values as ``repro.hwgen.targets.TPU_V5E``, which keys them by target
name instead.  A device that is not listed is an error, not a default.
"""
from __future__ import annotations

from typing import Dict

PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {
        "flops_bf16": 197e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, TPU v5e",
    },
}


def peaks(device_kind: str) -> Dict[str, float]:
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}")
    return PEAKS[device_kind]
