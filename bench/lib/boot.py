"""Process start of every benchmark script: the compile cache's one
place, and the chip check."""
from __future__ import annotations

import os
import sys

CACHE_DIR = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))), ".jax_cache")


def place_cache() -> None:
    """JAX's persistent compilation cache at ``.jax_cache/`` in the
    checkout: a fixed path, so later processes hit it.  Call before
    anything imports JAX, which reads these variables at import."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"


def tpu_devices(chips: int, what: str):
    """The TPU devices; exit 3 without a result where there are fewer
    than ``chips`` of them.  Never falls back to the CPU."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < chips:
        print(f"bench: {what} needs {chips} TPU chip(s); JAX found {len(devices)} "
              f"{devices[0].platform} device(s). No result.", file=sys.stderr, flush=True)
        raise SystemExit(3)
    return devices
