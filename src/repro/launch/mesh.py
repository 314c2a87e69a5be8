"""Production mesh builders.

``make_production_mesh`` is a FUNCTION (importing this module never
touches jax device state).  Single-pod: (16, 16) = 256 chips over
("data", "model"); multi-pod: (2, 16, 16) = 512 chips over
("pod", "data", "model").  The dry-run spoofs 512 host devices via
XLA_FLAGS (set in dryrun.py before any jax import); on real hardware the
same code paths see actual TPU devices.
"""
from __future__ import annotations

import math
from typing import Optional, Sequence

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = math.prod(shape)
    devices = jax.devices()
    if len(devices) < n:
        raise RuntimeError(
            f"need {n} devices for mesh {shape}, have {len(devices)} — "
            "run under XLA_FLAGS=--xla_force_host_platform_device_count=512 "
            "(the dry-run launcher sets this automatically)"
        )
    return jax.make_mesh(shape, axes, devices=devices[:n],
                         axis_types=(AxisType.Auto,) * len(shape))


def make_mesh(shape: Sequence[int], axes: Sequence[str], devices=None):
    """General mesh helper for tests / small meshes / elastic re-meshing."""
    n = math.prod(shape)
    devices = devices if devices is not None else jax.devices()
    if len(devices) < n:
        raise RuntimeError(f"need {n} devices, have {len(devices)}")
    return jax.make_mesh(tuple(shape), tuple(axes), devices=devices[:n],
                         axis_types=(AxisType.Auto,) * len(shape))


def make_host_mesh():
    """1-device mesh for smoke tests and host-backend NAS measurement."""
    return jax.make_mesh((1, 1), ("data", "model"), devices=jax.devices()[:1],
                         axis_types=(AxisType.Auto,) * 2)
