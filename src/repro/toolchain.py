"""What a compiled or timed value depends on besides its inputs: the
jax/jaxlib versions and the device.  Import-light on purpose (no jax at
import), so a remote worker's handshake can compare versions cheaply."""
from __future__ import annotations

from typing import Dict


def toolchain_versions() -> Dict[str, str]:
    """jax/jaxlib versions, or "unavailable" when not installed — the
    compiled-value salt: two toolchains may compile the same program to
    different latency/memory, so their values must never alias.  Read
    from the installed distributions' metadata: asking costs no import."""
    from importlib import metadata

    versions = {}
    for name in ("jax", "jaxlib"):
        try:
            versions[name] = metadata.version(name)
        except metadata.PackageNotFoundError:
            versions[name] = "unavailable"
    return versions


def device_identity(device=None) -> Dict[str, str]:
    """Platform and ``device_kind`` of ``device`` (default: the default
    backend's first device) — what a compiled or timed value was
    produced on.  Touches the backend, so only call it where the process
    already uses JAX."""
    if device is None:
        import jax

        device = jax.devices()[0]
    return {"platform": device.platform, "device_kind": device.device_kind}
