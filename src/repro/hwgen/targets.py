"""Hardware target specifications (the TPU analogue of the paper's
Raspberry Pi / Pico / FPGA backend descriptors).

A TargetSpec bundles chip constants (for the roofline cost model) with a
mesh recipe and backend capabilities (for the reflection API, paper §VI).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional, Tuple


@dataclasses.dataclass(frozen=True)
class ChipSpec:
    name: str
    peak_flops_bf16: float  # FLOP/s
    hbm_bandwidth: float  # B/s
    ici_bandwidth: float  # B/s per link
    hbm_bytes: int
    vmem_bytes: int = 128 * 1024 * 1024


TPU_V5E = ChipSpec(
    name="tpu_v5e",
    peak_flops_bf16=197e12,
    hbm_bandwidth=819e9,
    ici_bandwidth=50e9,
    hbm_bytes=16 * 1024 ** 3,
)

HOST_CPU = ChipSpec(
    name="host_cpu",
    peak_flops_bf16=1e11,  # nominal; host backend measures wall-clock instead
    hbm_bandwidth=20e9,
    ici_bandwidth=1e9,
    hbm_bytes=32 * 1024 ** 3,
)

# Edge-class accelerator (the paper's Raspberry-Pi/Pico deployment tier):
# a single-chip NPU with modest compute but *proportionally* even less
# memory bandwidth than the datacenter parts — its roofline crosses over
# at a much higher arithmetic intensity, so architectures that win on
# tpu_v5e (compute-bound) can lose here (bandwidth-bound).  That
# asymmetry is what makes cross-target sweep comparisons informative.
EDGE_NPU = ChipSpec(
    name="edge_npu",
    peak_flops_bf16=4e12,
    hbm_bandwidth=34e9,
    ici_bandwidth=0.25e9,
    hbm_bytes=8 * 1024 ** 3,
    vmem_bytes=8 * 1024 * 1024,
)


@dataclasses.dataclass(frozen=True)
class TargetSpec:
    name: str
    chip: ChipSpec
    mesh_shape: Tuple[int, ...]
    mesh_axes: Tuple[str, ...]
    # reflection API (paper §VI): capability set consulted by the
    # ModelBuilder so only backend-supported ops are sampled
    supported_ops: frozenset = frozenset()
    supports_pallas: bool = False
    measurement: str = "roofline"  # "roofline" | "wallclock"
    # wallclock targets time a real device: the JAX platform whose
    # devices they place on and whose clock they read ("cpu" for
    # host_cpu).  Roofline targets model their chip and compile on the
    # default backend.
    platform: Optional[str] = None

    def __post_init__(self):
        if self.measurement == "wallclock" and not self.platform:
            raise ValueError(
                f"target {self.name!r}: a wallclock target must name the "
                f"platform it measures on")

    @property
    def n_chips(self) -> int:
        n = 1
        for s in self.mesh_shape:
            n *= s
        return n

    @property
    def mesh_scope(self) -> str:
        """Identity of the *compiled program* this target produces.

        Two targets sharing a mesh topology compile byte-identical
        executables — chip constants only enter the roofline arithmetic
        afterwards — so compile-derived cache entries are scoped by this
        string instead of the target name, letting cross-target sweeps
        reuse each other's compiles (see ``_CompiledEstimator``).
        """
        return ("mesh:" + "x".join(str(s) for s in self.mesh_shape)
                + ":" + ",".join(self.mesh_axes))

    def to_dict(self) -> Dict[str, Any]:
        """JSON form with the full chip constants, persisted into
        ``ExplorationReport``/``SweepReport`` so a report stays
        interpretable even after a target's registered constants are
        edited (the numbers that produced it travel with it)."""
        return {
            "name": self.name,
            "chip": dataclasses.asdict(self.chip),
            "mesh_shape": list(self.mesh_shape),
            "mesh_axes": list(self.mesh_axes),
            "n_chips": self.n_chips,
            "supported_ops": sorted(self.supported_ops),
            "supports_pallas": self.supports_pallas,
            "measurement": self.measurement,
            "platform": self.platform,
        }


_COMMON_OPS = frozenset({
    "linear", "conv1d", "maxpool", "avgpool", "identity", "global_avg_pool",
    "layernorm", "attention", "ssm",
})

TARGETS: Dict[str, TargetSpec] = {
    # single-chip tpu_v5e: the datacenter chip constants on a mesh any
    # host can compile for (the pod targets need 256+ spoofed devices) —
    # what cross-target sweeps compare against host_cpu/edge_npu
    "tpu_v5e": TargetSpec(
        name="tpu_v5e", chip=TPU_V5E,
        mesh_shape=(1, 1), mesh_axes=("data", "model"),
        supported_ops=_COMMON_OPS, supports_pallas=True,
        measurement="roofline",
    ),
    "tpu_v5e_pod": TargetSpec(
        name="tpu_v5e_pod", chip=TPU_V5E,
        mesh_shape=(16, 16), mesh_axes=("data", "model"),
        supported_ops=_COMMON_OPS, supports_pallas=True,
        measurement="roofline",
    ),
    "tpu_v5e_2pod": TargetSpec(
        name="tpu_v5e_2pod", chip=TPU_V5E,
        mesh_shape=(2, 16, 16), mesh_axes=("pod", "data", "model"),
        supported_ops=_COMMON_OPS, supports_pallas=True,
        measurement="roofline",
    ),
    "host_cpu": TargetSpec(
        name="host_cpu", chip=HOST_CPU,
        mesh_shape=(1, 1), mesh_axes=("data", "model"),
        supported_ops=_COMMON_OPS, supports_pallas=False,
        measurement="wallclock", platform="cpu",
    ),
    # single-chip edge deployment tier: same mesh topology as host_cpu
    # (so sweeps reuse its compiles) but roofline-measured against the
    # EDGE_NPU constants — latency/memory trade-offs rank differently
    # than on either datacenter target
    "edge_npu": TargetSpec(
        name="edge_npu", chip=EDGE_NPU,
        mesh_shape=(1, 1), mesh_axes=("data", "model"),
        supported_ops=_COMMON_OPS, supports_pallas=False,
        measurement="roofline",
    ),
}


def get_target(name: str) -> TargetSpec:
    if name not in TARGETS:
        raise KeyError(f"unknown target {name!r}; available: {sorted(TARGETS)}")
    return TARGETS[name]


# Publish the built-in targets to the Explorer facade's registry so YAML
# experiments can name them; plugin targets register the same way
# (``register("target", "my_board", spec)``) without touching this dict.
from repro.explorer.registry import TARGETS as _EXPLORER_TARGETS  # noqa: E402

for _name, _spec in TARGETS.items():
    _EXPLORER_TARGETS.register(_name, _spec)
del _name, _spec
