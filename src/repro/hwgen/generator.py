"""Generator pipeline (paper §VI): model instance -> deployable artifact.

The paper's generators emit TorchScript/LiteRT/VHDL and drive Docker
cross-compilation; the TPU-native equivalent lowers a jitted + sharded
step function and AOT-compiles it for the target mesh (the
``--xla_force_host_platform_device_count`` trick is our cross-compilation
toolchain: building a 512-chip executable on a 1-CPU host).

Two usage modes, mirroring the paper:
  1. deploy-best: generate once for the final architecture;
  2. hardware-in-the-loop: a cost estimator generates + benchmarks every
     candidate and feeds the measurement back into the study.

``HardwareManager.benchmark`` measures wall-clock on a ``wallclock``
target's own platform (``host_cpu`` places on and times CPU devices,
even in a process whose default backend is a TPU) and returns the
roofline-modelled step time for roofline targets.
"""
from __future__ import annotations

import dataclasses
import os
import threading
import time
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import numpy as np

from repro import faults
from repro.envvars import read_env
from repro.hwgen.hlo_analysis import parse_collectives, total_collective_bytes
from repro.hwgen.roofline import RooflineReport, roofline_terms
from repro.hwgen.targets import TargetSpec, get_target
from repro.kernels import schedule as ksched
from repro.launch.mesh import make_mesh


@dataclasses.dataclass
class Artifact:
    """A compiled, deployable executable + its static analysis."""

    target: TargetSpec
    compiled: Any
    flops: float
    bytes_accessed: float
    collective_bytes: float
    memory: Dict[str, int]
    roofline: RooflineReport
    example_args: Tuple = ()
    # the *effective* kernel schedules this executable was built with,
    # keyed by kernel name (None = program used no schedulable kernels)
    schedules: Optional[Dict[str, Dict[str, Any]]] = None

    @property
    def fits_memory(self) -> bool:
        peak = self.memory.get("peak_bytes_per_device")
        return peak is not None and peak <= self.target.chip.hbm_bytes


class GeneratorError(RuntimeError):
    pass


def _compile_limit() -> int:
    """Max concurrent XLA compilations (admission control).

    XLA's compiler uses its own internal thread pool, so letting every
    ParallelStudy worker compile simultaneously oversubscribes the host
    and makes *each* compile slower than running them back to back
    (measured 0.68x aggregate on a 2-core container).  Serializing
    compilation while workers overlap tracing, init and benchmarking
    turns that thrash into a pipeline.  Override with
    ``REPRO_COMPILE_CONCURRENCY`` (declared in :mod:`repro.envvars`; a
    malformed value warns and falls back rather than exploding at first
    compile deep inside a worker thread).
    """
    return read_env("REPRO_COMPILE_CONCURRENCY",
                    max(1, (os.cpu_count() or 2) // 2))


_gate_init_lock = threading.Lock()
_gate: Optional[threading.BoundedSemaphore] = None

_generate_count_lock = threading.Lock()
_generate_count = 0


def generate_call_count() -> int:
    """Process-local count of :meth:`XLAGenerator.generate` invocations
    (i.e. actual XLA compilations).  Warm-restart tests and benchmarks
    assert this stays flat when every value comes from the disk cache."""
    return _generate_count


def compile_gate() -> threading.BoundedSemaphore:
    """The shared admission-control semaphore, created on first use (not
    at import) so ``REPRO_COMPILE_CONCURRENCY`` set any time before the
    first generate/benchmark takes effect."""
    global _gate
    if _gate is None:
        with _gate_init_lock:
            if _gate is None:
                _gate = threading.BoundedSemaphore(_compile_limit())
    return _gate


def target_devices(target: TargetSpec):
    """Devices a target compiles for: its named platform's (a wallclock
    target never measures one platform under another's name), else the
    default backend's."""
    try:
        return jax.devices(target.platform) if target.platform else jax.devices()
    except RuntimeError as e:
        raise GeneratorError(
            f"target {target.name} measures on platform "
            f"{target.platform!r}, which this process cannot reach: {e}"
        ) from e


class XLAGenerator:
    """Translates model instances into target-specific XLA executables."""

    def __init__(self, target: TargetSpec | str):
        self.target = get_target(target) if isinstance(target, str) else target

    # -- reflection API (paper §VI) -----------------------------------------

    def supported_ops(self) -> frozenset:
        return self.target.supported_ops

    def capabilities(self) -> Dict[str, Any]:
        return {
            "ops": sorted(self.target.supported_ops),
            "pallas": self.target.supports_pallas,
            "chips": self.target.n_chips,
            "hbm_bytes": self.target.chip.hbm_bytes,
            "measurement": self.target.measurement,
        }

    # -- generation -----------------------------------------------------------

    def _mesh(self):
        devices = target_devices(self.target)
        try:
            return make_mesh(self.target.mesh_shape, self.target.mesh_axes,
                             devices=devices)
        except RuntimeError as e:
            raise GeneratorError(
                f"target {self.target.name} needs {self.target.n_chips} devices: {e}"
            ) from e

    def generate_cached(self, cache, key, fn: Callable, example_args: Tuple, **kw) -> Artifact:
        """Memoized :meth:`generate` through a shared
        :class:`~repro.evaluation.cache.EvaluationCache`: estimators that
        need the same candidate's artifact (latency + memory) compile it
        once; concurrent workers racing on one key compile it once too
        (single-flight)."""
        return cache.get_or_compute(key, lambda: self.generate(fn, example_args, **kw))

    def generate(
        self,
        fn: Callable,
        example_args: Tuple,
        in_shardings=None,
        out_shardings=None,
        static_argnums=(),
        schedules=None,
    ) -> Artifact:
        """``schedules`` maps kernel name -> :class:`KernelSchedule` (or a
        field mapping); it is made active for the trace so every Pallas
        kernel the program reaches launches with the tuned parameters,
        and the artifact records the *effective* (shape-clamped)
        schedules it was actually built with."""
        global _generate_count
        with _generate_count_lock:
            _generate_count += 1
        # chaos seam: a `raise` here models an XLA/toolchain crash on one
        # candidate, a `delay` models a pathological compile
        faults.fault_point("compile", key=self.target.name)
        mesh = self._mesh()
        # Admission control around the whole generate pipeline: tracing is
        # GIL-bound Python, XLA compilation oversubscribes its internal
        # pool, and the post-compile HLO analysis is GIL-bound text
        # parsing — all of them contend when every ParallelStudy worker
        # runs them at once (measured 0.68x aggregate for concurrent
        # compiles on a 2-core container).  Gating them pipelines the
        # workers; what overlaps is everything else: model build/init and
        # cache hits (wall-clock measurement takes the same gate — see
        # HardwareManager.benchmark).
        kernel_calls: Dict[Tuple[str, str], Dict[str, Any]] = {}
        with compile_gate():
            with mesh, jax.default_device(mesh.devices.flat[0]):
                jitted = jax.jit(
                    fn,
                    in_shardings=in_shardings,
                    out_shardings=out_shardings,
                    static_argnums=static_argnums,
                )
                # schedules bind at trace time (the kernel resolvers run
                # in Python during lowering), and the recorder captures
                # what each call actually launched with
                with ksched.use_schedules(schedules), \
                        ksched.record_kernel_calls(kernel_calls):
                    lowered = jitted.lower(*example_args)
                compiled = lowered.compile()
            ca = compiled.cost_analysis() or {}
            flops = float(ca.get("flops", 0.0))
            bytes_accessed = float(ca.get("bytes accessed", 0.0))
            coll = total_collective_bytes(parse_collectives(compiled.as_text()))
            try:
                ma = compiled.memory_analysis()
                memory = {
                    "argument_bytes": int(ma.argument_size_in_bytes),
                    "output_bytes": int(ma.output_size_in_bytes),
                    "temp_bytes": int(ma.temp_size_in_bytes),
                    "peak_bytes_per_device": int(
                        ma.argument_size_in_bytes + ma.output_size_in_bytes
                        + ma.temp_size_in_bytes - ma.alias_size_in_bytes
                    ),
                }
            except Exception:
                memory = {}
        roofline = roofline_terms(
            hlo_flops=flops,
            hlo_bytes=bytes_accessed,
            collective_bytes=coll,
            n_chips=1,  # per-device program quantities
            chip=self.target.chip,
        )
        built_with = {
            entry["kernel"]: entry["effective"].to_dict()
            for entry in kernel_calls.values()
        } or None
        return Artifact(
            target=self.target,
            compiled=compiled,
            flops=flops,
            bytes_accessed=bytes_accessed,
            collective_bytes=coll,
            memory=memory,
            roofline=roofline,
            example_args=example_args,
            schedules=built_with,
        )


class HardwareManager:
    """Deploys artifacts and extracts cost metrics (paper §VI).

    On measurement="wallclock" targets, executes the compiled binary with
    real inputs and times it (true hardware-in-the-loop in this
    container); on roofline targets, returns the modelled step time.
    """

    def __init__(self, warmup: int = 2, iters: int = 10):
        self.warmup = warmup
        self.iters = iters

    def benchmark(self, artifact: Artifact, concrete_args: Optional[Tuple] = None) -> Dict[str, float]:
        if artifact.target.measurement == "roofline":
            r = artifact.roofline
            return {
                "latency_s": r.bound_s,
                "compute_s": r.compute_s,
                "memory_s": r.memory_s,
                "collective_s": r.collective_s,
                "measured": 0.0,
            }
        args = concrete_args
        if args is None:
            args = tuple(
                jax.tree_util.tree_map(
                    lambda s: np.zeros(s.shape, s.dtype)
                    if hasattr(s, "shape") else s,
                    a,
                )
                for a in artifact.example_args
            )
        if artifact.target.platform:
            args = jax.device_put(args, target_devices(artifact.target)[0])
        fn = artifact.compiled
        # Wall-clock measurement must not overlap sibling workers' XLA
        # compiles (or other measurements) — a timing taken during a
        # neighbour's compile reports scheduler contention, not the
        # architecture's latency, and the evaluation cache would freeze
        # that corrupted number.  Take the same admission gate.
        with compile_gate():
            for _ in range(self.warmup):
                out = fn(*args)
            jax.block_until_ready(out)
            t0 = time.perf_counter()
            for _ in range(self.iters):
                out = fn(*args)
            jax.block_until_ready(out)
            dt = (time.perf_counter() - t0) / self.iters
        return {"latency_s": dt, "measured": 1.0}
