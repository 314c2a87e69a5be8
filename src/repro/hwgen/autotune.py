"""Per-target kernel schedule autotuning.

The paper's generators emit target-specific code; this is the jax_pallas
analogue for the *kernel mapping*: the same candidate architecture gets
its Pallas block/chunk parameters tuned per target and cached next to
its compiled artifacts.  :class:`ScheduleTuner` sweeps the small
candidate grid in :data:`repro.kernels.schedule.CANDIDATE_SCHEDULES` on
synthetic inputs at the call's real shapes, times each candidate under
the shared compile admission gate, and memoizes the winner in the
(optionally disk-backed) evaluation cache keyed by
``(kernel, shape_bucket, mesh_scope)`` — so a warm restart re-tunes
nothing, and same-topology targets share tuned schedules exactly like
they share compiled artifacts.  The disk tier salts every key with the
platform and ``device_kind`` the process runs on, so a winner timed on
the CPU (the Pallas interpreter) never answers on a TPU; each record
also names the device it was timed on.

Shape buckets round every dimension up to the next power of two and fold
in the masking flags, so nearby shapes (which want the same blocking)
share one sweep instead of each paying their own.

Records are plain JSON dicts on purpose: the flock-safe disk cache
persists JSON-able values only, and the ``schedule`` field holds the
*requested* (validated, power-of-two) winner — re-loadable via
``as_schedule`` — while ``effective`` documents what that request
clamped to at the swept shapes.
"""
from __future__ import annotations

import threading
import time
from typing import Any, Callable, Dict, Mapping, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.envvars import read_env
from repro.hwgen.generator import compile_gate, target_devices
from repro.kernels import ops as kops
from repro.kernels import schedule as ksched
from repro.kernels.schedule import KernelSchedule
from repro.toolchain import device_identity

# the documented default of REPRO_TUNE_BUDGET (covers every built-in grid)
DEFAULT_BUDGET = 8

KernelCalls = Dict[Tuple[str, str], Dict[str, Any]]


def discover_kernel_calls(fn: Callable, example_args: Tuple) -> KernelCalls:
    """Which schedulable kernels does ``fn`` reach, at what shapes?

    Runs ``jax.eval_shape`` under the call recorder — an abstract trace,
    no compile, so discovery costs milliseconds even for programs whose
    compilation takes seconds."""
    sink: KernelCalls = {}
    with ksched.record_kernel_calls(sink):
        jax.eval_shape(fn, *example_args)
    return sink


def _pow2_ceil(n: int) -> int:
    p = 1
    while p < n:
        p <<= 1
    return p


class ScheduleTuner:
    """Sweeps schedule candidates per (kernel, shape-bucket, target).

    ``budget`` (explicit spec value, else ``REPRO_TUNE_BUDGET``) caps how
    many candidates each sweep times; grids are default-first, so budget
    1 degenerates to the named default.  ``overrides`` pins kernels to a
    fixed schedule — pinned kernels are never swept.  Thread-safe: the
    cache provides single-flight per key, the stats counter has its own
    lock.
    """

    def __init__(self, target, cache=None, budget: Optional[int] = None,
                 overrides: Optional[Mapping[str, Any]] = None,
                 warmup: int = 1, iters: int = 3):
        self.target = target
        self.cache = cache
        self._budget = budget
        self.overrides: Dict[str, KernelSchedule] = {
            kernel: ksched.as_schedule(kernel, value)
            for kernel, value in (overrides or {}).items()
        }
        self.warmup = warmup
        self.iters = iters
        self._lock = threading.Lock()
        self._stats = {"tunes": 0, "cache_hits": 0, "tune_time_s": 0.0}

    @property
    def budget(self) -> int:
        if self._budget is not None:
            return max(1, int(self._budget))
        return read_env("REPRO_TUNE_BUDGET", DEFAULT_BUDGET)

    def stats(self) -> Dict[str, Any]:
        with self._lock:
            return dict(self._stats)

    # -- planning -----------------------------------------------------------

    def plan(self, calls: KernelCalls) -> Dict[str, KernelSchedule]:
        """Tuned (or pinned) schedule per kernel in a discovered call
        set; the mapping feeds straight into ``use_schedules`` /
        ``XLAGenerator.generate(schedules=...)``."""
        schedules: Dict[str, KernelSchedule] = {}
        for entry in calls.values():
            kernel = entry["kernel"]
            if kernel in schedules:
                continue
            if kernel in self.overrides:
                schedules[kernel] = self.overrides[kernel]
                continue
            record = self.tune(kernel, entry["shapes"], entry["meta"])
            schedules[kernel] = ksched.as_schedule(kernel, record["schedule"])
        return schedules

    # -- tuning -------------------------------------------------------------

    def shape_bucket(self, kernel: str, shapes: Mapping[str, Tuple[int, ...]],
                     meta: Mapping[str, Any]) -> str:
        dims = ";".join(
            f"{name}={'x'.join(str(_pow2_ceil(d)) for d in shape)}"
            for name, shape in sorted(shapes.items()))
        flags = ",".join(f"{k}={meta[k]}" for k in sorted(meta))
        return f"{dims}|{flags}"

    def tune(self, kernel: str, shapes: Mapping[str, Tuple[int, ...]],
             meta: Mapping[str, Any]) -> Dict[str, Any]:
        """Best schedule for this call site, from cache or a fresh sweep."""
        bucket = self.shape_bucket(kernel, shapes, meta)
        swept: list = []

        def sweep() -> Dict[str, Any]:
            swept.append(True)
            # time on the device the target measures on (a platform-pinned
            # target never reads another platform's clock)
            device = target_devices(self.target)[0]
            with jax.default_device(device):
                record = self._sweep(kernel, shapes, meta, bucket)
            record["device"] = device_identity(device)
            return record

        if self.cache is not None:
            key = ("kernel_schedule", kernel, bucket, self.target.mesh_scope)
            record = self.cache.get_or_compute(key, sweep)
        else:
            record = sweep()
        with self._lock:
            if swept:
                self._stats["tunes"] += 1
                self._stats["tune_time_s"] += float(record["tune_time_s"])
            else:
                self._stats["cache_hits"] += 1
        return record

    def _sweep(self, kernel: str, shapes: Mapping[str, Tuple[int, ...]],
               meta: Mapping[str, Any], bucket: str) -> Dict[str, Any]:
        run, seq_len, kv_len = self._runner(kernel, shapes, meta)
        # dedupe by *effective* signature: two requests that clamp to the
        # same launch would time (and later compile) the same program
        seen: Dict[str, KernelSchedule] = {}
        for cand in ksched.CANDIDATE_SCHEDULES[kernel]:
            eff = ksched.effective_schedule(kernel, cand, seq_len=seq_len,
                                            kv_len=kv_len)
            seen.setdefault(ksched.schedule_signature(kernel, eff), cand)
            if len(seen) >= self.budget:
                break
        t_start = time.perf_counter()
        timed = []
        for eff_sig, cand in seen.items():
            # measurements must not overlap sibling compiles (same
            # rationale as HardwareManager.benchmark)
            with compile_gate():
                for _ in range(self.warmup):
                    jax.block_until_ready(run(cand))
                t0 = time.perf_counter()
                for _ in range(self.iters):
                    out = run(cand)
                jax.block_until_ready(out)
                latency = (time.perf_counter() - t0) / self.iters
            timed.append((latency, cand, eff_sig))
        # stable min: the default candidate is first, so a tie keeps it
        best_latency, best, best_eff_sig = min(timed, key=lambda t: t[0])
        best_eff = ksched.effective_schedule(kernel, best, seq_len=seq_len,
                                             kv_len=kv_len)
        return {
            "kernel": kernel,
            "bucket": bucket,
            "schedule": best.to_dict(),
            "effective": best_eff.to_dict(),
            "latency_s": best_latency,
            "default_latency_s": timed[0][0],
            "n_candidates": len(timed),
            "candidates": [
                {"schedule": cand.to_dict(), "effective": sig,
                 "latency_s": lat}
                for lat, cand, sig in timed
            ],
            "tune_time_s": time.perf_counter() - t_start,
        }

    # -- synthetic inputs ---------------------------------------------------

    def _runner(self, kernel: str, shapes: Mapping[str, Tuple[int, ...]],
                meta: Mapping[str, Any]):
        """(closure timing one candidate, seq_len, kv_len) with synthetic
        inputs at the call's real shapes, fixed seed."""
        dtype = jnp.dtype(meta.get("dtype", "float32"))
        keys = iter(jax.random.split(jax.random.PRNGKey(0), 8))

        def normal(shape):
            return jax.random.normal(next(keys), shape, jnp.float32
                                     ).astype(dtype)

        if kernel == "flash_attention":
            q = normal(shapes["q"])
            k = normal(shapes["k"])
            v = normal(shapes["v"])

            def run(cand):
                return kops.flash_attention(
                    q, k, v, causal=bool(meta.get("causal", True)),
                    window=meta.get("window"), scale=meta.get("scale"),
                    schedule=cand)
            return run, shapes["q"][1], shapes["k"][1]

        if kernel == "ssm_scan":
            x = normal(shapes["x"])
            dt = jax.nn.softplus(normal(shapes["dt"]))
            a = -jnp.exp(normal(shapes["a"]))
            b = normal(shapes["b"])
            c = normal(shapes["c"])

            def run(cand):
                return kops.ssm_scan(x, dt, a, b, c, schedule=cand)
            return run, shapes["x"][1], None

        if kernel == "mlstm_scan":
            q = normal(shapes["q"])
            k = normal(shapes["k"])
            v = normal(shapes["v"])
            i_log = normal(shapes["i_log"])
            f_log = normal(shapes["f_log"])

            def run(cand):
                return kops.mlstm_scan(q, k, v, i_log, f_log, schedule=cand)
            return run, shapes["q"][1], None

        raise ksched.ScheduleError(
            f"no tuning recipe for kernel {kernel!r}")
