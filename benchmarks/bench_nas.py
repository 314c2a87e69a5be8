"""NAS-layer benchmarks mirroring the paper's claims:

  * sampler comparison (paper §III: Optuna-compatible optimization)
  * search-space translation + dynamic model construction throughput
    (paper §IV-C: models instantiated only after sampling)
  * estimator fidelity: analytical FLOPs/params vs XLA compiled truth
    (paper §V: cost estimators)
  * end-to-end HIL pipeline latency breakdown (paper §VI: generators)
  * pre-processing joint search benefit (paper §IV-E)
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit, timeit
from repro.core.builder import ModelBuilder
from repro.core.space import parse_search_space
from repro.core.translate import sample_architecture
from repro.data.pipeline import SyntheticClassificationData
from repro.evaluation import (
    CompiledLatencyEstimator,
    EvaluationCache,
    TrainedAccuracyEstimator,
)
from repro.search import (
    GridSampler,
    MedianPruner,
    ParallelStudy,
    RandomSampler,
    RegularizedEvolutionSampler,
    Study,
    TPESampler,
    TrialPruned,
    TrialState,
)
from repro.hwgen.generator import HardwareManager, XLAGenerator

SPACE_YAML = """
input: [4, 256]
output: 6
sequence:
  - block: "features"
    op_candidates: "conv-block"
    type_repeat:
      type: "vary_all"
      depth: [1, 2, 3, 4]
  - block: "head"
    op_candidates: "linear"
    linear:
      width: [32, 64, 128]
default_op_params:
  conv1d:
    kernel_size: [3, 5]
    out_channels: [8, 16]
    stride: [1, 2]
composites:
  conv-block:
    sequence:
      - block: "conv"
        op_candidates: "conv1d"
      - block: "pool"
        op_candidates: ["maxpool", "identity"]
"""


def bench_samplers() -> None:
    """Best objective value after N trials, per sampler (lower=better)."""
    space = parse_search_space(SPACE_YAML)
    builder = ModelBuilder(space.input_shape, space.output_dim)

    def objective(trial):
        arch = sample_architecture(space, trial)
        m = builder.build(arch)
        # synthetic hardware-cost surface: flops + param pressure
        return m.flops / 1e6 + m.n_params / 1e4

    for name, sampler in [
        ("random", RandomSampler(seed=0)),
        ("tpe", TPESampler(seed=0, n_startup=8)),
        ("evolution", RegularizedEvolutionSampler(seed=0, population=12)),
        ("grid", GridSampler(seed=0)),
    ]:
        t0 = time.perf_counter()
        study = Study(sampler=sampler)
        study.optimize(objective, 40)
        dt = (time.perf_counter() - t0) / 40
        emit(f"sampler/{name}", dt, f"best={study.best_trial.values[0]:.2f}")


def bench_builder_throughput() -> None:
    """sample+build latency (dynamic instantiation, paper §IV-C)."""
    space = parse_search_space(SPACE_YAML)
    builder = ModelBuilder(space.input_shape, space.output_dim)
    study = Study(sampler=RandomSampler(seed=1))

    def one():
        trial = study.ask()
        arch = sample_architecture(space, trial)
        return builder.build(arch)

    dt = timeit(one, warmup=3, iters=50)
    emit("builder/sample+build", dt, f"models_per_s={1 / dt:.0f}")

    dt_parse = timeit(lambda: parse_search_space(SPACE_YAML), warmup=2, iters=20)
    emit("builder/yaml_parse", dt_parse, "")


def bench_estimator_fidelity() -> None:
    """Analytical FLOPs vs XLA cost_analysis ground truth (paper §V)."""
    space = parse_search_space(SPACE_YAML)
    builder = ModelBuilder(space.input_shape, space.output_dim)
    study = Study(sampler=RandomSampler(seed=2))
    gen = XLAGenerator("host_cpu")
    rel_errs = []
    t_gen = 0.0
    n = 8
    for _ in range(n):
        arch = sample_architecture(space, study.ask())
        m = builder.build(arch)
        params = m.init(jax.random.PRNGKey(0))
        x = jnp.zeros((1, 256, 4))
        t0 = time.perf_counter()
        artifact = gen.generate(m.apply, (params, x))
        t_gen += time.perf_counter() - t0
        if artifact.flops > 0 and m.flops > 0:
            rel_errs.append(abs(artifact.flops - m.flops) / artifact.flops)
    emit("estimator/flops_vs_xla", t_gen / n,
         f"median_rel_err={np.median(rel_errs):.3f}")


def bench_hil_pipeline() -> None:
    """Generate vs benchmark latency per candidate (paper §VI mode 2)."""
    space = parse_search_space(SPACE_YAML)
    builder = ModelBuilder(space.input_shape, space.output_dim)
    study = Study(sampler=RandomSampler(seed=3))
    gen = XLAGenerator("host_cpu")
    mgr = HardwareManager(warmup=1, iters=5)
    arch = sample_architecture(space, study.ask())
    m = builder.build(arch)
    params = m.init(jax.random.PRNGKey(0))
    x = jnp.zeros((8, 256, 4))

    t0 = time.perf_counter()
    artifact = gen.generate(m.apply, (params, x))
    t_generate = time.perf_counter() - t0
    t1 = time.perf_counter()
    result = mgr.benchmark(artifact, (params, x))
    t_bench = time.perf_counter() - t1
    emit("hil/generate", t_generate, f"flops={artifact.flops:.0f}")
    emit("hil/benchmark", t_bench, f"latency_us={result['latency_s'] * 1e6:.0f}")


def bench_preprocessing_joint() -> None:
    """Joint pre-processing+arch search vs arch-only (paper §IV-E)."""
    base = SPACE_YAML
    joint = SPACE_YAML + """
preprocessing:
  normalize:
    kind: ["zscore", "minmax"]
  downsample:
    factor: [1, 2]
"""
    data = SyntheticClassificationData(n=240, length=256, channels=4, classes=6).split()
    acc_est = TrainedAccuracyEstimator(steps=30, batch=32)

    def run(yaml_text, seed):
        space = parse_search_space(yaml_text)
        builder = ModelBuilder(space.input_shape, space.output_dim)
        study = Study(sampler=RandomSampler(seed=seed), directions=("maximize",))

        def obj(trial):
            arch = sample_architecture(space, trial)
            m = builder.build(arch)
            return acc_est.estimate(m, {"data": data})

        study.optimize(obj, 6)
        return study.best_trial.values[0]

    t0 = time.perf_counter()
    acc_base = run(base, 0)
    acc_joint = run(joint, 0)
    dt = time.perf_counter() - t0
    emit("preprocess/joint_vs_base", dt / 12,
         f"acc_base={acc_base:.3f};acc_joint={acc_joint:.3f}")


PARALLEL_SPACE_YAML = """
input: [2, 128]
output: 4
sequence:
  - block: "features"
    op_candidates: "conv1d"
    type_repeat:
      type: "repeat_op"
      depth: [1, 2]
    conv1d:
      kernel_size: [3, 5]
      out_channels: [8]
  - block: "head"
    op_candidates: "linear"
    linear:
      width: [16, 32]
preprocessing:
  normalize:
    kind: ["zscore", "minmax"]
"""


PARALLEL_TRIALS, PARALLEL_SEED = 128, 5

# per-process lazy state for the picklable objective below: process-pool
# workers (spawn) re-import this module and build their own copy, sharing
# compiled values with the parent and each other through the disk cache
_WORKER_STATE = {}


class CompileBoundObjective:
    """Picklable compile-bound objective usable on every executor backend.

    Holds only strings; the heavy state (space, builder, estimator and
    its cache) is built lazily per process.  Each trial records a
    ``worker`` user-attr with the evaluating process's pid and its
    cumulative cache/compile counters, so the parent can aggregate
    "how many XLA compiles did this study really perform?" across
    processes it cannot otherwise observe.
    """

    def __init__(self, cache_dir: str | None = None, tag: str = "default"):
        self.cache_dir = cache_dir
        self.tag = tag

    def _state(self):
        key = (self.cache_dir, self.tag)
        state = _WORKER_STATE.get(key)
        if state is None:
            from repro.evaluation import EvaluationCache as _Cache

            space = parse_search_space(PARALLEL_SPACE_YAML)
            builder = ModelBuilder(space.input_shape, space.output_dim)
            cache = _Cache(disk=self.cache_dir) if self.cache_dir else _Cache()
            est = CompiledLatencyEstimator("host_cpu", batch=4, cache=cache,
                                           metric="modelled")
            state = _WORKER_STATE[key] = (space, builder, est)
        return state

    def __call__(self, trial):
        import os as _os

        from repro.hwgen.generator import generate_call_count

        space, builder, est = self._state()
        arch = sample_architecture(space, trial)
        value = est.estimate(builder.build(arch))
        trial.set_user_attr("worker", {
            "pid": _os.getpid(),
            "generates": generate_call_count(),
            **est.cache.stats.as_dict(),
        })
        return value


def _warm_worker():
    """Per-worker-process warmup: pay the jax import + XLA backend init
    before the measured region starts."""
    import os as _os

    import jax as _jax

    _jax.devices()
    return _os.getpid()


def aggregate_worker_stats(study) -> dict:
    """Sum each worker process's final cumulative counters (keyed by pid;
    counters are monotone, so the elementwise max per pid is its total)."""
    per_pid: dict = {}
    for t in study.trials:
        w = t.user_attrs.get("worker")
        if not w:
            continue
        cur = per_pid.setdefault(w["pid"], dict(w))
        for k in ("generates", "hits", "disk_hits", "misses"):
            cur[k] = max(cur[k], w[k])
    totals = {k: sum(c[k] for c in per_pid.values())
              for k in ("generates", "hits", "disk_hits", "misses")}
    lookups = totals["hits"] + totals["disk_hits"] + totals["misses"]
    totals["hit_rate"] = (totals["hits"] + totals["disk_hits"]) / lookups if lookups else 0.0
    totals["n_workers_seen"] = len(per_pid)
    return totals


def run_parallel_config(name: str, cache_dir: str | None = None) -> dict:
    """Run ONE serial/parallel configuration and return its measurements.

    Each configuration must run in a fresh process: jax/XLA keeps an
    in-process compilation cache, so any same-process rerun over the same
    architectures is several times faster and would corrupt the
    comparison (the later configuration always looks better).  The
    ``disk_*`` configurations share compiled values through the
    disk-persistent cache in ``cache_dir`` instead — pass a populated
    directory to measure a warm restart.
    """
    space = parse_search_space(PARALLEL_SPACE_YAML)
    builder = ModelBuilder(space.input_shape, space.output_dim)

    def make_objective(estimate):
        def objective(trial):
            arch = sample_architecture(space, trial)
            return estimate(builder.build(arch))
        return objective

    def cached_estimator():
        cache = EvaluationCache()
        return cache, CompiledLatencyEstimator("host_cpu", batch=4, cache=cache,
                                               metric="modelled")

    stats_cache = None  # in-process cache whose stats we report, if any
    if name == "serial":
        # baseline: serial loop, every candidate re-generated from scratch
        # (what the paper's framework and aw_nas do per trial)
        gen = XLAGenerator("host_cpu")

        def raw_estimate(m):
            import jax
            import jax.numpy as jnp

            l, c = m.input_shape[-1], m.input_shape[0]
            params = m.init(jax.random.PRNGKey(0))
            artifact = gen.generate(m.apply, (params, jnp.zeros((4, l, c), jnp.float32)))
            return float(artifact.roofline.bound_s)

        study, objective = Study(sampler=RandomSampler(seed=PARALLEL_SEED)), make_objective(raw_estimate)
        opt_kw = {}
    elif name == "serial_cached":
        stats_cache, est = cached_estimator()
        study, objective = Study(sampler=RandomSampler(seed=PARALLEL_SEED)), make_objective(est.estimate)
        opt_kw = {}
    elif name == "parallel4":
        stats_cache, est = cached_estimator()
        study = ParallelStudy(sampler=RandomSampler(seed=PARALLEL_SEED), n_workers=4)
        objective = make_objective(est.estimate)
        opt_kw = {"n_workers": 4}
    elif name == "disk_serial":
        study = Study(sampler=RandomSampler(seed=PARALLEL_SEED))
        objective = CompileBoundObjective(cache_dir, tag=name)
        opt_kw = {}
    elif name in ("disk_thread2", "disk_process2", "disk_remote2"):
        obj_cls = CompileBoundObjective
        if name == "disk_thread2":
            backend = "thread"
        elif name == "disk_remote2":
            # worker-daemon pool from REPRO_REMOTE_WORKERS (the bench
            # spawns the daemons); warmed like the process pool so the
            # measured region excludes jax import + XLA backend init
            from repro.search.remote.executor import RemoteExecutor

            backend = RemoteExecutor()
            backend.start(2)
            backend.warmup(_remote_safe("_warm_worker"))
            obj_cls = _remote_safe("CompileBoundObjective")
        else:
            # Pre-start + warm the worker processes (interpreter spawn,
            # jax import, XLA backend init) before the measured region:
            # the serial/thread configurations get those one-time costs
            # untimed too, via the parent's module imports.
            from repro.search import ProcessExecutor

            backend = ProcessExecutor()
            backend.start(2)
            backend.warmup(_warm_worker)
        study = ParallelStudy(sampler=RandomSampler(seed=PARALLEL_SEED),
                              n_workers=2, backend=backend)
        objective = obj_cls(cache_dir, tag=name)
        opt_kw = {"n_workers": 2}
    else:
        raise KeyError(name)

    t0 = time.perf_counter()
    study.optimize(objective, PARALLEL_TRIALS, **opt_kw)
    seconds = time.perf_counter() - t0
    best = study.best_trial
    out = {
        "name": name,
        "seconds": seconds,
        "hit_rate": stats_cache.stats.hit_rate if stats_cache is not None else 0.0,
        "best_number": best.number,
        "best_value": best.values[0],
    }
    if type(objective).__name__ == "CompileBoundObjective":
        # per-worker cumulative counters, aggregated across processes
        # (includes the authoritative hit_rate for these configs)
        out.update(aggregate_worker_stats(study))
    return out


def _remote_safe(name: str):
    """Resolve a module-level name via the importable ``benchmarks.bench_nas``
    path.  When this file runs as a script its globals pickle as
    ``__main__.X``, which a remote worker daemon (whose ``__main__`` is
    ``repro.worker``) cannot resolve — the twin from the real module can be."""
    import benchmarks.bench_nas as mod

    return getattr(mod, name)


def _child_env(extra_env: dict | None = None) -> dict:
    """Environment for a child interpreter that imports this repo.  A
    chip belongs to one process: a parent that already holds an
    accelerator must not start children that would need it."""
    import os

    from repro.search.executors import (ONE_PROCESS_PER_CHIP,
                                        OneProcessPerChipError,
                                        held_accelerator)

    platform = held_accelerator()
    if platform is not None:
        raise OneProcessPerChipError(
            f"{ONE_PROCESS_PER_CHIP}: this benchmark process holds the "
            f"{platform} backend; run subprocess-isolated groups from a "
            f"parent that has not touched JAX")
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = {**os.environ, **(extra_env or {})}
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(repo, "src"), repo] + env.get("PYTHONPATH", "").split(os.pathsep))
    return env


def _run_config_subprocess(name: str, cache_dir: str | None = None,
                           extra_env: dict | None = None) -> dict:
    """Run one configuration in an isolated interpreter and parse its
    JSON result line (see run_parallel_config for why isolation matters)."""
    import json
    import os
    import subprocess
    import sys

    env = _child_env(extra_env)
    cmd = [sys.executable, os.path.abspath(__file__), "--parallel-config", name]
    if cache_dir:
        cmd.append(cache_dir)
    r = subprocess.run(cmd, capture_output=True, text=True, env=env, timeout=1800)
    if r.returncode != 0:
        raise RuntimeError(f"config {name!r} failed:\n{r.stderr[-4000:]}")
    return json.loads(r.stdout.strip().splitlines()[-1])


def bench_parallel_engine() -> None:
    """Serial-recompile-everything vs ParallelStudy + shared EvaluationCache
    on the compiled-latency objective (the framework's hottest path).

    The space is deliberately compact so samplers revisit architectures —
    the regime where the cache matters.  metric="modelled" makes the
    objective value deterministic, so the serial and parallel runs at the
    same seed must find the same best trial.  Every configuration runs in
    its own subprocess (see run_parallel_config) so each pays its own cold
    XLA compiles.
    """
    results = {name: _run_config_subprocess(name)
               for name in ("serial", "serial_cached", "parallel4")}
    serial, cached, par = results["serial"], results["serial_cached"], results["parallel4"]
    best_match = (serial["best_number"] == par["best_number"]
                  and serial["best_value"] == par["best_value"]
                  and cached["best_value"] == par["best_value"])
    emit("parallel/serial", serial["seconds"] / PARALLEL_TRIALS,
         f"best={serial['best_value']:.3e}")
    emit("parallel/serial_cached", cached["seconds"] / PARALLEL_TRIALS,
         f"hit_rate={cached['hit_rate']:.2f}")
    emit("parallel/parallel4", par["seconds"] / PARALLEL_TRIALS,
         f"speedup_vs_serial={serial['seconds'] / par['seconds']:.2f}x;"
         f"speedup_vs_cached={cached['seconds'] / par['seconds']:.2f}x;"
         f"hit_rate={par['hit_rate']:.2f};"
         f"best_match={best_match}")


def bench_process_engine() -> None:
    """Thread vs process executor at n_workers=2 on the compile-bound
    objective, each against a cold disk store, then warm restarts over
    the populated store on all three backends.

    The process backend is the only configuration with real compile
    concurrency (each worker process owns its own XLA compiler; the
    in-process admission gate serializes sibling threads), so on a
    compile-bound objective it must be at least as fast as the thread
    backend.  A warm restart must perform ZERO XLA compiles (hit rate
    1.0) and reproduce the identical best trial on every backend.
    """
    import shutil
    import tempfile

    trials = PARALLEL_TRIALS
    dir_thread = tempfile.mkdtemp(prefix="bench-nas-cache-thread-")
    dir_process = tempfile.mkdtemp(prefix="bench-nas-cache-process-")
    try:
        cold_thread = _run_config_subprocess("disk_thread2", dir_thread)
        cold_process = _run_config_subprocess("disk_process2", dir_process)
        best_match = (cold_process["best_number"] == cold_thread["best_number"]
                      and cold_process["best_value"] == cold_thread["best_value"])
        emit("process/thread2", cold_thread["seconds"] / trials,
             f"compiles={cold_thread['generates']};hit_rate={cold_thread['hit_rate']:.2f}")
        emit("process/process2", cold_process["seconds"] / trials,
             f"speedup_vs_thread={cold_thread['seconds'] / cold_process['seconds']:.2f}x;"
             f"compiles={cold_process['generates']};"
             f"hit_rate={cold_process['hit_rate']:.2f};"
             f"best_match={best_match}")

        # warm restarts share the store the thread run populated
        for short in ("serial", "thread2", "process2"):
            r = _run_config_subprocess(f"disk_{short}", dir_thread)
            best_match = (r["best_number"] == cold_thread["best_number"]
                          and r["best_value"] == cold_thread["best_value"])
            emit(f"warm-restart/{short}", r["seconds"] / trials,
                 f"compiles={r['generates']};hit_rate={r['hit_rate']:.2f};"
                 f"best_match={best_match}")
    finally:
        shutil.rmtree(dir_thread, ignore_errors=True)
        shutil.rmtree(dir_process, ignore_errors=True)


def _spawn_worker_daemon(cache_dir: str):
    """Launch one ``python -m repro.worker`` daemon on an ephemeral port
    and return ``(proc, "host:port")`` once it prints its bound address."""
    import os
    import subprocess
    import sys

    env = _child_env()
    proc = subprocess.Popen(
        [sys.executable, "-m", "repro.worker", "--port", "0",
         "--cache-dir", cache_dir, "--no-warmup"],
        stdout=subprocess.PIPE, stderr=subprocess.DEVNULL, text=True, env=env)
    deadline = time.monotonic() + 120.0
    addr = None
    while time.monotonic() < deadline:
        line = proc.stdout.readline()
        if not line:
            break
        if line.startswith("listening on "):
            addr = line.split()[-1].strip()
            break
    if not addr:
        proc.kill()
        raise RuntimeError("worker daemon never printed its bound address")
    return proc, addr


def bench_remote_engine() -> None:
    """Remote worker daemons vs the local process pool at n_workers=2 on
    the compile-bound objective (each against its own cold disk store),
    then a kill-one-worker run over the remote pool's warm store.

    What must hold: (1) the remote run finds the identical best trial as
    the process run at the same seed — detached plans make the wire
    transparent to the search; (2) SIGKILLing one of the two daemons
    mid-run still completes every trial via bounded resubmission to the
    surviving sibling, again with the identical best trial."""
    import shutil
    import tempfile
    import threading
    import warnings as _warnings

    trials = PARALLEL_TRIALS
    dir_process = tempfile.mkdtemp(prefix="bench-nas-cache-rproc-")
    dir_remote = tempfile.mkdtemp(prefix="bench-nas-cache-remote-")
    daemons = []
    try:
        cold_process = _run_config_subprocess("disk_process2", dir_process)
        daemons = [_spawn_worker_daemon(dir_remote) for _ in range(2)]
        addrs = [a for _, a in daemons]
        cold_remote = _run_config_subprocess(
            "disk_remote2", dir_remote,
            extra_env={"REPRO_REMOTE_WORKERS": ",".join(addrs)})
        best_match = (cold_remote["best_number"] == cold_process["best_number"]
                      and cold_remote["best_value"] == cold_process["best_value"])
        if not best_match:
            raise AssertionError(
                f"remote best trial {cold_remote['best_number']} diverged from "
                f"process best {cold_process['best_number']} at the same seed")
        emit("remote/process2", cold_process["seconds"] / trials,
             f"compiles={cold_process['generates']};"
             f"hit_rate={cold_process['hit_rate']:.2f}")
        emit("remote/remote2", cold_remote["seconds"] / trials,
             f"vs_process={cold_process['seconds'] / cold_remote['seconds']:.2f}x;"
             f"compiles={cold_remote['generates']};"
             f"hit_rate={cold_remote['hit_rate']:.2f};"
             f"best_match={best_match}")

        # kill-one-worker: warm store, driven from this process so the
        # victim daemon can be SIGKILLed mid-run
        from repro.search.remote.executor import RemoteExecutor

        study = ParallelStudy(sampler=RandomSampler(seed=PARALLEL_SEED),
                              n_workers=2,
                              backend=RemoteExecutor(workers=list(addrs)),
                              schedule="sliding_window",
                              tell_order="completion")
        victim = daemons[0][0]
        # the warm-store run finishes in well under a second, so the kill
        # must land early to hit it mid-flight (killed_mid_run reports
        # whether it actually did)
        killer = threading.Timer(0.05, victim.kill)
        t0 = time.perf_counter()
        killer.start()
        with _warnings.catch_warnings():
            # the worker-lost + resubmit warning is the expected path here
            _warnings.simplefilter("ignore", RuntimeWarning)
            study.optimize(
                _remote_safe("CompileBoundObjective")(dir_remote, tag="kill"),
                trials)
        dt = time.perf_counter() - t0
        killer.cancel()
        killed_mid_run = victim.poll() is not None
        best = study.best_trial
        if (best.number != cold_remote["best_number"]
                or best.values[0] != cold_remote["best_value"]):
            raise AssertionError(
                f"kill-one-worker run diverged: best {best.number} vs "
                f"{cold_remote['best_number']} — resubmitted trials must "
                f"reproduce their original parameters")
        incomplete = [t for t in study.trials
                      if t.state not in (TrialState.COMPLETE, TrialState.PRUNED)]
        if incomplete:
            raise AssertionError(
                f"{len(incomplete)} trials did not complete after the kill")
        emit("remote/kill_one_worker", dt / trials,
             f"completed={len(study.trials)}/{trials};"
             f"killed_mid_run={killed_mid_run};best_match=True")
    finally:
        for proc, _ in daemons:
            proc.kill()
        shutil.rmtree(dir_process, ignore_errors=True)
        shutil.rmtree(dir_remote, ignore_errors=True)


def bench_explorer_facade() -> None:
    """Facade overhead: the declarative Explorer front door vs the same
    experiment hand-wired through the layered API.  Both drive identical
    analytic-estimator searches at a fixed seed, so they must find the
    identical best trial; the delta is pure composition overhead (spec
    validation, registry resolution, report assembly), which must stay
    negligible next to a single XLA compile."""
    import yaml as _yaml

    from repro import Explorer, ExperimentSpec
    from repro.evaluation import (
        CriteriaRunner,
        FlopsEstimator,
        OptimizationCriteria,
        ParamCountEstimator,
    )

    trials, seed = 40, 0

    def run_hand_wired():
        space = parse_search_space(SPACE_YAML)
        builder = ModelBuilder(space.input_shape, space.output_dim)
        runner = CriteriaRunner([
            OptimizationCriteria(FlopsEstimator(), kind="objective", weight=1.0),
            OptimizationCriteria(ParamCountEstimator(), kind="objective", weight=0.1),
        ])

        def objective(trial):
            arch = sample_architecture(space, trial)
            trial.set_user_attr("signature", arch.signature())
            return runner.evaluate(builder.build(arch), trial=trial)

        study = Study(sampler=TPESampler(seed=seed))
        study.optimize(objective, trials)
        return study.best_trial

    def run_facade():
        spec = ExperimentSpec.from_dict({
            "name": "bench-facade",
            "search_space": _yaml.safe_load(SPACE_YAML),
            "sampler": {"name": "tpe", "seed": seed},
            "executor": {"backend": "serial"},
            "criteria": [
                {"estimator": "flops", "kind": "objective", "weight": 1.0},
                {"estimator": "n_params", "kind": "objective", "weight": 0.1},
            ],
            "budget": {"n_trials": trials},
        })
        explorer = Explorer.from_spec(spec)
        report = explorer.run(save_report=False)
        return report.best

    t0 = time.perf_counter()
    hand_best = run_hand_wired()
    t_hand = time.perf_counter() - t0
    t1 = time.perf_counter()
    facade_best = run_facade()
    t_facade = time.perf_counter() - t1

    best_match = (hand_best.number == facade_best["number"]
                  and list(hand_best.values) == facade_best["values"])
    emit("explorer/hand_wired", t_hand / trials, f"best={hand_best.values[0]:.3e}")
    emit("explorer/facade", t_facade / trials,
         f"overhead_vs_hand_wired={(t_facade / t_hand - 1) * 100:+.0f}%;"
         f"best_match={best_match}")


# ---------------------------------------------------------------------------
# sweep group: one experiment fanned across targets/samplers over a
# SHARED disk cache — compile-derived values are scoped by mesh
# topology, so after the first target has paid for a candidate's
# compile, every later target with the same topology pays zero
# ---------------------------------------------------------------------------

SWEEP_SEED = 9


def bench_sweep_engine() -> None:
    """3-target x 2-sampler sweep on the compile-bound modelled-latency
    objective.  Expands through ``SweepSpec`` and runs cell by cell so
    per-target XLA compile counts are observable: the first target
    compiles every unique candidate; the second and third targets (same
    1x1 mesh topology, different chip constants) must compile ZERO —
    their modelled latencies come from the cached roofline terms.  A
    final ``run_sweep`` then resumes every completed cell from its
    persisted report (re-running nothing) and merges the SweepReport."""
    import shutil
    import tempfile

    import yaml as _yaml

    from repro.explorer.sweep import SweepSpec, run_sweep
    from repro.hwgen.generator import generate_call_count

    cache_dir = tempfile.mkdtemp(prefix="bench-nas-sweep-cache-")
    report_dir = tempfile.mkdtemp(prefix="bench-nas-sweep-report-")
    trials = 12
    try:
        spec = SweepSpec.from_dict({
            "name": "bench-sweep",
            "base": {
                "name": "bench-sweep-base",
                "search_space": _yaml.safe_load(PARALLEL_SPACE_YAML),
                "executor": {"backend": "serial"},
                "criteria": [
                    {"estimator": "latency_s", "kind": "objective",
                     "params": {"batch": 4, "metric": "modelled"}},
                    # second objective makes the cross-target Pareto
                    # union non-trivial; it shares the cached artifact
                    # with latency_s, so compile counts are unchanged
                    {"estimator": "peak_bytes", "kind": "objective",
                     "weight": 1.0e-9, "params": {"batch": 4}},
                ],
                "budget": {"n_trials": trials},
            },
            "axes": {
                "target": ["host_cpu", "edge_npu", "tpu_v5e"],
                "sampler": [{"name": "random", "seed": SWEEP_SEED},
                            {"name": "grid", "seed": SWEEP_SEED}],
            },
            "cache": cache_dir,
            "report_dir": report_dir,
        })
        from repro.explorer import Explorer

        per_target: dict = {}
        for cell in spec.expand():
            c0, t0 = generate_call_count(), time.perf_counter()
            Explorer.from_spec(cell.spec).run()
            dt = time.perf_counter() - t0
            compiles = generate_call_count() - c0
            agg = per_target.setdefault(cell.axes["target"],
                                        {"seconds": 0.0, "compiles": 0})
            agg["seconds"] += dt
            agg["compiles"] += compiles
        first = spec.axes["target"][0]
        for target, agg in per_target.items():
            note = f"compiles={agg['compiles']}"
            if target != first:
                note += (f";reuses_first_target_compiles="
                         f"{agg['compiles'] == 0}")
            emit(f"sweep/{target}", agg["seconds"] / (2 * trials), note)

        # merge pass: every cell's report is on disk, so the sweep engine
        # must resume all of them (re-running nothing) and just merge
        t0 = time.perf_counter()
        merged = run_sweep(spec)
        dt = time.perf_counter() - t0
        winners = {k: (v[0]["target"] if v else None)
                   for k, v in merged.target_rankings.items()}
        emit("sweep/merged_resume", dt,
             f"cells={merged.n_cells};resumed={merged.n_resumed};"
             f"pareto_union={len(merged.pareto_union)};"
             f"winners={winners}")
    finally:
        shutil.rmtree(cache_dir, ignore_errors=True)
        shutil.rmtree(report_dir, ignore_errors=True)


# ---------------------------------------------------------------------------
# cascade group: zero-cost screening vs flat compiled evaluation at the
# SAME trial budget and seed — the multi-fidelity cascade's whole value
# proposition is that screened-out candidates never pay an XLA compile
# ---------------------------------------------------------------------------

CASCADE_TRIALS, CASCADE_SEED, CASCADE_GENERATION = 64, 11, 16

# Deep-thin models: the regime where screening pays.  Many layers make
# the XLA compile expensive (graph-size-bound) while tiny channel counts
# keep the eager zero-cost proxy cheap (dispatch-bound, per-op kernels
# shared across the few distinct layer shapes).  The depth axis is
# bimodal on purpose: per-layer parameter sampling makes the deep
# candidates pairwise-unique (the flat baseline compiles every one of
# them), but the depth-1 low-capacity corner the synflow-minimize screen
# promotes from is small AND cheap to compile — the cascade pays a few
# small compiles where the baseline pays dozens of big ones.
CASCADE_SPACE_YAML = """
input: [4, 128]
output: 6
sequence:
  - block: "features"
    op_candidates: "conv1d"
    type_repeat:
      type: "repeat_op"
      depth: [1, 32, 48, 64]
    conv1d:
      kernel_size: [3, 5]
      out_channels: [4, 8]
      stride: [1]
  - block: "head"
    op_candidates: "linear"
    linear:
      width: [16, 32]
"""


def _cascade_spec(with_screen: bool, trials: int) -> dict:
    """Experiment dict for the cascade comparison.  Both configurations
    ask the IDENTICAL trial sequence (same sampler seed; the per-trial
    RNG streams key on the trial number, and the cascade pre-samples the
    same suggestions in-parent), so the flat run's best trial either
    survives the screen — and then the cascade must find it too — or was
    screened out, which the benchmark reports instead of hiding.  The
    synflow screen runs with ``direction: minimize`` because the final
    objective minimizes modelled latency: low-capacity candidates are
    the fast ones, so proxy rank and final rank point the same way."""
    import yaml as _yaml

    spec = {
        "name": f"bench-cascade-{'screen' if with_screen else 'flat'}",
        "search_space": _yaml.safe_load(CASCADE_SPACE_YAML),
        "sampler": {"name": "random", "seed": CASCADE_SEED},
        "executor": {"backend": "serial"},
        "criteria": [
            {"estimator": "latency_s", "kind": "objective",
             "params": {"batch": 4, "metric": "modelled"}},
        ],
        "budget": {"n_trials": trials},
    }
    if with_screen:
        spec["fidelity"] = {
            "generation": CASCADE_GENERATION,
            "stages": [
                {"name": "zero_cost",
                 "criteria": [{"estimator": "synflow", "kind": "objective",
                               "direction": "minimize"}],
                 "keep": {"top_frac": 0.25}},
            ],
        }
    return spec


def _warm_cascade_process() -> None:
    """One build + proxy + compile OUTSIDE the timed window (both
    configurations, identically): first-touch JAX backend init and the
    eager per-op kernel compiles are one-time process costs, not
    screening throughput.  Uses its own estimator instances, so nothing
    lands in the measured run's evaluation cache."""
    import yaml as _yaml

    from repro.core.builder import ModelBuilder
    from repro.core.space import parse_search_space
    from repro.core.translate import sample_architecture
    from repro.evaluation.estimators import CompiledLatencyEstimator
    from repro.evaluation.proxies import SynFlowEstimator
    from repro.search.samplers import RandomSampler
    from repro.search.study import Study

    space = parse_search_space(_yaml.safe_load(CASCADE_SPACE_YAML))
    study = Study(sampler=RandomSampler(seed=997))
    builder = ModelBuilder(space.input_shape, space.output_dim)
    syn = SynFlowEstimator()
    lat = CompiledLatencyEstimator("host_cpu", batch=4, metric="modelled")
    for _ in range(2):
        model = builder.build(sample_architecture(space, study.ask()))
        syn.estimate(model)
        lat.estimate(model)


def run_cascade_config(name: str, trials: int = CASCADE_TRIALS) -> dict:
    """Run ONE cascade configuration (fresh process — same in-process XLA
    cache reasoning as run_parallel_config) and return its measurements."""
    from repro.explorer import Explorer
    from repro.hwgen.generator import generate_call_count

    with_screen = name == "cascade"
    _warm_cascade_process()
    base_compiles = generate_call_count()
    explorer = Explorer.from_dict(_cascade_spec(with_screen, trials))
    t0 = time.perf_counter()
    report = explorer.run(save_report=False)
    seconds = time.perf_counter() - t0
    out = {
        "name": name,
        "seconds": seconds,
        "compiles": generate_call_count() - base_compiles,
        "best_number": report.best["number"],
        "best_value": report.best["values"][0],
        "states": report.states,
    }
    if with_screen:
        out["funnel"] = report.fidelity["funnel"]
        out["spearman"] = report.fidelity["spearman"]
        out["promoted_numbers"] = [
            t.number for t in explorer.study.trials
            if t.user_attrs.get("fidelity_stage") == "promoted"]
    return out


def _run_cascade_subprocess(name: str, trials: int) -> dict:
    import json
    import os
    import subprocess
    import sys

    env = _child_env()
    cmd = [sys.executable, os.path.abspath(__file__), "--cascade-config",
           name, str(trials)]
    r = subprocess.run(cmd, capture_output=True, text=True, env=env, timeout=1800)
    if r.returncode != 0:
        raise RuntimeError(f"cascade config {name!r} failed:\n{r.stderr[-4000:]}")
    return json.loads(r.stdout.strip().splitlines()[-1])


def bench_cascade(quick: bool = False) -> None:
    """Flat compiled evaluation vs the zero-cost -> compiled cascade at
    the same budget/seed on the compile-bound modelled-latency objective.

    What must hold: (1) candidates evaluated per unit wall-clock goes up
    by >= 4x — the screen pays milliseconds of eager proxy math to skip
    75% of the compiles, and concentrates the survivors on few unique
    (cached) architectures; (2) screened-out candidates never compile,
    so the cascade's total compile count stays <= its promoted count;
    (3) the flat run's winner, when it survives the screen, is exactly
    the cascade's winner (`best_match`)."""
    trials = 32 if quick else CASCADE_TRIALS
    flat = _run_cascade_subprocess("nocascade", trials)
    casc = _run_cascade_subprocess("cascade", trials)
    throughput = flat["seconds"] / casc["seconds"]
    funnel = casc["funnel"]
    screened_compiles_zero = casc["compiles"] <= funnel["promoted"]
    winner_survived = flat["best_number"] in casc["promoted_numbers"]
    best_match = (not winner_survived) or (
        casc["best_number"] == flat["best_number"]
        and casc["best_value"] == flat["best_value"])
    if not screened_compiles_zero:
        raise AssertionError(
            f"screened-out candidates compiled: {casc['compiles']} compiles "
            f"for {funnel['promoted']} promotions")
    if not best_match:
        raise AssertionError(
            f"flat winner {flat['best_number']} survived the screen but the "
            f"cascade best is {casc['best_number']} — fixed-seed runs must "
            f"agree when the winner is promoted")
    rho = casc["spearman"].get("zero_cost")
    emit("cascade/flat", flat["seconds"] / trials,
         f"compiles={flat['compiles']};best={flat['best_value']:.3e}")
    emit("cascade/screened", casc["seconds"] / trials,
         f"throughput_vs_flat={throughput:.2f}x;"
         f"compiles={casc['compiles']};"
         f"promoted={funnel['promoted']};screened={funnel['screened']};"
         f"screened_compiles_zero={screened_compiles_zero};"
         f"winner_survived={winner_survived};best_match={best_match};"
         f"spearman={rho if rho is None else round(rho, 2)}")


# ---------------------------------------------------------------------------
# async scheduler group: sliding window vs batch barrier on a
# latency-skewed objective (the regime hardware-in-the-loop NAS lives in)
# ---------------------------------------------------------------------------

ASYNC_SEED = 7


class LognormalSkewObjective:
    """Synthetic latency-skew objective: a deterministic lognormal
    per-trial evaluation cost (sleep, seeded by trial number — identical
    across schedulers and backends) plus an analytic quality surface, so
    fixed-seed best trials must agree between schedulers.  Lognormal
    skew models real compile+benchmark latency: most candidates are
    cheap, a heavy tail stalls whole batches behind one straggler."""

    def __init__(self, median_s: float = 0.05, sigma: float = 1.2):
        self.median_s = median_s
        self.sigma = sigma

    def __call__(self, trial):
        import math as _math
        import random as _random

        x = trial.suggest_float("x", 0.0, 1.0)
        width = trial.suggest_int("width", 16, 128, step=16)
        rng = _random.Random(f"async-cost/{trial.number}")
        time.sleep(self.median_s * _math.exp(self.sigma * rng.gauss(0.0, 1.0)))
        return (x - 0.7) ** 2 + abs(width - 64) / 640.0


PRUNE_BUDGET_STEPS = 12


def worker_prune_objective(trial):
    """Picklable stepped objective for the worker-side pruning demo:
    every fourth trial is obviously doomed (a minority, so the peer
    median stays at the good level); a worker consulting its shipped
    pruner snapshot should abandon them after a fraction of the step
    budget."""
    bad = trial.number % 4 == 3
    base = 100.0 if bad else 1.0
    steps = 0
    for step in range(PRUNE_BUDGET_STEPS):
        trial.report(step, base + 0.01 * step)
        steps += 1
        if trial.should_prune():
            trial.set_user_attr("steps_run", steps)
            raise TrialPruned()
        time.sleep(0.01)
    trial.set_user_attr("steps_run", steps)
    return base


def bench_async_scheduler(quick: bool = False) -> None:
    """Sliding-window vs batch scheduling at n_workers=4 on the
    lognormal latency-skew objective (thread backend: the objective
    sleeps, so threads are the realistic backend), plus best-trial
    parity on Random AND Grid, plus worker-side pruning on the process
    backend.  All runs share one process — the objective compiles
    nothing, so there is no warm-state bias between configurations."""
    trials = 16 if quick else 48
    median_s = 0.02 if quick else 0.05
    workers = 4

    def run(schedule, make_sampler):
        study = ParallelStudy(sampler=make_sampler(), n_workers=workers,
                              backend="thread", schedule=schedule,
                              tell_order="completion")
        t0 = time.perf_counter()
        study.optimize(LognormalSkewObjective(median_s=median_s), trials)
        return time.perf_counter() - t0, study.best_trial

    t_batch, best_batch = run("batch", lambda: RandomSampler(seed=ASYNC_SEED))
    t_slide, best_slide = run("sliding_window", lambda: RandomSampler(seed=ASYNC_SEED))
    best_match = (best_batch.number == best_slide.number
                  and best_batch.values == best_slide.values)
    emit("async/batch", t_batch / trials, f"wall_s={t_batch:.2f}")
    emit("async/sliding", t_slide / trials,
         f"speedup_vs_batch={t_batch / t_slide:.2f}x;wall_s={t_slide:.2f};"
         f"best_match={best_match}")

    gt_batch, g_batch = run("batch", lambda: GridSampler(seed=ASYNC_SEED))
    gt_slide, g_slide = run("sliding_window", lambda: GridSampler(seed=ASYNC_SEED))
    grid_match = (g_batch.number == g_slide.number
                  and g_batch.values == g_slide.values)
    emit("async/grid_parity", (gt_batch + gt_slide) / (2 * trials),
         f"speedup_vs_batch={gt_batch / gt_slide:.2f}x;best_match={grid_match}")

    # worker-side pruning: process backend + median pruner — doomed
    # trials must stop inside the worker, well short of the step budget
    n_prune = 10 if quick else 16
    study = ParallelStudy(sampler=RandomSampler(seed=ASYNC_SEED), n_workers=2,
                          backend="process", schedule="sliding_window",
                          tell_order="completion",
                          pruner=MedianPruner(n_startup_trials=2))
    t0 = time.perf_counter()
    study.optimize(worker_prune_objective, n_prune)
    dt = time.perf_counter() - t0
    pruned = [t for t in study.trials if t.state == TrialState.PRUNED]
    steps = [t.user_attrs["steps_run"] for t in pruned if "steps_run" in t.user_attrs]
    mean_steps = sum(steps) / len(steps) if steps else float("nan")
    emit("async/worker_prune", dt / n_prune,
         f"pruned={len(pruned)}/{n_prune};budget_steps={PRUNE_BUDGET_STEPS};"
         f"mean_steps_when_pruned={mean_steps:.1f}")


# ---------------------------------------------------------------------------
# kernel-tune group: Pallas block/chunk schedules as a tunable layer —
# tuned vs default wall-clock on the real kernels, plus warm-restart
# zero-re-tune and fixed-seed best-trial parity through the facade
# ---------------------------------------------------------------------------

KERNEL_TUNE_SPEC = {
    "name": "bench-kernel-tune",
    "search_space": {
        "input": [8, 256],  # l=256 divides every candidate chunk
        "output": 6,
        "sequence": [
            {"block": "mixer", "op_candidates": "ssm",
             "ssm": {"impl": ["pallas"], "d_state": [8, 16]}},
            {"block": "head", "op_candidates": "linear",
             "linear": {"width": [16, 32]}},
        ],
    },
    "sampler": {"name": "random", "seed": 3},
    "executor": {"backend": "serial"},
    "criteria": [{"estimator": "latency_s", "kind": "objective",
                  "params": {"batch": 2, "metric": "modelled"}}],
    "kernel_tuning": {"mode": "cached", "budget": 4},
    "budget": {"n_trials": 4},
}


def run_kernel_tune_config(cache_dir: str) -> dict:
    """One facade run of the kernel-tuning experiment over ``cache_dir``
    (subprocess mode: a fresh process proves warm restarts re-tune
    nothing from disk alone, with no in-process tuner state)."""
    from repro import Explorer, ExperimentSpec

    spec = ExperimentSpec.from_dict({**KERNEL_TUNE_SPEC, "cache": cache_dir})
    t0 = time.perf_counter()
    report = Explorer.from_spec(spec).run(save_report=False)
    seconds = time.perf_counter() - t0
    kt = report.kernel_tuning or {}
    best = report.best or {}
    return {
        "seconds": seconds,
        "tunes": kt.get("tunes"),
        "cache_hits": kt.get("cache_hits"),
        "schedules": kt.get("schedules"),
        "best_number": best.get("number"),
        "best_params": best.get("params"),
        "best_values": best.get("values"),
    }


def _run_kernel_tune_subprocess(cache_dir: str) -> dict:
    import json
    import os
    import subprocess
    import sys

    env = _child_env()
    cmd = [sys.executable, os.path.abspath(__file__), "--kernel-tune-config",
           cache_dir]
    r = subprocess.run(cmd, capture_output=True, text=True, env=env, timeout=1800)
    if r.returncode != 0:
        raise RuntimeError(f"kernel-tune config failed:\n{r.stderr[-4000:]}")
    return json.loads(r.stdout.strip().splitlines()[-1])


def bench_kernel_tune(quick: bool = False) -> None:
    """Kernel schedules as a tunable layer.  What must hold: (1) on at
    least one scan kernel the tuner's winner strictly beats the named
    ``default`` schedule's wall-clock on this host; (2) a cold facade
    run tunes and picks a non-default schedule, and a warm restart in a
    *fresh process* over the same disk cache re-tunes nothing
    (``tunes == 0``); (3) at a fixed seed the warm run's best trial is
    identical to the cold run's (``best_match``)."""
    import shutil
    import tempfile

    from repro.hwgen.autotune import ScheduleTuner, discover_kernel_calls
    from repro.hwgen.targets import get_target
    from repro.kernels import ops as kops
    from repro.kernels.schedule import default_schedule

    # (1) direct tuned-vs-default sweeps at the demo's shapes
    b, l, h, p, g, n = 2, 256, 4, 16, 1, 16
    zeros = jnp.zeros
    sweeps = {
        "ssm_scan": (lambda x, dt, a, bb, c: kops.ssm_scan(x, dt, a, bb, c)[0],
                     (zeros((b, l, h, p)), zeros((b, l, h)), zeros((h,)),
                      zeros((b, l, g, n)), zeros((b, l, g, n)))),
        "mlstm_scan": (lambda q, k, v, i, f: kops.mlstm_scan(q, k, v, i, f)[0],
                       (zeros((b, l, h, p)), zeros((b, l, h, p)),
                        zeros((b, l, h, p)), zeros((b, l, h)), zeros((b, l, h)))),
    }
    tuner = ScheduleTuner(get_target("host_cpu"), warmup=1,
                          iters=2 if quick else 3)
    strict_wins = 0
    for kernel, (fn, args) in sweeps.items():
        (entry,) = discover_kernel_calls(fn, args).values()
        record = tuner.tune(kernel, entry["shapes"], entry["meta"])
        default = default_schedule(kernel).to_dict()
        win = (record["schedule"] != default
               and record["latency_s"] < record["default_latency_s"])
        strict_wins += win
        emit(f"kernel_tune/{kernel}", record["latency_s"],
             f"schedule={record['schedule']};default={default};"
             f"speedup_vs_default="
             f"{record['default_latency_s'] / record['latency_s']:.2f}x;"
             f"candidates={record['n_candidates']};strict_win={win}")
    if not strict_wins:
        raise AssertionError(
            "no kernel's tuned schedule beat the default wall-clock — "
            "schedules are not a useful tuning dimension on this host")

    # (2) + (3) cold tune vs warm restart through the facade, separate
    # processes sharing one disk cache
    cache_dir = tempfile.mkdtemp(prefix="bench_kernel_tune_")
    try:
        cold = _run_kernel_tune_subprocess(cache_dir)
        warm = _run_kernel_tune_subprocess(cache_dir)
    finally:
        shutil.rmtree(cache_dir, ignore_errors=True)
    if not cold["tunes"]:
        raise AssertionError(f"cold run tuned nothing: {cold}")
    defaults = {k: default_schedule(k).to_dict() for k in (cold["schedules"] or {})}
    non_default = any(sched != defaults[k]
                      for k, sched in (cold["schedules"] or {}).items())
    if not non_default:
        raise AssertionError(
            f"cold run selected only default schedules: {cold['schedules']}")
    if warm["tunes"] != 0:
        raise AssertionError(
            f"warm restart re-tuned {warm['tunes']} sweeps — disk-cached "
            f"schedules must make re-tuning zero")
    best_match = (cold["best_number"] == warm["best_number"]
                  and cold["best_params"] == warm["best_params"])
    if not best_match:
        raise AssertionError(
            f"fixed-seed cold/warm best trials diverged: "
            f"{cold['best_number']} vs {warm['best_number']}")
    sched_str = "+".join(f"{k}.{f}={v}"
                         for k, s in sorted((cold["schedules"] or {}).items())
                         for f, v in sorted(s.items()))
    emit("kernel_tune/cold", cold["seconds"],
         f"tunes={cold['tunes']};schedules={sched_str}")
    emit("kernel_tune/warm", warm["seconds"],
         f"tunes=0;cache_hits={warm['cache_hits']};"
         f"speedup_vs_cold={cold['seconds'] / warm['seconds']:.2f}x;"
         f"best_match={best_match}")


# ---------------------------------------------------------------------------
# serve group: exploration -> serving hand-off through the
# content-addressed artifact store.  A warm boot (same cache dir the
# exploration populated) must perform ZERO XLA compiles; a cold boot of
# the same report against an empty store pays the compile — the delta is
# what the store is for.
# ---------------------------------------------------------------------------

SERVE_EXPERIMENT = {
    "name": "bench-serve",
    "search_space": {
        "input": [2, 64],
        "output": 3,
        "sequence": [
            {"block": "features", "op_candidates": "conv1d",
             "conv1d": {"kernel_size": [3, 5], "out_channels": [4, 8]}},
            {"block": "head", "op_candidates": "linear",
             "linear": {"width": [8, 16]}},
        ],
    },
    "sampler": {"name": "random", "seed": 7},
    "executor": {"backend": "serial"},
    "criteria": [
        {"estimator": "p99_latency_s", "kind": "objective", "weight": 1.0},
        {"estimator": "throughput_tok_s", "kind": "objective",
         "direction": "maximize", "weight": 1e-6},
    ],
    "serving": {
        "max_batch": 2, "queue_limit": 4,
        "traffic": {"seed": 3, "n_requests": 16, "arrival": "poisson",
                    "rate_rps": 50.0, "prompt_lens": [4, 8], "gen_lens": 4},
    },
}


def run_serve_explore(cache_dir: str, report_dir: str, trials: int) -> dict:
    """Subprocess mode: one exploration under serving criteria, report +
    artifact store persisted for the boot configurations to consume."""
    from repro import Explorer, ExperimentSpec
    from repro.hwgen.generator import generate_call_count

    spec = ExperimentSpec.from_dict({
        **SERVE_EXPERIMENT, "cache": cache_dir, "report_dir": report_dir,
        "budget": {"n_trials": trials},
    })
    t0 = time.perf_counter()
    report = Explorer.from_spec(spec).run()
    return {
        "seconds": time.perf_counter() - t0,
        "compiles": generate_call_count(),
        "artifacts": (report.artifacts or {}).get("entries", 0),
        "report": report.artifact,
    }


def _run_serve_boot(report_path: str) -> dict:
    """Boot ``repro.launch.serve --from-report`` in a fresh interpreter
    (compile counters are process-local) and parse its JSON summary."""
    import json
    import os
    import subprocess
    import sys

    env = _child_env()
    r = subprocess.run(
        [sys.executable, "-m", "repro.launch.serve",
         "--from-report", report_path],
        capture_output=True, text=True, env=env, timeout=1800)
    if r.returncode != 0:
        raise RuntimeError(f"serve boot failed:\n{r.stderr[-4000:]}")
    return json.loads(r.stdout.strip().splitlines()[-1])


def bench_serve(quick: bool = False) -> None:
    """Exploration -> serving hand-off.  What must hold: (1) the warm
    boot — same cache dir the exploration populated — performs ZERO XLA
    compiles and serves the full declared traffic; (2) the cold boot of
    the same report over an emptied store compiles at least once; (3)
    both boots serve the identical winning signature."""
    import json
    import shutil
    import tempfile

    trials = 6 if quick else 12
    cache_dir = tempfile.mkdtemp(prefix="bench-serve-cache-")
    report_dir = tempfile.mkdtemp(prefix="bench-serve-report-")
    cold_cache = tempfile.mkdtemp(prefix="bench-serve-cold-")
    try:
        explore = _run_serve_subprocess(cache_dir, report_dir, trials)
        emit("serve/explore", explore["seconds"] / trials,
             f"compiles={explore['compiles']};artifacts={explore['artifacts']}")

        warm = _run_serve_boot(explore["report"])
        if warm["compiles"] != 0:
            raise AssertionError(
                f"warm boot performed {warm['compiles']} XLA compile(s); the "
                f"artifact store must make it zero")
        if warm["served"] != warm["traffic"]["n_requests"]:
            raise AssertionError(
                f"warm boot served {warm['served']} of "
                f"{warm['traffic']['n_requests']} requests")

        # cold boot: same report, but pointed at an empty store
        with open(explore["report"]) as f:
            report = json.load(f)
        report["spec"]["cache"]["dir"] = cold_cache
        cold_path = explore["report"] + ".cold.json"
        with open(cold_path, "w") as f:
            json.dump(report, f)
        cold = _run_serve_boot(cold_path)
        if cold["compiles"] < 1:
            raise AssertionError("cold boot compiled nothing — the warm "
                                 "measurement is not measuring the store")
        if cold["signature"] != warm["signature"]:
            raise AssertionError(
                f"boots served different programs: {cold['signature']} vs "
                f"{warm['signature']}")
        emit("serve/warm_boot", warm["boot_s"],
             f"compiles=0;served={warm['served']};shed={warm['shed']};"
             f"speedup_vs_cold={cold['boot_s'] / max(warm['boot_s'], 1e-9):.2f}x")
        emit("serve/cold_boot", cold["boot_s"],
             f"compiles={cold['compiles']};served={cold['served']}")
    finally:
        shutil.rmtree(cache_dir, ignore_errors=True)
        shutil.rmtree(report_dir, ignore_errors=True)
        shutil.rmtree(cold_cache, ignore_errors=True)


def _run_serve_subprocess(cache_dir: str, report_dir: str, trials: int) -> dict:
    import json
    import os
    import subprocess
    import sys

    env = _child_env()
    cmd = [sys.executable, os.path.abspath(__file__), "--serve-explore",
           cache_dir, report_dir, str(trials)]
    r = subprocess.run(cmd, capture_output=True, text=True, env=env, timeout=1800)
    if r.returncode != 0:
        raise RuntimeError(f"serve exploration failed:\n{r.stderr[-4000:]}")
    return json.loads(r.stdout.strip().splitlines()[-1])


def bench_faults(quick: bool = False) -> None:
    """Fault-injection hot-path cost.  The contract: with no plan
    installed, every ``fault_point`` call is one global load + ``is
    None`` test — storage and transport seams pay nothing for being
    injectable.  Armed cost (a plan whose rules all target *other*
    sites) bounds the rule-scan overhead chaos runs actually pay."""
    import tempfile

    from repro import faults
    from repro.evaluation.disk_cache import DiskEvaluationCache
    from repro.faults import FaultPlan

    n = 20_000 if quick else 200_000
    line = '{"kind": "trial", "number": 7}\n'

    faults.uninstall()
    t0 = time.perf_counter()
    for _ in range(n):
        faults.fault_point("study.persist", line)
    off = (time.perf_counter() - t0) / n
    emit("faults/point_disabled", off, f"n={n}")

    faults.install(FaultPlan.from_string(
        "compile:delay@p=0.01;transport.send:drop@p=0.01"))
    t0 = time.perf_counter()
    for _ in range(n):
        faults.fault_point("study.persist", line)
    armed = (time.perf_counter() - t0) / n
    faults.uninstall()
    emit("faults/point_armed_other_sites", armed,
         f"x{armed / max(off, 1e-12):.1f} vs disabled")

    # the seam in situ: disk-cache store+lookup throughput, plan off
    rounds = 200 if quick else 1000
    with tempfile.TemporaryDirectory() as d:
        cache = DiskEvaluationCache(path=d)
        t0 = time.perf_counter()
        for i in range(rounds):
            cache.store(("bench", i), {"v": i})
            cache.lookup(("bench", i))
        dt = (time.perf_counter() - t0) / rounds
    emit("faults/disk_cache_roundtrip_off", dt, f"rounds={rounds}")


def main() -> None:
    bench_samplers()
    bench_builder_throughput()
    bench_estimator_fidelity()
    bench_hil_pipeline()
    bench_preprocessing_joint()
    bench_explorer_facade()
    bench_sweep_engine()
    bench_cascade()
    bench_async_scheduler()
    bench_kernel_tune()
    bench_serve()
    bench_faults()
    bench_parallel_engine()
    bench_process_engine()
    bench_remote_engine()


if __name__ == "__main__":
    import sys

    if len(sys.argv) in (3, 4) and sys.argv[1] == "--parallel-config":
        # subprocess mode for bench_parallel_engine / bench_process_engine:
        # emit one JSON line (optional third arg: disk-cache store dir)
        import json

        print(json.dumps(run_parallel_config(
            sys.argv[2], sys.argv[3] if len(sys.argv) == 4 else None)))
    elif len(sys.argv) == 4 and sys.argv[1] == "--cascade-config":
        # subprocess mode for bench_cascade: emit one JSON line
        import json

        print(json.dumps(run_cascade_config(sys.argv[2], int(sys.argv[3]))))
    elif len(sys.argv) == 3 and sys.argv[1] == "--kernel-tune-config":
        # subprocess mode for bench_kernel_tune: emit one JSON line
        import json

        print(json.dumps(run_kernel_tune_config(sys.argv[2])))
    elif len(sys.argv) == 5 and sys.argv[1] == "--serve-explore":
        # subprocess mode for bench_serve: emit one JSON line
        import json

        print(json.dumps(run_serve_explore(sys.argv[2], sys.argv[3],
                                           int(sys.argv[4]))))
    elif "--quick" in sys.argv[1:]:
        # CI mode: the scheduler + cascade + kernel-tune + serve groups,
        # small sizes, so scheduler, screening, schedule-tuning, and
        # serving-hand-off regressions surface in every PR log
        bench_async_scheduler(quick=True)
        bench_cascade(quick=True)
        bench_kernel_tune(quick=True)
        bench_serve(quick=True)
        bench_faults(quick=True)
    else:
        main()
