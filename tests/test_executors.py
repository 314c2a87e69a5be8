"""Executor backends: serial/thread/process parity, process merge-back,
error semantics.  Objectives are module-level so they pickle across the
process boundary (spawn workers re-import this module)."""
import threading

import pytest

from repro.search import (
    GridSampler,
    NSGA2Sampler,
    ParallelStudy,
    ProcessExecutor,
    RandomSampler,
    RegularizedEvolutionSampler,
    SerialExecutor,
    Study,
    ThreadExecutor,
    TPESampler,
    TrialPruned,
    TrialState,
    make_executor,
)
from repro.search.study import HardConstraintViolated

BACKENDS = ("serial", "thread", "process")


def _quadratic(trial):
    x = trial.suggest_float("x", -4.0, 4.0)
    y = trial.suggest_float("y", -4.0, 4.0)
    return (x - 1.0) ** 2 + (y + 0.5) ** 2


def _fingerprint(study):
    return [(t.number, t.params["x"], t.params["y"], t.values[0]) for t in study.trials]


# ---------------------------------------------------------------------------
# parity: identical trials and best value at fixed seed, any backend
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", BACKENDS)
def test_backend_parity_with_serial_study(backend):
    ref = Study(sampler=RandomSampler(seed=7))
    ref.optimize(_quadratic, 12)
    s = ParallelStudy(sampler=RandomSampler(seed=7), n_workers=3, backend=backend)
    s.optimize(_quadratic, 12)
    assert _fingerprint(s) == _fingerprint(ref)
    assert s.best_trial.number == ref.best_trial.number
    assert s.best_trial.values == ref.best_trial.values


def test_process_backend_worker_count_independent():
    runs = {}
    for w in (1, 3):
        s = ParallelStudy(sampler=RandomSampler(seed=11), n_workers=w, backend="process")
        s.optimize(_quadratic, 9)
        runs[w] = _fingerprint(s)
    assert runs[1] == runs[3]


def _grid_obj(trial):
    # suggest in NON-sorted name order to exercise the radix bookkeeping
    b = trial.suggest_categorical("b", ["p", "q", "r"])
    a = trial.suggest_int("a", 0, 1)
    return float(a) + (0.0 if b == "p" else 1.0)


@pytest.mark.parametrize("backend", ("thread", "process"))
def test_grid_parity_across_backends(backend):
    ref = Study(sampler=GridSampler())
    ref.optimize(_grid_obj, 6)
    cover = lambda s: sorted((t.params["a"], t.params["b"]) for t in s.trials)
    s = ParallelStudy(sampler=GridSampler(), n_workers=3, backend=backend)
    s.optimize(_grid_obj, 6)
    assert len(set(cover(s))) == 6  # full 2x3 product, no repeats
    assert cover(s) == cover(ref)


@pytest.mark.parametrize("make_sampler", [
    lambda: TPESampler(seed=5, n_startup=4),
    lambda: RegularizedEvolutionSampler(seed=5, population=6),
    lambda: NSGA2Sampler(seed=5, population=6),
], ids=["tpe", "evolution", "nsga2"])
def test_population_samplers_thread_process_parity(make_sampler):
    """Population snapshots are taken at ask time under the study lock, so
    for a fixed n_workers the process backend replays exactly the
    threaded trajectory."""
    a = ParallelStudy(sampler=make_sampler(), n_workers=2, backend="thread")
    a.optimize(_quadratic, 14)
    b = ParallelStudy(sampler=make_sampler(), n_workers=2, backend="process")
    b.optimize(_quadratic, 14)
    assert _fingerprint(a) == _fingerprint(b)


# ---------------------------------------------------------------------------
# process backend: state + attribute merge-back, storage
# ---------------------------------------------------------------------------

def _special_states_obj(trial):
    x = trial.suggest_int("i", 0, 100)
    if trial.number % 3 == 0:
        raise TrialPruned()
    if trial.number % 3 == 1:
        raise HardConstraintViolated("n_params", 10.0, 1.0)
    trial.report(1, float(x))
    trial.set_user_attr("echo", trial.number)
    return float(x)


def test_process_backend_records_special_states(tmp_path):
    path = str(tmp_path / "s.jsonl")
    s = ParallelStudy(sampler=RandomSampler(seed=0), n_workers=3,
                      backend="process", storage=path)
    s.optimize(_special_states_obj, 12)
    states = [t.state for t in s.trials]
    assert states.count(TrialState.PRUNED) == 4
    assert states.count(TrialState.INFEASIBLE) == 4
    assert states.count(TrialState.COMPLETE) == 4
    for t in s.trials:
        assert "i" in t.params and "i" in t.distributions  # merged back
        if t.state == TrialState.INFEASIBLE:
            assert t.user_attrs["violated"]["name"] == "n_params"
        if t.state == TrialState.COMPLETE and t.number > 0:
            assert t.user_attrs["echo"] == t.number
            assert t.intermediate == {1: t.values[0]}
    # storage got every trial exactly once, in trial order
    s2 = Study(storage=path)
    assert [t.number for t in s2.trials] == list(range(12))


def _boom_obj(trial):
    x = trial.suggest_int("i", 0, 100)
    if trial.number == 3:
        raise ValueError("boom")
    return float(x)


def test_process_backend_drains_batch_on_uncaught_error(tmp_path):
    path = str(tmp_path / "s.jsonl")
    s = ParallelStudy(sampler=RandomSampler(seed=0), n_workers=4,
                      backend="process", storage=path)
    with pytest.raises(ValueError, match="boom"):
        s.optimize(_boom_obj, 12)
    assert all(t.state != TrialState.RUNNING for t in s.trials)
    assert s.trials[3].state == TrialState.FAIL
    assert "boom" in s.trials[3].user_attrs["error"]
    completed = [t for t in s.trials if t.state == TrialState.COMPLETE]
    assert completed  # siblings of the failing trial were preserved
    s2 = Study(storage=path)
    assert len(s2.trials) == len(s.trials)  # every told trial persisted


def _unpicklable_boom_obj(trial):
    trial.suggest_int("i", 0, 3)
    if trial.number == 2:
        e = ValueError("nope")
        e.bad = threading.Lock()  # cannot cross the process boundary
        raise e
    return 1.0


def test_process_backend_wraps_unpicklable_exception():
    s = ParallelStudy(sampler=RandomSampler(seed=0), n_workers=2, backend="process")
    with pytest.raises(RuntimeError, match="nope"):
        s.optimize(_unpicklable_boom_obj, 4)
    assert s.trials[2].state == TrialState.FAIL


def _catchable_obj(trial):
    trial.suggest_int("i", 0, 3)
    if trial.number % 2 == 1:
        raise KeyError("missing")
    return 0.0


def test_process_backend_catch_maps_to_fail():
    s = ParallelStudy(sampler=RandomSampler(seed=0), n_workers=2, backend="process")
    s.optimize(_catchable_obj, 6, catch=(KeyError,))
    fails = [t for t in s.trials if t.state == TrialState.FAIL]
    assert len(fails) == 3
    assert all("missing" in t.user_attrs["error"] for t in fails)


# ---------------------------------------------------------------------------
# executor surface
# ---------------------------------------------------------------------------

def test_make_executor_resolves_names_and_instances():
    assert isinstance(make_executor("serial"), SerialExecutor)
    assert isinstance(make_executor("thread"), ThreadExecutor)
    assert isinstance(make_executor("process"), ProcessExecutor)
    ex = ThreadExecutor()
    assert make_executor(ex) is ex
    # resolution now goes through the explorer registry: the error lists
    # every registered backend, including plugins
    with pytest.raises(ValueError, match="unknown executor.*serial"):
        make_executor("gpu-cluster")


def test_executor_instance_reusable_across_optimize_calls():
    ex = ThreadExecutor()
    s = ParallelStudy(sampler=RandomSampler(seed=1), n_workers=2, backend=ex)
    s.optimize(_quadratic, 4)
    s.optimize(_quadratic, 4)  # restarted pool, same instance
    assert len(s.trials) == 8
    ref = Study(sampler=RandomSampler(seed=1))
    ref.optimize(_quadratic, 8)
    assert _fingerprint(s) == _fingerprint(ref)


# ---------------------------------------------------------------------------
# one process per chip
# ---------------------------------------------------------------------------

def test_process_backend_refuses_while_parent_holds_chip(monkeypatch):
    from repro.search import executors

    monkeypatch.setattr(executors, "held_accelerator", lambda: "tpu")
    s = ParallelStudy(sampler=RandomSampler(seed=0), n_workers=2, backend="process")
    with pytest.raises(executors.OneProcessPerChipError, match="one process per chip"):
        s.optimize(_quadratic, 4)


def test_worker_without_device_fails_study_not_trial(monkeypatch):
    """A worker whose trial failed because it cannot reach its device
    reports the rule, even when ``catch`` would have swallowed the error
    into a FAILed trial."""
    from repro.search import executors

    monkeypatch.setattr(executors, "_unreachable_device",
                        lambda: "TPU already in use by pid 1")
    study = Study(sampler=RandomSampler(seed=0))
    trial = study.ask()
    plan = study.sampler.detached(study, trial)
    number = 1  # _catchable_obj raises KeyError on odd trial numbers
    res = executors.run_detached_trial(_catchable_obj, number, plan, (KeyError,))
    assert res.state == TrialState.FAIL
    assert isinstance(res.error, executors.OneProcessPerChipError)
    assert "TPU already in use" in str(res.error)
