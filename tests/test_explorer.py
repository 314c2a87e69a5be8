"""Explorer facade: registries, declarative ExperimentSpec, and the
end-to-end run() contract (hand-wired parity at a fixed seed, report
fields, JSON artifact)."""
import json
import os

import pytest
import yaml

from repro import Explorer, ExperimentSpec
from repro.core.builder import ModelBuilder
from repro.core.space import parse_search_space
from repro.core.translate import sample_architecture
from repro.evaluation import (
    CriteriaRunner,
    Estimator,
    FlopsEstimator,
    OptimizationCriteria,
    ParamCountEstimator,
)
from repro.explorer.experiment import ExperimentError
from repro.explorer.registry import (
    ESTIMATORS,
    SAMPLERS,
    ExplorerError,
    UnknownComponentError,
    register,
)
from repro.search import Study, TPESampler

# the tiny conv1d space: 2 blocks, a handful of distributions — fast to
# sample, fast to build, no compilation needed for analytic criteria.
# Shared with the cross-backend parity matrix so every parity check in
# the suite runs the same spec.
from test_parity_matrix import CANONICAL_SPACE as TINY_SPACE

BASE_EXPERIMENT = {
    "name": "tiny",
    "search_space": TINY_SPACE,
    "sampler": {"name": "tpe", "seed": 0},
    "executor": {"backend": "serial"},
    "criteria": [
        {"estimator": "flops", "kind": "objective", "weight": 1.0},
        {"estimator": "n_params", "kind": "objective", "weight": 0.1},
    ],
    "budget": {"n_trials": 8},
}


def make_experiment(tmp_path, **overrides):
    raw = {**{k: (dict(v) if isinstance(v, dict) else v)
              for k, v in BASE_EXPERIMENT.items()},
           "report_dir": str(tmp_path / "results")}
    raw["criteria"] = [dict(c) for c in BASE_EXPERIMENT["criteria"]]
    raw.update(overrides)
    return raw


def hand_wired_study(n_trials=8, seed=0):
    space = parse_search_space(dict(TINY_SPACE))
    builder = ModelBuilder(space.input_shape, space.output_dim)
    flops, nparams = FlopsEstimator(), ParamCountEstimator()

    def objective(trial):
        arch = sample_architecture(space, trial)
        model = builder.build(arch)
        return flops.estimate(model) + 0.1 * nparams.estimate(model)

    study = Study(sampler=TPESampler(seed=seed))
    study.optimize(objective, n_trials)
    return study


# ---------------------------------------------------------------------------
# spec parsing + validation
# ---------------------------------------------------------------------------

def test_yaml_spec_round_trip(tmp_path):
    path = tmp_path / "exp.yaml"
    path.write_text(yaml.safe_dump(make_experiment(tmp_path)))
    spec = ExperimentSpec.from_yaml(str(path))
    d = spec.to_dict()
    spec2 = ExperimentSpec.from_dict(d)
    assert spec2.to_dict() == d  # stable fixpoint
    assert spec2.name == "tiny"
    assert spec2.sampler.name == "tpe" and spec2.sampler.options == {"seed": 0}
    assert spec2.executor.backend == "serial" and spec2.executor.n_workers == 1
    assert [c.estimator for c in spec2.criteria] == ["flops", "n_params"]
    assert spec2.budget.n_trials == 8
    assert json.dumps(d)  # fully JSON-able (picklable across process workers)


def test_search_space_file_ref_resolves_relative_to_experiment(tmp_path):
    (tmp_path / "spaces").mkdir()
    (tmp_path / "spaces" / "tiny.yaml").write_text(yaml.safe_dump(TINY_SPACE))
    raw = make_experiment(tmp_path, search_space={"file": "spaces/tiny.yaml"})
    path = tmp_path / "exp.yaml"
    path.write_text(yaml.safe_dump(raw))
    spec = ExperimentSpec.from_yaml(str(path))
    # the file ref comes back inlined: the spec is self-contained
    assert spec.search_space["input"] == [2, 64]
    assert spec.to_dict()["search_space"]["output"] == 3


def test_unknown_top_level_key_names_key_and_alternatives(tmp_path):
    raw = make_experiment(tmp_path)
    raw["sampler_seed"] = 3
    with pytest.raises(ExperimentError) as e:
        ExperimentSpec.from_dict(raw)
    assert "sampler_seed" in str(e.value)
    assert "'sampler'" in str(e.value)  # allowed keys are listed


def test_unknown_sampler_lists_registered_names(tmp_path):
    raw = make_experiment(tmp_path, sampler={"name": "anneal"})
    with pytest.raises(UnknownComponentError) as e:
        ExperimentSpec.from_dict(raw)
    msg = str(e.value)
    assert "anneal" in msg and "tpe" in msg and "random" in msg


def test_unknown_estimator_and_backend_list_alternatives(tmp_path):
    raw = make_experiment(tmp_path)
    raw["criteria"][0]["estimator"] = "flopz"
    with pytest.raises(UnknownComponentError, match="flopz.*flops"):
        ExperimentSpec.from_dict(raw)
    raw = make_experiment(tmp_path, executor={"backend": "ray"})
    with pytest.raises(UnknownComponentError, match="ray.*process"):
        ExperimentSpec.from_dict(raw)


def test_bad_component_kwarg_fails_at_parse_time(tmp_path):
    raw = make_experiment(tmp_path, sampler={"name": "tpe", "sed": 0})
    with pytest.raises(ExperimentError, match="sed"):
        ExperimentSpec.from_dict(raw)
    raw = make_experiment(tmp_path)
    raw["criteria"][0]["params"] = {"batchsize": 4}
    with pytest.raises(ExperimentError, match="batchsize"):
        ExperimentSpec.from_dict(raw)


def test_spec_requires_objective_and_rejects_duplicates(tmp_path):
    raw = make_experiment(tmp_path, criteria=[
        {"estimator": "n_params", "kind": "hard_constraint", "limit": 1e6}])
    with pytest.raises(ExperimentError, match="objective"):
        ExperimentSpec.from_dict(raw)
    raw = make_experiment(tmp_path, criteria=[
        {"estimator": "flops", "kind": "objective"},
        {"estimator": "flops", "kind": "objective", "weight": 0.5}])
    with pytest.raises(ExperimentError, match="flops"):
        ExperimentSpec.from_dict(raw)


def test_constraint_requires_limit_and_bad_kind_rejected(tmp_path):
    raw = make_experiment(tmp_path)
    raw["criteria"].append({"estimator": "activation_bytes", "kind": "soft_constraint"})
    with pytest.raises(ExperimentError, match="limit"):
        ExperimentSpec.from_dict(raw)
    raw = make_experiment(tmp_path)
    raw["criteria"][0]["kind"] = "goal"
    with pytest.raises(ExperimentError, match="goal"):
        ExperimentSpec.from_dict(raw)


# ---------------------------------------------------------------------------
# registries
# ---------------------------------------------------------------------------

def test_registry_plugin_registration_and_use_in_spec(tmp_path):
    @register("estimator", "test_depth_cost")
    class DepthCostEstimator(Estimator):
        name = "test_depth_cost"

        def __init__(self, scale=1.0):
            self.scale = scale

        def estimate(self, candidate, context=None):
            return self.scale * len(candidate.layers)

    assert "test_depth_cost" in ESTIMATORS
    raw = make_experiment(tmp_path, criteria=[
        {"estimator": "test_depth_cost", "kind": "objective",
         "params": {"scale": 2.0}}])
    report = Explorer.from_dict(raw).run(save_report=False)
    assert report.best is not None
    # depth is constant in the tiny space: every candidate scores 2 * n_layers
    assert report.best["values"][0] == report.criteria_values["test_depth_cost"] * 1.0


def test_registry_rejects_shadowing_but_allows_reregistration():
    sampler = SAMPLERS.get("random")
    SAMPLERS.register("random", sampler)  # same object: no-op
    with pytest.raises(ExplorerError, match="already registered"):
        SAMPLERS.register("random", object())


# ---------------------------------------------------------------------------
# end-to-end run(): hand-wired parity, report fields, artifact
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", ("serial", "process"))
def test_run_reproduces_hand_wired_quickstart(tmp_path, backend):
    """The facade composes exactly the wiring the quickstart builds by
    hand, so at a fixed seed it must find the identical best trial — on
    the in-process backend and across the process boundary (detached
    sampling plans)."""
    ref = hand_wired_study(n_trials=8, seed=0)
    raw = make_experiment(
        tmp_path, executor={"backend": backend, "n_workers": 1})
    explorer = Explorer.from_dict(raw)
    report = explorer.run()

    assert report.best["number"] == ref.best_trial.number
    assert report.best["values"] == list(ref.best_trial.values)

    # report integrity
    assert report.n_trials == 8
    assert report.states == {"complete": 8}
    assert report.backend == backend
    assert report.directions == ["minimize"]
    assert set(report.criteria_values) == {"flops", "n_params"}
    assert report.best["values"][0] == pytest.approx(
        report.criteria_values["flops"] + 0.1 * report.criteria_values["n_params"])
    assert report.best["signature"].startswith("conv1d(")
    assert report.pareto_front  # 2 objectives -> trade-off surface reported
    assert report.wall_clock_s > 0
    assert report.toolchain["jax"] not in ("", "unavailable")

    # JSON artifact under report_dir
    assert report.artifact and os.path.exists(report.artifact)
    with open(report.artifact) as f:
        persisted = json.load(f)
    assert persisted["experiment"] == "tiny"
    assert persisted["best"] == report.best

    # the winning architecture rebuilds into a runnable model
    model = explorer.best_model()
    assert model.n_params > 0


def test_multi_objective_rejects_soft_constraints(tmp_path):
    """evaluate_multi only runs hard constraints + objectives, so a
    soft constraint under scalarize:false would be silently ignored —
    the spec must refuse it."""
    raw = make_experiment(tmp_path, scalarize=False)
    raw["criteria"].append({"estimator": "activation_bytes",
                            "kind": "soft_constraint", "limit": 1e9})
    with pytest.raises(ExperimentError, match="soft"):
        ExperimentSpec.from_dict(raw)


def test_plugin_executor_resolves_through_make_executor():
    from repro.search import BaseExecutor, make_executor
    from repro.search.executors import SerialExecutor

    @register("executor", "test_inline")
    class InlineExecutor(SerialExecutor):
        name = "test_inline"

    assert isinstance(make_executor("test_inline"), InlineExecutor)
    assert isinstance(make_executor("test_inline"), BaseExecutor)


def test_report_artifact_field_round_trips(tmp_path):
    report = Explorer.from_dict(make_experiment(tmp_path)).run()
    with open(report.artifact) as f:
        assert json.load(f)["artifact"] == report.artifact


def test_multi_objective_mode_reports_pareto_front(tmp_path):
    raw = make_experiment(tmp_path, scalarize=False, name="tiny-mo")
    raw["sampler"] = {"name": "random", "seed": 1}
    report = Explorer.from_dict(raw).run(save_report=False)
    assert report.directions == ["minimize", "minimize"]
    front = report.pareto_front
    assert front
    for entry in front:
        assert len(entry["values"]) == 2


def test_persistence_resume_counts_against_budget(tmp_path):
    storage = str(tmp_path / "study.jsonl")
    raw = make_experiment(tmp_path, persistence=storage,
                          budget={"n_trials": 5})
    r1 = Explorer.from_dict(raw).run(save_report=False)
    assert r1.n_trials == 5
    # a re-run resumes the stored trials and only tops up to the budget
    raw2 = make_experiment(tmp_path, persistence=storage,
                           budget={"n_trials": 7})
    r2 = Explorer.from_dict(raw2).run(save_report=False)
    assert r2.n_trials == 7


def test_rerun_in_same_process_gets_fresh_objective_state(tmp_path):
    # Two runs of the SAME spec in one process must not share pipeline
    # state: the report reads cumulative cache/tuner counters from the
    # objective's per-process state, so inheriting run 1's state would
    # attribute its work (e.g. kernel tunes) to run 2.  Disk-tier values
    # still flow between runs — only the counters/instances are fresh.
    from repro.explorer.explorer import SpecObjective

    raw = make_experiment(tmp_path, cache={"dir": str(tmp_path / "cache")})
    e1, e2 = Explorer.from_dict(raw), Explorer.from_dict(raw)
    r1 = e1.run(save_report=False)
    r2 = e2.run(save_report=False)
    assert r1.best["number"] == r2.best["number"]
    assert e1._objective.run_token != e2._objective.run_token
    assert e1._objective.cache is not e2._objective.cache
    # run 2's report counts only its own lookups, not run 1's as well
    assert r2.cache["misses"] <= r1.cache["misses"]
    # same token -> same state (what keeps per-worker memoization alive
    # across submissions within one run); run 1's entry was evicted
    spec_dict = e2._objective.spec_dict
    token = e2._objective.run_token
    assert (SpecObjective(spec_dict, token)._state()
            is e2._objective._state())


# ---------------------------------------------------------------------------
# satellite fixes: criteria validation survives -O, duplicate detection
# ---------------------------------------------------------------------------

def test_criteria_kind_and_direction_raise_value_error():
    est = FlopsEstimator()
    with pytest.raises(ValueError, match="goal"):
        OptimizationCriteria(est, kind="goal")
    with pytest.raises(ValueError, match="sideways"):
        OptimizationCriteria(est, direction="sideways")
    with pytest.raises(ValueError, match="limit"):
        OptimizationCriteria(est, kind="hard_constraint")


def test_criteria_runner_rejects_duplicate_estimator_names():
    a, b = FlopsEstimator(), FlopsEstimator()
    with pytest.raises(ValueError) as e:
        CriteriaRunner([
            OptimizationCriteria(a, kind="objective"),
            OptimizationCriteria(b, kind="soft_constraint", limit=1.0),
        ])
    msg = str(e.value)
    assert "flops" in msg
    assert "objective" in msg and "soft_constraint" in msg  # both offenders named


# ---------------------------------------------------------------------------
# satellite fix: disk-cache toolchain salt
# ---------------------------------------------------------------------------

def test_canonical_key_salted_with_toolchain_versions(tmp_path):
    import jax

    from repro.evaluation import DiskEvaluationCache
    from repro.evaluation import disk_cache as dc

    ck = dc.canonical_key(("latency_s", "host_cpu", 2, "sig"))
    rec = json.loads(ck)
    assert rec["toolchain"]["jax"] == jax.__version__
    assert rec["toolchain"]["jaxlib"] not in ("", None)
    assert rec["key"] == ["latency_s", "host_cpu", 2, "sig"]

    # same toolchain: values round-trip between instances
    store = DiskEvaluationCache(str(tmp_path / "store"))
    assert store.store(("k",), 1.5)
    assert DiskEvaluationCache(str(tmp_path / "store")).lookup(("k",)) == (True, 1.5)

    # a different toolchain must structurally miss the persisted entry
    old = dc._TOOLCHAIN
    try:
        dc._TOOLCHAIN = {"jax": "0.0.0-other", "jaxlib": "0.0.0-other"}
        fresh = DiskEvaluationCache(str(tmp_path / "store"))
        assert fresh.lookup(("k",)) == (False, None)
    finally:
        dc._TOOLCHAIN = old


def test_canonical_key_salted_with_device(tmp_path):
    """A value made on one platform never answers on another: the salt
    carries the backend platform and device_kind, and the artifact store
    keys through the same salt."""
    import jax

    from repro.evaluation import DiskEvaluationCache
    from repro.evaluation import disk_cache as dc
    from repro.evaluation.artifact_store import ArtifactStore

    device = jax.devices()[0]
    salt = json.loads(dc.canonical_key(("k",)))["toolchain"]
    assert salt["platform"] == device.platform
    assert salt["device_kind"] == device.device_kind

    store = DiskEvaluationCache(str(tmp_path / "store"))
    assert store.store(("k",), 1.5)
    cpu_artifact_key = ArtifactStore.canonical(("k",))
    old = dc._TOOLCHAIN
    try:
        dc._TOOLCHAIN = {**old, "platform": "tpu", "device_kind": "TPU v5 lite"}
        assert DiskEvaluationCache(str(tmp_path / "store")).lookup(("k",)) \
            == (False, None)
        assert ArtifactStore.canonical(("k",)) != cpu_artifact_key
    finally:
        dc._TOOLCHAIN = old


# ---------------------------------------------------------------------------
# schedule spec: validation + wiring into the study
# ---------------------------------------------------------------------------

def test_schedule_spec_validation(tmp_path):
    raw = make_experiment(tmp_path, schedule={"mode": "eventually"})
    with pytest.raises(ExperimentError, match="mode.*auto.*batch.*sliding_window"):
        ExperimentSpec.from_dict(raw)
    raw = make_experiment(tmp_path, schedule={"tell_order": "sometimes"})
    with pytest.raises(ExperimentError, match="tell_order"):
        ExperimentSpec.from_dict(raw)
    raw = make_experiment(tmp_path, schedule={"window": 0})
    with pytest.raises(ExperimentError, match="window"):
        ExperimentSpec.from_dict(raw)
    raw = make_experiment(tmp_path, schedule={"modus": "batch"})
    with pytest.raises(ExperimentError, match="unknown key"):
        ExperimentSpec.from_dict(raw)
    # bare string shorthand selects the mode
    spec = ExperimentSpec.from_dict(make_experiment(tmp_path, schedule="batch"))
    assert spec.schedule.mode == "batch"
    assert spec.schedule.tell_order == "trial" and spec.schedule.window is None


def test_explorer_wires_schedule_and_timeout(tmp_path, monkeypatch):
    from repro.search import ParallelStudy

    captured = {}
    orig = ParallelStudy.optimize

    def spy(self, objective, n_trials, **kw):
        captured.update(kw, n_trials=n_trials)
        return orig(self, objective, n_trials, **kw)

    monkeypatch.setattr(ParallelStudy, "optimize", spy)
    raw = make_experiment(
        tmp_path,
        sampler={"name": "random", "seed": 0},
        schedule={"mode": "sliding_window", "tell_order": "completion",
                  "window": 2},
        budget={"n_trials": 4, "timeout_s": 120.0},
    )
    explorer = Explorer.from_dict(raw)
    report = explorer.run(save_report=False)
    assert captured["timeout_s"] == 120.0 and captured["n_trials"] == 4
    assert explorer.study.default_schedule == "sliding_window"
    assert explorer.study.default_tell_order == "completion"
    assert explorer.study.default_window == 2
    assert report.schedule == {"mode": "sliding_window",
                               "tell_order": "completion", "window": 2}
    assert report.n_trials == 4


def test_facade_sliding_window_matches_batch_best_trial(tmp_path):
    def run(mode):
        raw = make_experiment(
            tmp_path,
            sampler={"name": "random", "seed": 11},
            executor={"backend": "thread", "n_workers": 3},
            schedule={"mode": mode, "tell_order": "completion"},
            budget={"n_trials": 10},
        )
        return Explorer.from_dict(raw).run(save_report=False)

    batch, sliding = run("batch"), run("sliding_window")
    assert batch.best is not None
    assert sliding.best["number"] == batch.best["number"]
    assert sliding.best["values"] == batch.best["values"]
