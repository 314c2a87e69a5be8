"""Fidelity cascade: keep rules, staged screening, zero-cost proxies,
the `fidelity:` spec section, and the end-to-end determinism contract
(identical survivors / funnel / best trial across every backend and
schedule at a fixed seed)."""
import math

import jax
import jax.numpy as jnp
import pytest

from repro import Explorer
from repro.core.builder import ModelBuilder
from repro.core.space import parse_search_space
from repro.core.translate import sample_architecture
from repro.evaluation import (
    CascadeRunner,
    CriteriaRunner,
    EvaluationCache,
    Estimator,
    FidelityStage,
    FlopsEstimator,
    GradNormEstimator,
    KeepRule,
    OptimizationCriteria,
    ParamCountEstimator,
    SynFlowEstimator,
    constraint_violation,
    weighted_sum,
)
from repro.explorer.experiment import ExperimentError, ExperimentSpec
from repro.explorer.registry import ESTIMATORS
from repro.hwgen.generator import generate_call_count
from repro.search.study import HardConstraintViolated

# the canonical tiny space shared with the cross-backend parity matrix
from test_parity_matrix import CANONICAL_SPACE as TINY_SPACE

CASCADE_EXPERIMENT = {
    "name": "cascade-tiny",
    "search_space": TINY_SPACE,
    "sampler": {"name": "random", "seed": 7},
    "executor": {"backend": "serial"},
    "criteria": [{"estimator": "flops", "kind": "objective"}],
    "fidelity": {
        "generation": 8,
        "stages": [
            {"name": "zero_cost",
             "criteria": [{"estimator": "synflow", "kind": "objective",
                           "direction": "minimize"}],
             "keep": {"top_frac": 0.5}},
        ],
    },
    "budget": {"n_trials": 16},
}


def build_tiny_models(n=4, seed=0):
    from repro.search.samplers import RandomSampler
    from repro.search.study import Study

    space = parse_search_space(dict(TINY_SPACE))
    builder = ModelBuilder(space.input_shape, space.output_dim)
    study = Study(sampler=RandomSampler(seed=seed))
    return [builder.build(sample_architecture(space, study.ask()))
            for _ in range(n)]


class FixedEstimator(Estimator):
    def __init__(self, name, values):
        self.name = name
        self.values = dict(values)  # id(candidate) -> value

    def estimate(self, candidate, context=None):
        return self.values[id(candidate)]


# ---------------------------------------------------------------------------
# keep rules
# ---------------------------------------------------------------------------

def test_keep_rule_requires_exactly_one_field():
    with pytest.raises(ValueError, match="exactly one"):
        KeepRule()
    with pytest.raises(ValueError, match="exactly one"):
        KeepRule(top_k=2, top_frac=0.5)
    with pytest.raises(ValueError, match="top_k"):
        KeepRule(top_k=0)
    with pytest.raises(ValueError, match="top_frac"):
        KeepRule(top_frac=1.5)


def test_keep_rule_survivor_semantics():
    scored = [(0, 3.0), (1, 1.0), (2, 2.0), (3, 1.0)]
    # top_k ranks by (score, index): the tie at 1.0 keeps ask order
    assert KeepRule(top_k=2).survivors(scored) == [1, 3]
    # top_frac keeps ceil(frac * n), at least one
    assert KeepRule(top_frac=0.5).survivors(scored) == [1, 3]
    assert KeepRule(top_frac=0.01).survivors(scored) == [1]
    # threshold is per-candidate, cohort-independent
    assert KeepRule(threshold=2.0).survivors(scored) == [1, 2, 3]
    assert KeepRule(threshold=0.5).survivors(scored) == []


# ---------------------------------------------------------------------------
# cascade runner construction + screening
# ---------------------------------------------------------------------------

def test_cascade_validates_stage_structure():
    crit = [OptimizationCriteria(FlopsEstimator())]
    with pytest.raises(ValueError, match="at least one stage"):
        CascadeRunner([])
    with pytest.raises(ValueError, match="keep rule"):
        CascadeRunner([FidelityStage("screen", crit),
                       FidelityStage("final",
                                     [OptimizationCriteria(ParamCountEstimator())])])
    with pytest.raises(ValueError, match="must not have a keep rule"):
        CascadeRunner([FidelityStage("final", crit, keep=KeepRule(top_k=1))])
    with pytest.raises(ValueError, match="duplicate fidelity stage"):
        CascadeRunner([
            FidelityStage("s", crit, keep=KeepRule(top_k=1)),
            FidelityStage("s", [OptimizationCriteria(ParamCountEstimator())]),
        ])
    # estimator names must be distinct across the WHOLE cascade
    with pytest.raises(ValueError, match="share estimator name"):
        CascadeRunner([
            FidelityStage("screen", crit, keep=KeepRule(top_k=1)),
            FidelityStage("final", [OptimizationCriteria(FlopsEstimator())]),
        ])


def test_single_stage_cascade_is_flat_runner():
    models = build_tiny_models(3)
    criteria = [OptimizationCriteria(FlopsEstimator()),
                OptimizationCriteria(ParamCountEstimator(), weight=0.1)]
    flat = CriteriaRunner(criteria)
    cascade = CascadeRunner([FidelityStage("final", criteria)])
    for m in models:
        assert cascade.evaluate(m) == flat.evaluate(m)
        assert cascade.evaluate_multi(m) == flat.evaluate_multi(m)
    result = cascade.screen_cohort(models)
    assert result.promoted == [0, 1, 2]
    assert result.screened == {} and result.infeasible == {}


def test_screen_cohort_promotes_screens_and_rejects():
    models = build_tiny_models(4)
    proxy = FixedEstimator("proxy", {id(m): float(i)
                                     for i, m in enumerate(models)})
    gate = FixedEstimator("gate", {id(m): float(i)
                                   for i, m in enumerate(models)})
    runner = CascadeRunner([
        FidelityStage("screen", [
            OptimizationCriteria(gate, kind="hard_constraint", limit=2.5),
            OptimizationCriteria(proxy),
        ], keep=KeepRule(top_k=2)),
        FidelityStage("final", [OptimizationCriteria(FlopsEstimator())]),
    ])
    result = runner.screen_cohort(models)
    # index 3 violates the hard gate (3.0 > 2.5) before ranking
    assert result.infeasible.keys() == {3}
    stage, exc = result.infeasible[3]
    assert stage == "screen" and isinstance(exc, HardConstraintViolated)
    # of the feasible 0..2, top_k=2 by proxy score keeps 0 and 1
    assert result.promoted == [0, 1]
    assert result.screened == {2: "screen"}
    assert result.counts == {"promoted": 2, "screened": 1, "infeasible": 1}


# ---------------------------------------------------------------------------
# satellite: direction-aware constraints ("val_accuracy >= 0.9")
# ---------------------------------------------------------------------------

def test_maximize_hard_constraint_violates_below_limit():
    models = build_tiny_models(1)
    acc = FixedEstimator("val_accuracy", {id(models[0]): 0.8})
    runner = CriteriaRunner([
        OptimizationCriteria(acc, kind="hard_constraint",
                             direction="maximize", limit=0.9),
        OptimizationCriteria(FlopsEstimator()),
    ])
    with pytest.raises(HardConstraintViolated):
        runner.evaluate(models[0])
    # the same value SATISFIES a minimize constraint with the same limit
    runner_min = CriteriaRunner([
        OptimizationCriteria(FixedEstimator("v", {id(models[0]): 0.8}),
                             kind="hard_constraint", limit=0.9),
        OptimizationCriteria(FlopsEstimator()),
    ])
    runner_min.evaluate(models[0])


def test_maximize_soft_constraint_hinge_direction():
    c = OptimizationCriteria(FixedEstimator("acc", {}),
                             kind="soft_constraint",
                             direction="maximize", limit=0.9)
    assert constraint_violation(c, 0.8) > 0.0   # below the floor: violated
    assert constraint_violation(c, 0.95) < 0.0  # above: satisfied
    # hinge enters weighted_sum only when violated
    assert weighted_sum({"acc": 0.95}, [c]) == 0.0
    assert weighted_sum({"acc": 0.8}, [c]) > 0.0


def test_staged_iteration_shared_between_paths():
    """Hard constraints run before objectives in BOTH evaluate paths —
    the expensive objective estimator must never run on a violator."""
    models = build_tiny_models(1)

    class Exploding(Estimator):
        name = "expensive"

        def estimate(self, candidate, context=None):
            raise AssertionError("objective ran despite hard violation")

    runner = CriteriaRunner([
        OptimizationCriteria(Exploding()),
        OptimizationCriteria(FixedEstimator("gate", {id(models[0]): 1.0}),
                             kind="hard_constraint", limit=0.5),
    ])
    with pytest.raises(HardConstraintViolated):
        runner.evaluate(models[0])
    with pytest.raises(HardConstraintViolated):
        runner.evaluate_multi(models[0])


# ---------------------------------------------------------------------------
# zero-cost proxies
# ---------------------------------------------------------------------------

def test_proxies_registered_as_estimators():
    assert isinstance(ESTIMATORS.get("synflow"), type)
    assert ESTIMATORS.get("synflow") is SynFlowEstimator
    assert ESTIMATORS.get("grad_norm") is GradNormEstimator


def test_proxies_deterministic_and_capacity_ordered():
    models = build_tiny_models(4, seed=3)
    syn, gn = SynFlowEstimator(), GradNormEstimator()
    for m in models:
        assert syn.estimate(m) == SynFlowEstimator().estimate(m)
        assert gn.estimate(m) == GradNormEstimator().estimate(m)
        assert math.isfinite(syn.estimate(m)) and syn.estimate(m) > 0.0


def test_proxies_never_touch_the_xla_generator():
    models = build_tiny_models(2)
    before = generate_call_count()
    for m in models:
        SynFlowEstimator().estimate(m)
        GradNormEstimator().estimate(m)
    assert generate_call_count() == before


def test_synflow_conservation_identity_matches_autodiff():
    """The one-forward fast path equals the classical |θ ⊙ ∂R/∂θ|
    backward-pass formulation on the same probe."""
    for m in build_tiny_models(3, seed=5):
        syn = SynFlowEstimator()
        probe, _ = SynFlowEstimator._probe_params(m)
        x = jnp.ones((syn.batch, m.input_shape[-1], m.input_shape[0]),
                     jnp.float32)

        def saliency(p):
            return jnp.sum(SynFlowEstimator._apply_net(m, p, x))

        grads = jax.grad(saliency)(probe)
        total = sum(float(jnp.sum(jnp.abs(g * p)))
                    for g, p in zip(jax.tree_util.tree_leaves(grads),
                                    jax.tree_util.tree_leaves(probe)))
        assert syn._score(m) == pytest.approx(math.log1p(total), rel=1e-5)


def test_proxy_scores_ride_the_disk_cache(tmp_path):
    model = build_tiny_models(1)[0]
    store = str(tmp_path / "cache")
    first = SynFlowEstimator(cache=EvaluationCache(disk=store))
    score = first.estimate(model)

    class Broken(SynFlowEstimator):
        def _score(self, candidate):
            raise AssertionError("disk tier missed: proxy recomputed")

    second = Broken(cache=EvaluationCache(disk=store))
    assert second.estimate(model) == score


def test_proxy_batch_env_knob(monkeypatch):
    monkeypatch.setenv("REPRO_PROXY_BATCH", "5")
    assert SynFlowEstimator().batch == 5
    monkeypatch.delenv("REPRO_PROXY_BATCH")
    assert SynFlowEstimator(batch=3).batch == 3


# ---------------------------------------------------------------------------
# fidelity spec validation
# ---------------------------------------------------------------------------

def make_cascade_experiment(tmp_path, **overrides):
    import copy

    raw = copy.deepcopy(CASCADE_EXPERIMENT)
    raw["report_dir"] = str(tmp_path / "results")
    raw.update(copy.deepcopy(overrides))
    return raw


def test_fidelity_spec_round_trips(tmp_path):
    spec = ExperimentSpec.from_dict(make_cascade_experiment(tmp_path))
    again = ExperimentSpec.from_dict(spec.to_dict())
    assert again.to_dict()["fidelity"] == spec.to_dict()["fidelity"]
    assert spec.fidelity.generation == 8
    assert spec.fidelity.stages[0].keep.top_frac == 0.5


@pytest.mark.parametrize("mutation, message", [
    ({"fidelity": {"generation": 8, "stages": []}}, "non-empty list"),
    ({"fidelity": {"stages": [{"name": "final", "criteria": [
        {"estimator": "synflow"}], "keep": {"top_k": 1}}]}}, "reserved"),
    ({"fidelity": {"stages": [{"name": "s", "criteria": [
        {"estimator": "synflow"}],
        "keep": {"top_k": 1, "top_frac": 0.5}}]}}, "exactly one"),
    ({"fidelity": {"stages": [{"name": "s", "criteria": [
        {"estimator": "synflow"}], "keep": {"bogus": 1}}]}}, "unknown"),
    ({"fidelity": {"stages": [{"name": "s", "criteria": [
        {"estimator": "flops"}], "keep": {"top_k": 1}}]}},
     "share estimator name|flops"),
])
def test_fidelity_spec_rejects_bad_configs(tmp_path, mutation, message):
    with pytest.raises((ExperimentError, ValueError), match=message):
        ExperimentSpec.from_dict(make_cascade_experiment(tmp_path, **mutation))


# ---------------------------------------------------------------------------
# satellite: fixed-seed determinism across backends and schedules
# ---------------------------------------------------------------------------

def run_cascade(tmp_path, backend, schedule, n_workers=2):
    raw = make_cascade_experiment(
        tmp_path,
        executor={"backend": backend,
                  "n_workers": 1 if backend == "serial" else n_workers},
        schedule={"mode": schedule},
    )
    explorer = Explorer.from_dict(raw)
    report = explorer.run(save_report=False)
    study = explorer.study
    screened = sorted(t.number for t in study.trials
                      if t.user_attrs.get("fidelity_stage") == "zero_cost")
    promoted = sorted(t.number for t in study.trials
                      if t.user_attrs.get("fidelity_stage") == "promoted")
    return {
        "funnel": report.fidelity["funnel"],
        "screened": screened,
        "promoted": promoted,
        "best_number": report.best["number"],
        "best_values": report.best["values"],
        "states": report.states,
    }


@pytest.mark.parametrize("backend", ("serial", "thread", "process"))
@pytest.mark.parametrize("schedule", ("batch", "sliding_window"))
def test_cascade_deterministic_across_backends(tmp_path, backend, schedule):
    reference = run_cascade(tmp_path / "ref", "serial", "batch")
    assert reference["funnel"]["asked"] == 16
    assert reference["funnel"]["screened"] == 8
    assert reference["funnel"]["promoted"] == 8
    assert run_cascade(tmp_path / "run", backend, schedule) == reference


def test_cascade_counts_only_the_compiles_of_its_run(tmp_path, monkeypatch):
    """``compiled`` counts the generator calls the run made, not the ones
    its process made before it (an earlier study, another test)."""
    from repro.hwgen import generator

    monkeypatch.setattr(generator, "_generate_count", generator._generate_count + 5)
    report = Explorer.from_dict(make_cascade_experiment(tmp_path)).run(save_report=False)
    assert report.fidelity["funnel"]["compiled"] == 0


def test_cascade_report_funnel_and_spearman(tmp_path):
    raw = make_cascade_experiment(tmp_path)
    explorer = Explorer.from_dict(raw)
    report = explorer.run(save_report=False)
    funnel = report.fidelity["funnel"]
    assert funnel["asked"] == 16
    assert funnel["screened"] + funnel["promoted"] + funnel["infeasible"] == 16
    # the final stage here is analytic — nothing may compile at all
    assert funnel["compiled"] == 0
    rho = report.fidelity["spearman"]["zero_cost"]
    assert rho is None or -1.0 <= rho <= 1.0
    # screened trials carry the stage score attr for the correlation
    scored = [t for t in explorer.study.trials
              if "fidelity_score:zero_cost" in t.user_attrs]
    assert len(scored) == 16
    assert report.to_dict()["fidelity"]["funnel"] == funnel
