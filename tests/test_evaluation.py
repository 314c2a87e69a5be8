"""Evaluation API: staged criteria, scalarization, HIL estimators."""
import jax
import jax.numpy as jnp
import pytest

from repro.core.builder import ModelBuilder
from repro.core.space import parse_search_space
from repro.core.translate import sample_architecture
from repro.evaluation import (
    CompiledLatencyEstimator,
    CriteriaRunner,
    Estimator,
    FlopsEstimator,
    OptimizationCriteria,
    ParamCountEstimator,
)
from repro.search import HardConstraintViolated, RandomSampler, Study

SPACE = parse_search_space("""
input: [2, 64]
output: 3
sequence:
  - block: "c"
    op_candidates: "conv1d"
  - block: "h"
    op_candidates: "linear"
default_op_params:
  conv1d:
    kernel_size: [3]
    out_channels: [4]
""")


def _model(seed=0):
    study = Study(sampler=RandomSampler(seed=seed))
    arch = sample_architecture(SPACE, study.ask())
    return ModelBuilder(SPACE.input_shape, SPACE.output_dim).build(arch)


class CountingEstimator(Estimator):
    def __init__(self, name, value):
        self.name = name
        self.value = value
        self.calls = 0

    def estimate(self, candidate, context=None):
        self.calls += 1
        return self.value


def test_hard_constraint_stops_staged_evaluation():
    hard = CountingEstimator("hard_cost", 100.0)
    obj = CountingEstimator("obj_cost", 1.0)
    runner = CriteriaRunner([
        OptimizationCriteria(obj, kind="objective"),
        OptimizationCriteria(hard, kind="hard_constraint", limit=10.0),
    ])
    with pytest.raises(HardConstraintViolated):
        runner.evaluate(_model())
    assert hard.calls == 1
    assert obj.calls == 0  # never evaluated — early termination


def test_weighted_sum_and_soft_constraint():
    obj = CountingEstimator("o", 2.0)
    soft = CountingEstimator("s", 15.0)  # limit 10 -> violation 0.5
    runner = CriteriaRunner([
        OptimizationCriteria(obj, kind="objective", weight=1.0),
        OptimizationCriteria(soft, kind="soft_constraint", limit=10.0, weight=2.0),
    ])
    score = runner.evaluate(_model())
    assert score == pytest.approx(2.0 + 2.0 * 0.5)


def test_soft_constraint_no_penalty_below_limit():
    soft = CountingEstimator("s", 5.0)
    runner = CriteriaRunner([OptimizationCriteria(soft, kind="soft_constraint", limit=10.0)])
    assert runner.evaluate(_model()) == 0.0


def test_custom_aggregator_injection():
    a = CountingEstimator("a", 3.0)
    b = CountingEstimator("b", 4.0)
    runner = CriteriaRunner(
        [OptimizationCriteria(a), OptimizationCriteria(b)],
        aggregator=lambda values, crit: max(values.values()),
    )
    assert runner.evaluate(_model()) == 4.0


def test_maximize_objective_sign():
    acc = CountingEstimator("acc", 0.9)
    runner = CriteriaRunner([OptimizationCriteria(acc, direction="maximize")])
    assert runner.evaluate(_model()) == pytest.approx(-0.9)


def test_analytical_estimators_match_model():
    m = _model()
    assert ParamCountEstimator().estimate(m) == float(m.n_params)
    assert FlopsEstimator().estimate(m) == float(m.flops)
    assert m.n_params > 0 and m.flops > 0


def test_hardware_in_the_loop_latency_on_host():
    est = CompiledLatencyEstimator("host_cpu", batch=2)
    m = _model()
    latency = est.estimate(m)
    assert 0 < latency < 5.0
    # cached by signature: second call is instant and identical
    assert est.estimate(m) == latency


def test_multiobjective_evaluation():
    a = CountingEstimator("a", 1.0)
    b = CountingEstimator("b", 2.0)
    runner = CriteriaRunner([
        OptimizationCriteria(a, kind="objective"),
        OptimizationCriteria(b, kind="objective"),
    ])
    assert runner.evaluate_multi(_model()) == (1.0, 2.0)


def test_wallclock_target_places_on_its_platform(monkeypatch):
    """host_cpu times CPU devices only: it asks for them by platform, and
    a process that cannot reach that platform gets an error, never
    another platform's clock."""
    from repro.hwgen import generator
    from repro.hwgen.targets import HOST_CPU, TargetSpec, get_target

    host = get_target("host_cpu")
    assert host.measurement == "wallclock" and host.platform == "cpu"
    assert all(d.platform == "cpu" for d in generator.target_devices(host))

    asked = []

    def no_such_platform(platform=None):
        asked.append(platform)
        raise RuntimeError(f"Unknown backend {platform}")

    monkeypatch.setattr(generator.jax, "devices", no_such_platform)
    with pytest.raises(generator.GeneratorError, match="measures on platform 'cpu'"):
        generator.target_devices(host)
    assert asked == ["cpu"]

    with pytest.raises(ValueError, match="must name the platform"):
        TargetSpec(name="board", chip=HOST_CPU, mesh_shape=(1, 1),
                   mesh_axes=("data", "model"), measurement="wallclock")
