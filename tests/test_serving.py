"""Serving path: seeded traffic replay, the continuous-batching engine,
batched prefill vs the token-by-token loop, the content-addressed
artifact store's zero-compile warm boot, and determinism of the
traffic-shaped estimators across backends."""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import Explorer
from repro.configs import get_arch
from repro.evaluation.serving import _ServingEstimator, resolve_serving
from repro.explorer.experiment import ExperimentError, ServingSpec
from repro.hwgen.generator import generate_call_count
from repro.launch.serve import RequestQueue, ServingEngine, rebuild_best
from repro.launch.traffic import (
    Request,
    ServingCosts,
    ServingSim,
    TrafficError,
    TrafficSpec,
)
from repro.models.lm import LM
from repro.nn.types import split
from test_parity_matrix import CANONICAL_SERVING, canonical_experiment


# ---------------------------------------------------------------------------
# TrafficSpec: seeded replay + validation
# ---------------------------------------------------------------------------

def test_traffic_fixed_seed_replays_bit_identically():
    spec = TrafficSpec.from_raw({
        "seed": 11, "n_requests": 40, "arrival": "poisson", "rate_rps": 20.0,
        "prompt_lens": {8: 3, 16: 1}, "gen_lens": [4, 8]})
    a, b = spec.requests(), TrafficSpec.from_raw(spec.to_dict()).requests()
    assert a == b  # dataclass equality: arrivals, lengths, token seeds
    # prompt tokens replay bit-identically too
    for ra, rb in zip(a, b):
        assert np.array_equal(ra.prompt_tokens(512), rb.prompt_tokens(512))
    # a different seed is a different stream
    other = TrafficSpec.from_raw({**spec.to_dict(), "seed": 12})
    assert other.requests() != a


def test_traffic_length_mix_shorthands_normalize():
    spec = TrafficSpec.from_raw({"prompt_lens": 8, "gen_lens": [2, 6]})
    assert spec.prompt_lens == {8: 1.0}
    assert spec.gen_lens == {2: 0.5, 6: 0.5}
    assert spec.max_context == 8 + 6
    weighted = TrafficSpec.from_raw({"prompt_lens": {4: 3, 8: 1}})
    assert weighted.prompt_lens == {4: 0.75, 8: 0.25}


def test_traffic_arrival_shapes():
    burst = TrafficSpec.from_raw({"arrival": "burst", "n_requests": 5})
    assert [r.arrival_s for r in burst.requests()] == [0.0] * 5
    uniform = TrafficSpec.from_raw(
        {"arrival": "uniform", "n_requests": 4, "rate_rps": 2.0})
    assert [r.arrival_s for r in uniform.requests()] == [0.0, 0.5, 1.0, 1.5]
    poisson = TrafficSpec.from_raw({"arrival": "poisson", "n_requests": 8})
    arrivals = [r.arrival_s for r in poisson.requests()]
    assert arrivals == sorted(arrivals) and arrivals[0] > 0.0


@pytest.mark.parametrize("raw, message", [
    ({"n_requests": 0}, "n_requests"),
    ({"rate_rps": 0.0}, "rate_rps"),
    ({"arrival": "flood"}, "flood"),
    ({"prompt_lens": {0: 1.0}}, ">= 1"),
    ({"gen_lens": {4: -1.0}}, "> 0"),
    ({"cadence": 3}, "cadence"),
])
def test_traffic_validation_names_the_problem(raw, message):
    with pytest.raises(TrafficError, match=message):
        TrafficSpec.from_raw(raw)


def test_serving_spec_validation():
    spec = ServingSpec.from_raw(dict(CANONICAL_SERVING))
    assert spec.max_batch == 2 and spec.queue_limit == 4
    assert spec.traffic.seed == 5
    assert ServingSpec.from_raw(None) is None
    with pytest.raises(ExperimentError, match="max_batch"):
        ServingSpec.from_raw({"max_batch": 0})
    with pytest.raises(ExperimentError, match="dtype_bytes"):
        ServingSpec.from_raw({"dtype_bytes": 3})
    with pytest.raises(ExperimentError, match="flood"):
        ServingSpec.from_raw({"traffic": {"arrival": "flood"}})


# ---------------------------------------------------------------------------
# ServingSim: shedding, concurrency limit, determinism
# ---------------------------------------------------------------------------

def _req(i, arrival, prompt=4, gen=2):
    return Request(id=i, arrival_s=arrival, prompt_len=prompt, gen_len=gen,
                   token_seed=i)


COSTS = ServingCosts(prefill_s_per_token=0.001, decode_step_s=0.01)


def test_sim_sheds_arrivals_beyond_queue_limit():
    # 6 requests burst into a queue of 3: the whole burst is admitted
    # (or shed) on arrival, before any slot frees up
    requests = [_req(i, 0.0) for i in range(6)]
    out = ServingSim(max_batch=1, queue_limit=3).run(requests, COSTS)
    assert out["served"] == 3 and out["shed"] == 3
    assert out["shed_ids"] == [3, 4, 5]  # later arrivals shed first-come
    assert out["peak_concurrency"] == 1


def test_sim_respects_concurrency_limit():
    requests = [_req(i, 0.0) for i in range(4)]
    out = ServingSim(max_batch=2, queue_limit=8).run(requests, COSTS)
    assert out["served"] == 4 and out["shed"] == 0
    assert out["peak_concurrency"] == 2
    # kv peak: 2 concurrent sequences at prompt+generated depth
    assert out["kv_peak_tokens"] <= 2 * (4 + 2)


def test_sim_is_a_pure_function_of_requests_and_costs():
    spec = TrafficSpec.from_raw({"seed": 3, "n_requests": 24,
                                 "arrival": "poisson", "rate_rps": 64.0,
                                 "prompt_lens": [4, 8], "gen_lens": [2, 4]})
    sim = ServingSim(max_batch=2, queue_limit=4)
    a = sim.run(spec.requests(), COSTS)
    b = ServingSim(max_batch=2, queue_limit=4).run(spec.requests(), COSTS)
    assert a == b
    assert a["total_tokens"] > 0 and a["throughput_tok_s"] > 0
    assert a["p99_latency_s"] >= a["p50_latency_s"] > 0


def test_request_queue_sheds_when_full():
    q = RequestQueue(2)
    assert q.offer("a") and q.offer("b")
    assert not q.offer("c")  # full -> shed
    assert q.shed == ["c"] and len(q) == 2
    assert q.take() == "a" and q.take() == "b" and q.take() is None


# ---------------------------------------------------------------------------
# batched prefill vs the token-by-token decode loop
# ---------------------------------------------------------------------------

PREFILL_ARCHS = ("qwen3-1.7b", "zamba2-2.7b", "xlstm-1.3b")


def _smoke_model(name):
    spec = get_arch(name).smoke_spec_fn()
    model = LM(spec)
    params, _ = split(model.init(jax.random.PRNGKey(0), dtype=jnp.float32))
    return spec, model, params


@pytest.mark.parametrize("name", PREFILL_ARCHS)
def test_prefill_matches_token_loop(name):
    """One full-sequence prefill must produce the same logits and the
    same decode cache as feeding the prompt token-by-token."""
    spec, model, params = _smoke_model(name)
    S, max_ctx = 8, 16
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, S), 0, spec.vocab)

    loop_cache = model.init_cache(params, 2, max_ctx, dtype=jnp.float32)
    loop_logits = []
    for t in range(S):
        lg, loop_cache = model.decode(params, loop_cache,
                                      tokens[:, t:t + 1], t)
        loop_logits.append(lg)
    loop_logits = jnp.concatenate(loop_logits, axis=1)

    cache = model.init_cache(params, 2, max_ctx, dtype=jnp.float32)
    logits, cache = model.prefill(params, cache, tokens)

    assert logits.shape == loop_logits.shape
    assert jnp.max(jnp.abs(logits - loop_logits)) < 1e-4
    for a, b in zip(jax.tree_util.tree_leaves(cache),
                    jax.tree_util.tree_leaves(loop_cache)):
        assert jnp.max(jnp.abs(a.astype(jnp.float32)
                               - b.astype(jnp.float32))) < 1e-4
    # and decoding continues identically from both caches
    nxt = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)
    lg_a, _ = model.decode(params, cache, nxt, S)
    lg_b, _ = model.decode(params, loop_cache, nxt, S)
    assert jnp.max(jnp.abs(lg_a - lg_b)) < 1e-4


def test_decode_accepts_per_slot_position_vector():
    spec, model, params = _smoke_model("qwen3-1.7b")
    cache = model.init_cache(params, 2, 16, dtype=jnp.float32)
    tok = jnp.zeros((2, 1), jnp.int32)
    scalar, _ = model.decode(params, cache, tok, 3)
    vector, _ = model.decode(params, cache, tok, jnp.array([3, 3]))
    assert jnp.max(jnp.abs(scalar - vector)) < 1e-5


# ---------------------------------------------------------------------------
# ServingEngine: continuous batching, mid-flight joins, shedding
# ---------------------------------------------------------------------------

def test_engine_matches_isolated_generation():
    """Requests joining a shared batch mid-flight must emit the same
    tokens as each request generated alone: slots are independent."""
    spec, model, params = _smoke_model("qwen3-1.7b")
    traffic = TrafficSpec.from_raw({
        "seed": 2, "n_requests": 3, "arrival": "burst",
        "prompt_lens": [4, 6], "gen_lens": 3})
    requests = traffic.requests()
    max_ctx = min(traffic.max_context + 1, spec.max_position)

    engine = ServingEngine(model, params, max_batch=2, queue_limit=4,
                           max_context=max_ctx)
    summary = engine.run(requests)
    assert summary["served"] == 3 and summary["shed"] == 0
    assert summary["prefills"] == 3

    by_id = {r["id"]: r for r in engine.completed}
    for req in requests:
        cache = model.init_cache(params, 1, max_ctx, dtype=jnp.float32)
        prompt = jnp.asarray(req.prompt_tokens(spec.vocab)[None])
        logits, cache = model.prefill(params, cache, prompt)
        tok = int(jnp.argmax(logits[0, -1]))
        alone = [tok]
        pos = req.prompt_len
        while len(alone) < req.gen_len:
            lg, cache = model.decode(params, cache,
                                     jnp.array([[tok]], jnp.int32),
                                     jnp.array([pos]))
            tok = int(jnp.argmax(lg[0, 0]))
            alone.append(tok)
            pos += 1
        assert by_id[req.id]["tokens"] == alone


@pytest.mark.parametrize("name", PREFILL_ARCHS)
def test_join_programs_match_their_eager_forms(name):
    """A join's batch-1 cache, one compiled program, is the cache built
    op by op, and its merge writes the slot in place: the batched cache
    it was given is donated, the slot holds the batch-1 cache, the
    others are kept."""
    spec, model, params = _smoke_model(name)
    engine = ServingEngine(model, params, max_batch=2, queue_limit=2, max_context=16)
    fresh = model.init_cache(params, 1, 16, dtype=jnp.float32)
    with jax.disable_jit():
        want = model._build_cache(params, 1, 16, None, jnp.float32)
    assert jax.tree_util.tree_structure(fresh) == jax.tree_util.tree_structure(want)
    for a, b in zip(jax.tree_util.tree_leaves(fresh), jax.tree_util.tree_leaves(want)):
        assert a.shape == b.shape and a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    tokens = jax.random.randint(jax.random.PRNGKey(2), (1, 5), 0, spec.vocab)
    _, single = engine._prefill_jit(params, fresh, tokens)
    # copies: a host view of a buffer would keep it from being donated
    before = [np.array(x) for x in jax.tree_util.tree_leaves(engine.cache)]
    old = engine.cache
    engine._merge_slot(single, 1)
    assert all(x.is_deleted() for x in jax.tree_util.tree_leaves(old))
    for b, s, m, from_right in zip(before, jax.tree_util.tree_leaves(single),
                                   jax.tree_util.tree_leaves(engine.cache), engine._axes_flat):
        axis = b.ndim - from_right
        np.testing.assert_array_equal(np.take(np.asarray(m), 1, axis=axis),
                                      np.take(np.asarray(s), 0, axis=axis))
        np.testing.assert_array_equal(np.take(np.asarray(m), 0, axis=axis),
                                      np.take(b, 0, axis=axis))


def test_pick_reads_each_rows_last_position():
    """``_pick`` takes (batch, seq, vocab) logits: the greedy id at each
    row's last position, and only those logits count as non-finite."""
    _, model, params = _smoke_model("qwen3-1.7b")
    engine = ServingEngine(model, params, max_batch=2, queue_limit=2, max_context=16)
    logits = np.array(jax.random.normal(jax.random.PRNGKey(3), (2, 3, 11)))
    np.testing.assert_array_equal(engine._pick(jnp.asarray(logits)),
                                  logits[:, -1].argmax(-1))
    assert engine.nonfinite_logits == 0
    logits[:, 0, 4] = np.nan
    engine._pick(jnp.asarray(logits))
    assert engine.nonfinite_logits == 0
    logits[1, -1, 4] = np.inf
    engine._pick(jnp.asarray(logits))
    assert engine.nonfinite_logits == 1


def test_engine_sheds_and_replays_deterministically():
    spec, model, params = _smoke_model("qwen3-1.7b")
    traffic = TrafficSpec.from_raw({
        "seed": 0, "n_requests": 6, "arrival": "burst",
        "prompt_lens": 4, "gen_lens": 2})
    max_ctx = min(traffic.max_context + 1, spec.max_position)

    def run():
        engine = ServingEngine(model, params, max_batch=2, queue_limit=3,
                               max_context=max_ctx)
        summary = engine.run(traffic.requests())
        return summary, [r["tokens"] for r in engine.completed]

    (a, toks_a), (b, toks_b) = run(), run()
    # burst of 6 into queue_limit 3: the overflow is shed gracefully
    assert a["shed"] == 3 and a["shed_ids"] == [3, 4, 5]
    assert a["served"] == 3
    # fixed seed -> bit-identical replay, admissions and outputs alike
    assert a == b and toks_a == toks_b


# ---------------------------------------------------------------------------
# artifact store: cold explore -> warm boot with zero XLA compiles
# ---------------------------------------------------------------------------

@pytest.fixture
def serving_report(tmp_path):
    raw = canonical_experiment(
        tmp_path, cache_dir=str(tmp_path / "cache"),
        budget={"n_trials": 6})
    os.environ.setdefault("REPRO_ARTIFACTS", "1")
    explorer = Explorer.from_dict(raw)
    report = explorer.run()
    assert report.artifacts and report.artifacts["entries"] > 0
    return report


def test_warm_boot_serves_same_logits_with_zero_compiles(serving_report):
    with open(serving_report.artifact) as f:
        persisted = json.load(f)
    candidate, spec = rebuild_best(persisted)
    assert candidate.arch.signature() == persisted["best"]["signature"]

    # cold path: a fresh estimator with no cache dir must compile
    cold = _ServingEstimator(target=spec.target, serving=spec.serving)
    plan = cold._schedule_plan(candidate)
    before = generate_call_count()
    cold_artifact, (params, x0) = cold._artifact(candidate, plan)
    assert generate_call_count() - before == 1
    cold_logits = np.asarray(cold_artifact.compiled(params, x0))

    # warm path: same cache dir the exploration populated -> store hit,
    # zero generate() calls, and the loaded executable agrees exactly
    warm = _ServingEstimator(target=spec.target, serving=spec.serving,
                             cache=spec.cache.dir)
    before = generate_call_count()
    warm_artifact, (params_w, x0_w) = warm._artifact(candidate, plan)
    assert generate_call_count() - before == 0
    assert warm.artifacts is not None and warm.artifacts.hits >= 1
    warm_logits = np.asarray(warm_artifact.compiled(params_w, x0_w))
    assert np.array_equal(cold_logits, warm_logits)


def test_serve_cli_boots_report_with_zero_compiles(serving_report):
    """The CI smoke in-process: `serve --from-report --expect-compiles 0`
    must serve every request of the declared traffic without compiling."""
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    env.setdefault("REPRO_ARTIFACTS", "1")
    proc = subprocess.run(
        [sys.executable, "-m", "repro.launch.serve",
         "--from-report", serving_report.artifact, "--expect-compiles", "0"],
        capture_output=True, text=True, env=env, timeout=300,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    assert proc.returncode == 0, proc.stderr
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["compiles"] == 0
    assert out["served"] == out["traffic"]["n_requests"]
    assert out["shed"] == 0
    assert out["signature"] == serving_report.best["signature"]


def test_rebuild_best_rejects_signature_drift(serving_report):
    with open(serving_report.artifact) as f:
        persisted = json.load(f)
    persisted["best"]["signature"] = "linear(width=9999)"
    with pytest.raises(SystemExit, match="does not\n?.*match"):
        rebuild_best(persisted)


# ---------------------------------------------------------------------------
# estimator determinism: serial vs process backends
# ---------------------------------------------------------------------------

def test_serving_criteria_deterministic_across_backends(tmp_path):
    def run(backend, sub):
        raw = canonical_experiment(
            tmp_path / sub, backend=backend,
            cache_dir=str(tmp_path / sub / "cache"),
            budget={"n_trials": 6})
        report = Explorer.from_dict(raw).run(save_report=False)
        return (report.best["number"], report.best["params"],
                report.best["values"], report.criteria_values)

    serial = run("serial", "serial")
    assert run("process", "process") == serial
    assert run("serial", "again") == serial  # and across repeat runs


def test_estimator_values_are_pure_functions_of_spec():
    """Same candidate + same serving spec -> same values, no cache."""
    from repro.core.builder import ModelBuilder
    from repro.core.space import parse_search_space
    from repro.core.translate import sample_architecture
    from repro.search.samplers import RandomSampler
    from repro.search.study import Study
    from test_parity_matrix import CANONICAL_SPACE

    space = parse_search_space(dict(CANONICAL_SPACE))
    builder = ModelBuilder(space.input_shape, space.output_dim)
    study = Study(sampler=RandomSampler(seed=0))
    candidate = builder.build(sample_architecture(space, study.ask()))

    serving = resolve_serving(dict(CANONICAL_SERVING))
    values = {}
    for _ in range(2):
        est = _ServingEstimator(target="host_cpu", serving=serving)
        summary = est._simulate(candidate)
        for k in ("p99_latency_s", "throughput_tok_s", "kv_peak_tokens"):
            values.setdefault(k, []).append(summary[k])
    for k, (a, b) in values.items():
        assert a == b, k
