"""Kernel schedules: validation, parity, threading, and tuning.

The contract under test (see ``repro/kernels/schedule.py``):

  * every legal candidate schedule computes the same values as the
    pure-jnp oracles in ``repro/kernels/ref.py`` — blocking is a launch
    decision, never a numerics decision;
  * resolving the named ``default`` schedule is bit-identical to calling
    the kernels with their legacy constants;
  * validation errors name the offending field;
  * effective (shape-clamped) schedules mirror what the ops layer
    launches, and the recorder/sink sees exactly that;
  * the autotuner honors budget/overrides and memoizes sweeps.

No hypothesis dependency on purpose: this suite must run in the bare
container (``tests/test_kernels.py`` module-skips without hypothesis).
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels import schedule as ksched
from repro.kernels.schedule import (
    CANDIDATE_SCHEDULES,
    KERNEL_FIELDS,
    KernelSchedule,
    ScheduleError,
    as_schedule,
    default_schedule,
    effective_schedule,
    schedule_signature,
    use_schedules,
    validate_schedule,
)

L = 256  # divides every scan candidate chunk; spans the flash block grid


def _rand(key, shape):
    return jax.random.normal(jax.random.PRNGKey(key), shape, jnp.float32)


# ---------------------------------------------------------------------------
# shared inputs + per-kernel call plumbing
# ---------------------------------------------------------------------------

def _flash_inputs():
    q = _rand(0, (1, L, 2, 8))
    k = _rand(1, (1, L, 2, 8))
    v = _rand(2, (1, L, 2, 8))
    return q, k, v


def _flash_ref(q, k, v):
    # ref takes (B, H, S, D); ops takes the model layout (B, S, H, D)
    out = ref.flash_attention_ref(
        q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
        v.transpose(0, 2, 1, 3), causal=True)
    return out.transpose(0, 2, 1, 3)


def _ssm_inputs():
    x = _rand(3, (1, L, 2, 8))
    dt = jax.nn.softplus(_rand(4, (1, L, 2)))
    a = -jnp.exp(_rand(5, (2,)))
    b = _rand(6, (1, L, 1, 4))  # one group, expanded to 2 heads inside ops
    c = _rand(7, (1, L, 1, 4))
    return x, dt, a, b, c


def _mlstm_inputs():
    q = _rand(8, (1, L, 2, 8))
    k = _rand(9, (1, L, 2, 8))
    v = _rand(10, (1, L, 2, 8))
    i_log = _rand(11, (1, L, 2))
    f_log = _rand(12, (1, L, 2)) + 3.0
    return q, k, v, i_log, f_log


def _decode_matmul_inputs():
    return _rand(13, (8, 256)), _rand(14, (2, 256, 384)), jnp.int32(1)


def _decode_attention_inputs():
    # 4 slots of a 2-layer stack of 40 positions, 2 K/V heads of 128
    return (_rand(15, (4, 4, 128)), _rand(16, (2, 4, 40, 2, 128)),
            _rand(17, (2, 4, 40, 2, 128)), jnp.int32(1),
            jnp.asarray((1, 16, 17, 40), jnp.int32))


def _call(kernel, schedule=None, **kwargs):
    """Run one schedulable op on the shared inputs; returns the primary
    output array."""
    if kernel == "decode_matmul":
        return ops.decode_matmul(*_decode_matmul_inputs(), schedule=schedule,
                                 **kwargs)
    if kernel == "decode_attention":
        return ops.decode_attention(*_decode_attention_inputs(), scale=128 ** -0.5,
                                    schedule=schedule, **kwargs)
    if kernel == "flash_attention":
        return ops.flash_attention(*_flash_inputs(), causal=True,
                                   schedule=schedule, **kwargs)
    if kernel == "ssm_scan":
        y, _ = ops.ssm_scan(*_ssm_inputs(), schedule=schedule, **kwargs)
        return y
    q, k, v, i_log, f_log = _mlstm_inputs()
    h, _ = ops.mlstm_scan(q, k, v, i_log, f_log, schedule=schedule, **kwargs)
    return h


def _oracle(kernel):
    if kernel == "decode_matmul":
        return ref.decode_matmul_ref(*_decode_matmul_inputs())
    if kernel == "decode_attention":
        return ref.decode_attention_ref(*_decode_attention_inputs(), scale=128 ** -0.5)
    if kernel == "flash_attention":
        return _flash_ref(*_flash_inputs())
    if kernel == "ssm_scan":
        x, dt, a, b, c = _ssm_inputs()
        b_mat = jnp.repeat(b, 2, axis=2)
        c_mat = jnp.repeat(c, 2, axis=2)
        y, _ = ref.ssm_scan_ref(x, dt, a, b_mat, c_mat)
        return y
    return ref.mlstm_scan_ref(*_mlstm_inputs())


_PARITY_CASES = [(kernel, cand)
                 for kernel, grid in sorted(CANDIDATE_SCHEDULES.items())
                 for cand in grid]


@pytest.mark.parametrize(
    "kernel,cand", _PARITY_CASES,
    ids=[f"{k}-{schedule_signature(k, c.merged_over(default_schedule(k)))}"
         for k, c in _PARITY_CASES])
def test_every_candidate_schedule_matches_reference(kernel, cand):
    out = _call(kernel, schedule=cand)
    want = _oracle(kernel)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=2e-4, rtol=2e-3)


@pytest.mark.parametrize("kernel", sorted(KERNEL_FIELDS))
def test_default_schedule_is_bit_identical_to_legacy_path(kernel):
    """Resolving the named default must reproduce the legacy constant
    path bit-for-bit — same blocks, same launch, same floats."""
    plain = _call(kernel)  # no schedule anywhere -> named default
    explicit = _call(kernel, schedule=default_schedule(kernel))
    assert np.array_equal(np.asarray(plain), np.asarray(explicit))
    if kernel in ("decode_matmul", "decode_attention"):
        return  # came after schedules: they have no legacy kwargs
    if kernel == "flash_attention":
        legacy = _call(kernel, block_q=128, block_kv=128)
    else:
        legacy = _call(kernel, chunk=128)
    assert np.array_equal(np.asarray(plain), np.asarray(legacy))


# ---------------------------------------------------------------------------
# validation: errors name the offending field
# ---------------------------------------------------------------------------

def test_unknown_kernel_named_in_error():
    with pytest.raises(ScheduleError, match="warp_drive"):
        validate_schedule("warp_drive", KernelSchedule())


def test_inapplicable_field_named_in_error():
    with pytest.raises(ScheduleError, match="'chunk'"):
        validate_schedule("flash_attention", KernelSchedule(chunk=64))
    with pytest.raises(ScheduleError, match="'block_q'"):
        validate_schedule("ssm_scan", KernelSchedule(block_q=64))


def test_non_integer_field_named_in_error():
    with pytest.raises(ScheduleError, match="'chunk'"):
        validate_schedule("ssm_scan", KernelSchedule(chunk=64.0))
    with pytest.raises(ScheduleError, match="'chunk'"):
        validate_schedule("ssm_scan", KernelSchedule(chunk=True))


def test_out_of_range_field_named_in_error():
    with pytest.raises(ScheduleError, match=r"'block_q'=4"):
        validate_schedule("flash_attention", KernelSchedule(block_q=4))
    with pytest.raises(ScheduleError, match=r"'chunk'=2048"):
        validate_schedule("ssm_scan", KernelSchedule(chunk=2048))


def test_non_power_of_two_field_named_in_error():
    with pytest.raises(ScheduleError, match=r"'block_kv'=96"):
        validate_schedule("flash_attention", KernelSchedule(block_kv=96))


def test_decode_matmul_fields_named_in_error():
    with pytest.raises(ScheduleError, match=r"'block_k'=96"):
        validate_schedule("decode_matmul", KernelSchedule(block_k=96))
    with pytest.raises(ScheduleError, match=r"'block_n'=2048"):
        validate_schedule("decode_matmul", KernelSchedule(block_n=2048))
    with pytest.raises(ScheduleError, match="'chunk'"):
        validate_schedule("decode_matmul", KernelSchedule(chunk=128))
    with pytest.raises(ScheduleError, match="'block_k'"):
        validate_schedule("flash_attention", KernelSchedule(block_k=128))


def test_unknown_schedule_dict_field_rejected():
    with pytest.raises(ScheduleError, match="block_z"):
        KernelSchedule.from_dict({"block_z": 64})


def test_as_schedule_fills_defaults():
    s = as_schedule("flash_attention", {"block_q": 64})
    assert (s.block_q, s.block_kv) == (64, 128)


# ---------------------------------------------------------------------------
# effective (shape-clamped) schedules mirror the ops layer
# ---------------------------------------------------------------------------

def test_effective_flash_clamps_to_sequence():
    eff = effective_schedule("flash_attention",
                             KernelSchedule(block_q=128, block_kv=256),
                             seq_len=40, kv_len=80)
    assert (eff.block_q, eff.block_kv) == (40, 80)
    # never below the 16-row floor
    eff = effective_schedule("flash_attention", None, seq_len=4)
    assert (eff.block_q, eff.block_kv) == (16, 16)


def test_effective_chunk_halves_until_it_divides():
    eff = effective_schedule("ssm_scan", KernelSchedule(chunk=32), seq_len=48)
    assert eff.chunk == 16  # 32 -> 16 divides 48
    eff = effective_schedule("mlstm_scan", KernelSchedule(chunk=512), seq_len=192)
    assert eff.chunk == 192  # min(512, 192) already divides
    eff = effective_schedule("ssm_scan", KernelSchedule(chunk=64), seq_len=96)
    assert eff.chunk == 32  # 64 -> 32 divides 96


def test_effective_decode_tiles_divide_and_stay_lane_aligned():
    eff = effective_schedule("decode_matmul", None, seq_len=6144, kv_len=1024)
    assert (eff.block_k, eff.block_n) == (512, 1024)
    eff = effective_schedule("decode_matmul", KernelSchedule(block_k=1024, block_n=32),
                             seq_len=384, kv_len=640)
    assert (eff.block_k, eff.block_n) == (384, 128)  # the whole K; lifted to a lane


def test_effective_decode_attention_block_fits_the_cache():
    eff = effective_schedule("decode_attention", None, seq_len=1281)
    assert eff.block_kv == default_schedule("decode_attention").block_kv
    eff = effective_schedule("decode_attention", KernelSchedule(block_kv=512), seq_len=40)
    assert eff.block_kv == 40  # the whole cache, one block


@pytest.mark.parametrize("block_kv", [8, 16, 32, 64])
def test_decode_attention_schedules_match_reference(block_kv):
    out = _call("decode_attention", schedule=KernelSchedule(block_kv=block_kv))
    np.testing.assert_allclose(np.asarray(out), np.asarray(_oracle("decode_attention")),
                               atol=5e-3, rtol=0)


@pytest.mark.parametrize("block_k,block_n", [(128, 128), (256, 128), (1024, 1024)])
def test_decode_matmul_schedules_match_reference(block_k, block_n):
    out = _call("decode_matmul",
                schedule=KernelSchedule(block_k=block_k, block_n=block_n))
    np.testing.assert_allclose(np.asarray(out), np.asarray(_oracle("decode_matmul")),
                               atol=1e-5, rtol=1e-5)


def test_recorder_sees_effective_not_requested():
    sink = {}
    q, k, v = _rand(0, (1, 40, 2, 8)), _rand(1, (1, 40, 2, 8)), _rand(2, (1, 40, 2, 8))
    with ksched.record_kernel_calls(sink):
        jax.eval_shape(lambda q, k, v: ops.flash_attention(
            q, k, v, schedule=KernelSchedule(block_q=256, block_kv=256)),
            q, k, v)
    (entry,) = sink.values()
    assert entry["requested"].block_q == 256
    assert entry["effective"].block_q == 40  # clamped to the sequence
    sig = ksched.effective_signature(sink)
    assert "block_q=40" in sig and "flash_attention" in sig


# ---------------------------------------------------------------------------
# trace-time threading: use_schedules precedence
# ---------------------------------------------------------------------------

def test_context_overrides_legacy_kwargs():
    want = _call("ssm_scan", schedule=KernelSchedule(chunk=32))
    with use_schedules({"ssm_scan": {"chunk": 32}}):
        got = _call("ssm_scan", chunk=128)  # legacy kwarg loses
    assert np.array_equal(np.asarray(got), np.asarray(want))


def test_explicit_schedule_overrides_context():
    want = _call("ssm_scan", schedule=KernelSchedule(chunk=64))
    with use_schedules({"ssm_scan": {"chunk": 32}}):
        got = _call("ssm_scan", schedule=KernelSchedule(chunk=64))
    assert np.array_equal(np.asarray(got), np.asarray(want))


def test_use_schedules_validates_up_front():
    with pytest.raises(ScheduleError, match="'chunk'=7"):
        with use_schedules({"ssm_scan": {"chunk": 7}}):
            pass


# ---------------------------------------------------------------------------
# autotuner: discovery, budget, overrides, memoization
# ---------------------------------------------------------------------------

def _tuner(**kwargs):
    from repro.hwgen.autotune import ScheduleTuner
    from repro.hwgen.targets import get_target
    kwargs.setdefault("warmup", 0)
    kwargs.setdefault("iters", 1)
    return ScheduleTuner(get_target("host_cpu"), **kwargs)


def _discovered_ssm():
    from repro.hwgen.autotune import discover_kernel_calls
    x, dt, a, b, c = _ssm_inputs()
    return discover_kernel_calls(
        lambda *args: ops.ssm_scan(*args)[0], (x, dt, a, b, c))


def test_discovery_finds_kernel_without_compiling():
    calls = _discovered_ssm()
    (entry,) = calls.values()
    assert entry["kernel"] == "ssm_scan"
    assert entry["shapes"]["x"] == (1, L, 2, 8)


def test_tuner_budget_caps_swept_candidates():
    tuner = _tuner(budget=2)
    (entry,) = _discovered_ssm().values()
    record = tuner.tune("ssm_scan", entry["shapes"], entry["meta"])
    assert record["n_candidates"] <= 2
    # default-first grid: the named default is always candidate 0
    assert record["candidates"][0]["schedule"] == {"chunk": 128}
    assert tuner.stats()["tunes"] == 1


def test_tuner_override_pins_kernel_without_sweeping():
    tuner = _tuner(overrides={"ssm_scan": {"chunk": 64}})
    plan = tuner.plan(_discovered_ssm())
    assert plan["ssm_scan"].chunk == 64
    assert tuner.stats() == {"tunes": 0, "cache_hits": 0, "tune_time_s": 0.0}


def test_tuner_memoizes_sweeps_in_cache(tmp_path):
    from repro.evaluation.cache import EvaluationCache
    cache = EvaluationCache(disk=str(tmp_path / "store"))
    (entry,) = _discovered_ssm().values()
    first = _tuner(budget=2, cache=cache)
    r1 = first.tune("ssm_scan", entry["shapes"], entry["meta"])
    assert first.stats()["tunes"] == 1
    # a fresh tuner over the same store re-tunes nothing (warm restart)
    second = _tuner(budget=2, cache=EvaluationCache(disk=str(tmp_path / "store")))
    r2 = second.tune("ssm_scan", entry["shapes"], entry["meta"])
    assert second.stats() == {"tunes": 0, "cache_hits": 1, "tune_time_s": 0.0}
    assert r2["schedule"] == r1["schedule"]
    # the persisted winner is the *requested* (validated) schedule
    validate_schedule("ssm_scan", as_schedule("ssm_scan", r2["schedule"]))


def test_shape_bucket_rounds_up_and_keeps_flags():
    tuner = _tuner()
    b1 = tuner.shape_bucket("ssm_scan", {"x": (1, 200, 2, 8)}, {"dtype": "float32"})
    b2 = tuner.shape_bucket("ssm_scan", {"x": (1, 256, 2, 8)}, {"dtype": "float32"})
    b3 = tuner.shape_bucket("ssm_scan", {"x": (1, 256, 2, 8)}, {"dtype": "bfloat16"})
    assert b1 == b2  # 200 buckets with 256
    assert b2 != b3  # dtype flag is part of the bucket


# ---------------------------------------------------------------------------
# spec layer: kernel_tuning section
# ---------------------------------------------------------------------------

def test_kernel_tuning_spec_roundtrip():
    from repro.explorer.experiment import KernelTuningSpec
    spec = KernelTuningSpec.from_raw(
        {"mode": "cached", "budget": 3, "kernels": {"ssm_scan": {"chunk": 64}}})
    assert spec.mode == "cached" and spec.budget == 3
    assert KernelTuningSpec.from_raw(spec.to_dict()).to_dict() == spec.to_dict()
    # bare string shorthand
    assert KernelTuningSpec.from_raw("search").mode == "search"
    assert KernelTuningSpec.from_raw(None) is None


def test_kernel_tuning_spec_rejects_bad_sections():
    from repro.explorer.experiment import ExperimentError, KernelTuningSpec
    with pytest.raises(ExperimentError, match="mode"):
        KernelTuningSpec.from_raw({"mode": "always"})
    with pytest.raises(ExperimentError, match="budget"):
        KernelTuningSpec.from_raw({"budget": 0})
    with pytest.raises(ExperimentError, match="unknown kernel"):
        KernelTuningSpec.from_raw({"kernels": {"warp_drive": {"chunk": 64}}})
    with pytest.raises(ExperimentError, match="'chunk'=7"):
        KernelTuningSpec.from_raw({"kernels": {"ssm_scan": {"chunk": 7}}})


def test_tpu_backend_never_interprets(monkeypatch):
    """On a TPU backend neither REPRO_PALLAS_INTERPRET nor a schedule's
    interpret field can put a kernel in the interpreter."""
    from repro.kernels import ops

    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "1")
    monkeypatch.setattr(ops, "_platform", lambda: "tpu")
    assert ops._interpret(None) is False
    assert ops._interpret(True) is False
    monkeypatch.setattr(ops, "_platform", lambda: "cpu")
    assert ops._interpret(None) is True
    assert ops._interpret(False) is False
