"""The ``zamba2-2.7b.chat-closed`` cell at the smoke size on the CPU: a
whole run through ``measure``, the planted faults of
``test_bench_harness.py``, the reference against the program's prefill
and decode, and the work counts against a hand count."""
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from test_bench_harness import _alter_tokens, _stale_state

from lib import refmath as R
from lib import weights as W
from lib.compiles import CompileCounter
from lib.harness import config_dims, program_shapes
from lib.measure import measure
from lib.peaks import peaks
from lib.registry import BENCH, load_cell, load_module

CELL = "zamba2-2.7b.chat-closed"
SEED = 2**31 + 515151
V5E = peaks("TPU v5 lite")


def _run(fault=None, seconds=3.0):
    return measure(load_cell(CELL), SEED, seconds, False, t_start=time.time(),
                   smoke=True, counter=CompileCounter(), fault=fault)


def test_smoke_run_is_correct_with_nothing_compiled_in_the_window():
    result, info, checks = _run()
    assert result["correct"], result["check"]
    assert info["window_compiles"] == 0 and info["drain_compiles"] == 0
    assert result["attempted"] > 0 and result["failed"] == 0
    assert set(result["metrics"]) >= {"tokens_per_s", "setup_s"}
    assert checks and all(c.startswith("check ") for c in checks)


@pytest.mark.parametrize("fault", [_alter_tokens, _stale_state])
def test_a_broken_timed_path_is_not_correct(fault):
    result, _, _ = _run(fault=fault, seconds=1.5)
    assert result["correct"] is False
    assert result["check"]["widest_gap"]["value"] > result["check"]["widest_gap"]["limit"]


def test_reference_matches_prefill_then_decode():
    """As ``test_bench_reference.py`` holds qwen3: max |program -
    reference| <= 1e-4 x max |reference logit| at ``HIGHEST``."""
    from repro.configs import get_arch
    from repro.models.lm import LM

    cell = load_cell(CELL)
    dims = config_dims(cell, smoke=True)
    model = LM(get_arch(cell.config["arch"]).smoke_spec_fn())
    shapes = program_shapes(model, jnp.float32)
    assert shapes == cell.reference.param_shapes(dims)
    flat = W.make(2**31 + 5, shapes, jnp.float32)
    params = W.unflatten_paths(flat)
    prompt, steps = 12, 6
    tokens = np.random.default_rng(3).integers(0, dims["vocab_size"], prompt + steps).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        decode = jax.jit(model.decode)
        cache = model.init_cache(params, 1, 32, dtype=jnp.float32)
        logits, cache = jax.jit(model.prefill)(params, cache, jnp.asarray(tokens[None, :prompt]))
        got = [np.asarray(logits[0])]
        for i in range(steps - 1):
            step, cache = decode(params, cache, jnp.asarray(tokens[None, prompt + i:prompt + i + 1]),
                                 jnp.asarray([prompt + i], jnp.int32))
            got.append(np.asarray(step[0]))
        want = np.asarray(cell.reference.forward(flat, jnp.asarray(tokens[:-1]), dims,
                                                 jnp.float32, R.HIGHEST))
    got = np.concatenate(got)
    assert got.shape == want.shape
    assert np.abs(got - want).max() <= 1e-4 * np.abs(want).max()


def _config():
    with open(os.path.join(BENCH, "configs", "zamba2-2.7b.json")) as f:
        c = json.load(f)
    return c, load_module(os.path.join(BENCH, "configs", "zamba2-2.7b.py"), "work_zamba2")


def test_zamba2_counts_by_hand():
    c, w = _config()
    # a Mamba2 layer: in_proj 2560 x (5120 z + 5248 xBC + 80 dt), out_proj
    # 5120 x 2560; conv 4 x 5248 + 5248, A/D/dt 3 x 80, norms 5120 + 2560
    mamba_mm = 2560 * 10448 + 5120 * 2560
    mamba = mamba_mm + 4 * 5248 + 5248 + 240 + 5120 + 2560
    assert mamba == 39_888_240
    # a shared block: q, k, v 5120 x 5120, o 5120 x 2560, gate, up, down
    # 2560 x 10240; norms 5120 + 2560
    block_mm = 3 * 5120 * 5120 + 5120 * 2560 + 3 * 2560 * 10240
    assert block_mm + 7680 == 170_401_280
    # an invocation's own: adapter 2560 x 128 + 128 x 20480, linear 2560^2
    own = 2560 * 128 + 128 * 20480 + 2560 * 2560
    assert own == 2_949_120 + 6_553_600
    embed = 32000 * 2560
    assert w.params(c) == 54 * mamba + 2 * (block_mm + 7680) + 9 * own + embed + 2560
    assert w.params(c) == 2_662_214_560
    assert w.weight_bytes(c) == 4 * 2_662_214_560 == c["memory"]["weights_bytes"]
    n = 54 * mamba_mm + 9 * (block_mm + own) + embed
    assert w.matmul_params(c) == n
    # per token and Mamba2 layer: conv 2 x 4 x 5248, SSM 5 x 80 x 64 x 64
    per_token = 2 * n + 54 * (2 * 4 * 5248 + 5 * 80 * 64 * 64)
    # K/V: 9 invocations x 2 x 32 heads x 160 x 4 bytes; state 80 x 64 x
    # 64 x 4 and window 3 x 5248 x 4 bytes in each of 54 layers
    assert c["memory"]["kv_bytes_per_token"] == 9 * 2 * 32 * 160 * 4 == 368_640
    ssm = 54 * (80 * 64 * 64 * 4 + 3 * 5248 * 4)
    assert w.ssm_bytes(c) == ssm == c["memory"]["ssm_bytes_per_slot"]
    assert c["memory"]["batched_cache_bytes"] == 8 * (513 * 368_640 + ssm)
    one = w.prefill(c, 1)
    assert one.flops == per_token + 9 * 4 * 32 * 160
    assert one.bytes == w.weight_bytes(c) + 368_640 + ssm
    step = w.decode(c, [10, 20])
    assert step.flops == 2 * per_token + 9 * 4 * 32 * 160 * 30
    assert step.bytes == w.weight_bytes(c) + 30 * 368_640 + 2 * 2 * ssm
    assert step.bound(V5E) == "memory"
    assert w.prefill(c, 99).bound(V5E) == "memory"
