"""Which decode projections read one layer of a stacked f32 weight
through the ``decode_matmul`` kernel, and that nothing else changes.

``LM.decode`` hands the layer scan's stacked f32 attention and MLP
projections to ``nn.linear.dense`` as ``LayerWeight``s.  The kernel is
one branch of ``lax.platform_dependent``: it is traced on every
platform (so the recorder sees it here too) and lowered on a TPU
only; on the CPU the layer is sliced and multiplied as before, which
these tests hold to the bit.  One test lowers the TPU branch on the
CPU, with the kernel in the Pallas interpreter.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_arch
from repro.configs.zamba2_2_7b import _layers as zamba2_layers
from repro.hwgen.autotune import discover_kernel_calls
from repro.kernels import ops as kops
from repro.kernels import schedule as ksched
from repro.launch.serve import ServingEngine
from repro.models import lm as lm_mod
from repro.models.lm import LM
from repro.models.specs import ModelSpec, transformer_layer
from repro.nn.types import split

# lane-aligned widths (the kernel tiles K and N by 128), three layers so
# the segment runs in the scan; the seven projections come in five
# shapes (k and v, gate and up share theirs)
ALIGNED = ModelSpec(
    name="aligned", d_model=128, vocab=256, tie_embeddings=True,
    layers=(transformer_layer(128, 2, 1, 384, activation="silu", gated=True,
                              qk_norm=True, d_head=128),) * 3)


def _params(spec, dtype=jnp.float32):
    values, _ = split(LM(spec).init(jax.random.PRNGKey(0)))
    return jax.tree_util.tree_map(lambda a: a.astype(dtype), values)


def _decode_args(model, params, batch=4):
    cache = model.init_cache(params, batch, 32, dtype=jnp.float32)
    tokens = (jnp.arange(batch, dtype=jnp.int32) + 1)[:, None]
    pos = jnp.arange(batch, dtype=jnp.int32) * 3
    return params, cache, tokens, pos


def _calls(fn, args):
    return discover_kernel_calls(fn, args)


def _weights(calls):
    return sorted(c["shapes"]["w"] for c in calls.values())


@pytest.fixture
def xla_only(monkeypatch):
    """Decode with no weight streamed: every projection on the XLA path."""
    def use():
        monkeypatch.setattr(lm_mod.linear, "streams", lambda w: False)
    return use


@pytest.mark.parametrize("spec", [ALIGNED, get_arch("qwen3-1.7b").smoke_spec_fn()],
                         ids=["aligned", "qwen3-smoke"])
def test_cpu_decode_is_bit_identical_to_xla_path(spec, xla_only):
    model = LM(spec)
    args = _decode_args(model, _params(spec))
    logits, cache = jax.jit(model.decode)(*args)
    xla_only()
    want_logits, want_cache = jax.jit(model.decode)(*args)
    np.testing.assert_array_equal(np.asarray(logits), np.asarray(want_logits))
    for got, want in zip(jax.tree_util.tree_leaves(cache),
                         jax.tree_util.tree_leaves(want_cache)):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def _serve(spec, params):
    from repro.launch.traffic import TrafficSpec

    engine = ServingEngine(LM(spec), params, max_batch=2, queue_limit=8,
                           max_context=40)
    requests = TrafficSpec(seed=3, n_requests=3, arrival="burst",
                           prompt_lens={8: 0.5, 12: 0.5},
                           gen_lens={6: 1.0}).requests()
    engine.run(requests)
    return engine, [r["tokens"] for r in engine.completed]


@pytest.mark.parametrize("spec", [ALIGNED, get_arch("qwen3-1.7b").smoke_spec_fn()],
                         ids=["aligned", "qwen3-smoke"])
def test_cpu_served_tokens_are_bit_identical_to_xla_path(spec, xla_only):
    params = _params(spec)
    _, tokens = _serve(spec, params)
    xla_only()
    _, want = _serve(spec, params)
    assert tokens == want


def test_kernel_engages_for_stacked_f32_decode_only():
    model = LM(ALIGNED)
    params = _params(ALIGNED)
    args = _decode_args(model, params)
    calls = _calls(model.decode, args)
    assert {c["kernel"] for c in calls.values()} == {"decode_matmul"}
    # q; k and v; o; gate and up; down
    assert _weights(calls) == [(3, 128, 128), (3, 128, 256), (3, 128, 384),
                               (3, 256, 128), (3, 384, 128)]

    bf16 = _params(ALIGNED, jnp.bfloat16)
    assert not _calls(model.decode, _decode_args(model, bf16))
    tokens = jnp.ones((1, 8), jnp.int32)
    cache = model.init_cache(params, 1, 32, dtype=jnp.float32)
    assert not _calls(model.prefill, (params, cache, tokens))
    assert not _calls(model.apply, (params, tokens))
    with jax.default_matmul_precision("highest"):
        assert not _calls(model.decode, args)


def test_shared_segment_keeps_the_xla_path():
    """zamba2's weight-shared attention block holds one (unstacked)
    parameter set: nothing to stream, even at lane-aligned widths."""
    base = get_arch("zamba2-2.7b").smoke_spec_fn()
    spec = dataclasses.replace(
        base, d_model=128,
        layers=zamba2_layers(128, 1, 256, 16, 32, 4, 2, None, smoke=True))
    assert any(layer.shared for layer in spec.layers)
    model = LM(spec)
    assert not _calls(model.decode, _decode_args(model, _params(spec), batch=2))


def test_qwen3_decode_traces_four_kernel_calls_at_published_widths():
    model = LM(get_arch("qwen3-1.7b").spec_fn())
    params = jax.eval_shape(lambda: split(model.init(jax.random.PRNGKey(0)))[0])
    cache = jax.eval_shape(lambda p: model.init_cache(p, 8, 1281, dtype=jnp.float32),
                           params)
    tokens = jax.ShapeDtypeStruct((8, 1), jnp.int32)
    pos = jax.ShapeDtypeStruct((8,), jnp.int32)
    calls = _calls(model.decode, (params, cache, tokens, pos))
    # q and o; k and v; gate and up; down
    assert _weights(calls) == [(28, 2048, 1024), (28, 2048, 2048),
                               (28, 2048, 6144), (28, 6144, 2048)]


@pytest.mark.parametrize("spec,dtype,platform,want", [
    (ALIGNED, jnp.float32, "tpu", 5),
    (ALIGNED, jnp.float32, "cpu", 0),  # traced, not lowered
    (ALIGNED, jnp.bfloat16, "tpu", 0),
    (get_arch("qwen3-1.7b").smoke_spec_fn(), jnp.float32, "tpu", 0),  # under a lane
], ids=["aligned-f32-tpu", "aligned-f32-cpu", "aligned-bf16", "qwen3-smoke"])
def test_engine_records_decode_kernel_calls(spec, dtype, platform, want, monkeypatch):
    monkeypatch.setattr(ServingEngine, "_platform", lambda self: platform)
    engine = ServingEngine(LM(spec), _params(spec, dtype), max_batch=2,
                           queue_limit=4, max_context=32)
    assert engine.decode_kernel_calls == want


def test_engine_platform_is_its_cache_devices():
    engine = ServingEngine(LM(ALIGNED), _params(ALIGNED), max_batch=2,
                           queue_limit=4, max_context=32)
    assert engine._platform() == jax.default_backend()


@pytest.mark.parametrize("pos", [(0, 3, 6, 9), (31, 0, 17, 5)], ids=["early", "late"])
def test_tpu_branch_matches_xla_path_within_bf16_operands(pos, monkeypatch):
    """The TPU branch of ``dense`` (the kernel, the layer index, the row
    reshape) lowered on the CPU with the kernel interpreted: logits and
    every layer's cache within the rounding of bf16 operands of the XLA
    path.  A layer read from the wrong place is off by its whole size."""
    model = LM(ALIGNED)
    params, cache, tokens, _ = _decode_args(model, _params(ALIGNED))
    args = (params, cache, tokens, jnp.asarray(pos, jnp.int32))
    want_logits, want_cache = jax.jit(model.decode)(*args)

    monkeypatch.setattr(jax.lax, "platform_dependent",
                        lambda *operands, tpu, default: tpu(*operands))
    monkeypatch.setattr(kops, "_interpret", lambda requested, platform=None: True)
    with ksched.record_kernel_calls({}) as sink:
        logits, cache = jax.jit(model.decode)(*args)
    assert {c["effective"].interpret for c in sink.values()} == {True}
    assert len(sink) == 5

    def close(got, want):
        got, want = np.asarray(got), np.asarray(want)
        np.testing.assert_allclose(got, want, rtol=0, atol=0.025 * np.abs(want).max())

    close(logits, want_logits)
    for got, want in zip(jax.tree_util.tree_leaves(cache),
                         jax.tree_util.tree_leaves(want_cache)):
        assert got.shape[0] == len(ALIGNED.layers)
        for layer in range(got.shape[0]):
            close(got[layer], want[layer])
