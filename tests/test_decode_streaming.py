"""Which decode projections read one layer of a stacked f32 weight
through the ``decode_matmul`` kernel, which attention reads its stacked
K/V through the ``decode_attention`` kernel, and that nothing else
changes.

``LM.decode`` hands the layer scan's stacked f32 attention and MLP
projections to ``nn.linear.dense`` as ``LayerWeight``s, and attention
over lane-aligned heads reads the stacked cache itself.  Each kernel is
one branch of ``lax.platform_dependent``: it is traced on every
platform (so the recorder sees it here too) and lowered on a TPU
only; on the CPU the layer is sliced and multiplied as before, which
these tests hold to the bit.  Some tests lower the TPU branches on the
CPU, with the kernels in the Pallas interpreter.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_arch
from repro.configs.zamba2_2_7b import _spec as zamba2_spec
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
    return sorted(c["shapes"]["w"] for c in calls.values() if c["kernel"] == "decode_matmul")


def _attention_calls(calls):
    return [c for c in calls.values() if c["kernel"] == "decode_attention"]


@pytest.fixture
def xla_only(monkeypatch):
    """Decode with no weight streamed: every projection on the XLA path."""
    def use():
        monkeypatch.setattr(lm_mod.linear, "streams", lambda w: False)
    return use


# zamba2 at widths where its Mamba2 in_proj (2,096 wide) gives the
# kernel 2,048 columns, read K-minor, and XLA the other 48
HYBRID = zamba2_spec("zamba2-512", 512, 4, (2,), n_heads=4, d_ff=1024, d_state=16,
                     d_head_ssm=64, adapter_rank=128, vocab=256, chunk=8)


@pytest.mark.parametrize("spec", [ALIGNED, get_arch("qwen3-1.7b").smoke_spec_fn(), HYBRID],
                         ids=["aligned", "qwen3-smoke", "zamba2-512"])
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
    assert {c["kernel"] for c in calls.values()} == {"decode_matmul", "decode_attention"}
    # q; k and v; o; gate and up; down
    assert _weights(calls) == [(3, 128, 128), (3, 128, 256), (3, 128, 384),
                               (3, 256, 128), (3, 384, 128)]

    # bf16 weights stay on XLA's dots; attention still reads the f32 cache
    bf16 = _params(ALIGNED, jnp.bfloat16)
    calls = _calls(model.decode, _decode_args(model, bf16))
    assert {c["kernel"] for c in calls.values()} == {"decode_attention"}
    tokens = jnp.ones((1, 8), jnp.int32)
    cache = model.init_cache(params, 1, 32, dtype=jnp.float32)
    assert not _calls(model.prefill, (params, cache, tokens))
    assert not _calls(model.apply, (params, tokens))
    with jax.default_matmul_precision("highest"):
        assert not _calls(model.decode, args)


def test_shared_and_hybrid_weights_stream_through_the_kernel():
    """zamba2's weight-shared blocks (one unstacked parameter set each)
    and its hybrid layers (each a segment of one): at lane-aligned
    widths decode reads their f32 projections through the kernel, each
    weight as a stack of one layer."""
    spec = zamba2_spec("zamba2-aligned", 128, 5, (1, 3), n_heads=2, d_ff=256, d_state=16,
                       d_head_ssm=32, adapter_rank=128, vocab=256, chunk=8)
    assert any(layer.hybrid for layer in spec.layers)
    model = LM(spec)
    weights = _weights(_calls(model.decode, _decode_args(model, _params(spec), batch=2)))
    # q, k, v; o; gate, up; down; adapter; linear; Mamba2 out_proj
    assert weights == [(1, 128, 128), (1, 128, 256), (1, 128, 512), (1, 256, 128),
                       (1, 256, 256)]


@pytest.mark.parametrize("spec,passes", [(HYBRID, 3), (ALIGNED, None)],
                         ids=["zamba2-high", "aligned-default"])
def test_spec_precision_sets_the_kernel_passes(spec, passes):
    """zamba2's spec sets the ``high`` precision: decode's kernel makes
    three bf16 passes.  A spec that sets none keeps one."""
    model = LM(spec)
    calls = _calls(model.decode, _decode_args(model, _params(spec)))
    assert calls and {c["meta"].get("passes") for c in calls.values()} == {passes}


@pytest.mark.parametrize("arch,want", [("zamba2-2.7b", "HIGH"), ("qwen3-1.7b", "DEFAULT")])
def test_spec_precision_reaches_every_xla_dot(arch, want):
    """``apply``, ``prefill`` and ``decode`` trace their dots at the
    spec's precision; without one, at the default."""
    model = LM(get_arch(arch).smoke_spec_fn())
    params = _params(model.spec)
    cache = model.init_cache(params, 1, 16, dtype=jnp.float32)
    tokens = jnp.ones((1, 4), jnp.int32)
    texts = [jax.jit(model.apply).lower(params, tokens).as_text(),
             jax.jit(model.prefill).lower(params, cache, tokens).as_text(),
             jax.jit(model.decode).lower(params, cache, tokens[:, :1],
                                         jnp.zeros((1,), jnp.int32)).as_text()]
    for text in texts:
        dots = [line for line in text.splitlines() if "stablehlo.dot_general" in line]
        assert dots
        found = {line.split("precision = [")[1].split(",")[0] if "precision = [" in line
                 else None for line in dots}
        assert found == {want}


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
    # and one attention call over the whole stack, in blocks
    (attend,) = _attention_calls(calls)
    assert attend["shapes"] == {"q": (8, 16, 128), "k": (28, 8, 1281, 8, 128)}
    assert attend["effective"].block_kv == ksched.default_schedule("decode_attention").block_kv


@pytest.mark.parametrize("spec,dtype,platform,want", [
    (ALIGNED, jnp.float32, "tpu", 6),  # five decode_matmul shapes, one decode_attention
    (ALIGNED, jnp.float32, "cpu", 0),  # traced, not lowered
    (ALIGNED, jnp.bfloat16, "tpu", 1),  # decode_attention over the f32 cache
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


def _tpu_branches(monkeypatch):
    """Lower every ``lax.platform_dependent`` branch as on a TPU, with the
    kernels in the Pallas interpreter."""
    monkeypatch.setattr(jax.lax, "platform_dependent",
                        lambda *operands, tpu, default: tpu(*operands))
    monkeypatch.setattr(kops, "_interpret", lambda requested, platform=None: True)


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

    _tpu_branches(monkeypatch)
    with ksched.record_kernel_calls({}) as sink:
        logits, cache = jax.jit(model.decode)(*args)
    assert {c["effective"].interpret for c in sink.values()} == {True}
    assert len(sink) == 6

    def close(got, want):
        got, want = np.asarray(got), np.asarray(want)
        np.testing.assert_allclose(got, want, rtol=0, atol=0.025 * np.abs(want).max())

    close(logits, want_logits)
    for got, want in zip(jax.tree_util.tree_leaves(cache),
                         jax.tree_util.tree_leaves(want_cache)):
        assert got.shape[0] == len(ALIGNED.layers)
        for layer in range(got.shape[0]):
            close(got[layer], want[layer])


def test_tpu_branch_matches_xla_path_for_a_hybrid_model(monkeypatch):
    """As above for zamba2's streamed weights: Mamba2 stacks, in_proj's
    leading columns read K-minor beside XLA's rest, a hybrid layer's
    adapter and linear, and the shared blocks as stacks of one."""
    model = LM(HYBRID)
    params, cache, tokens, _ = _decode_args(model, _params(HYBRID))
    args = (params, cache, tokens, jnp.asarray((0, 3, 9, 5), jnp.int32))
    want_logits, want_cache = jax.jit(model.decode)(*args)

    _tpu_branches(monkeypatch)
    with ksched.record_kernel_calls({}) as sink:
        logits, cache = jax.jit(model.decode)(*args)
    assert {c["effective"].interpret for c in sink.values()} == {True}
    assert {c["shapes"].get("out") for c in sink.values()} == {None, (4, 2048)}

    def close(got, want):
        got, want = np.asarray(got), np.asarray(want)
        np.testing.assert_allclose(got, want, rtol=0, atol=0.025 * np.abs(want).max())

    close(logits, want_logits)
    for got, want in zip(jax.tree_util.tree_leaves(cache),
                         jax.tree_util.tree_leaves(want_cache)):
        close(got, want)


def test_tpu_attention_branch_matches_xla_path_over_decode_steps(monkeypatch):
    """Six decode steps with the TPU branches lowered on the CPU (the
    attention kernel reading blocks of 8 positions, the projections
    through ``decode_matmul``) against the XLA path, from a cache whose
    every row holds K/V: the slots start at positions 0, 7, 14 and 25 of
    32, so each step reads a different number of blocks a slot and the
    slots cross blocks' edges as they go.  Each step's logits and the
    final cache agree within the rounding of bf16 operands."""
    model = LM(ALIGNED)
    params = _params(ALIGNED)
    empty = model.init_cache(params, 4, 32, dtype=jnp.float32)
    keys = iter(jax.random.split(jax.random.PRNGKey(5), len(jax.tree_util.tree_leaves(empty))))
    full = jax.tree_util.tree_map(lambda a: jax.random.normal(next(keys), a.shape), empty)
    start = jnp.asarray((0, 7, 14, 25), jnp.int32)

    def run():
        step, cache, logits = jax.jit(model.decode), full, []
        for i in range(6):
            tokens = ((jnp.arange(4, dtype=jnp.int32) * 5 + i) % ALIGNED.vocab)[:, None]
            out, cache = step(params, cache, tokens, start + i)
            logits.append(out)
        return jnp.concatenate(logits, axis=1), cache

    want_logits, want_cache = run()
    _tpu_branches(monkeypatch)
    with ksched.record_kernel_calls({}) as sink, \
            ksched.use_schedules({"decode_attention": {"block_kv": 8}}):
        logits, cache = run()
    (attend,) = _attention_calls(sink)
    assert attend["effective"].block_kv == 8 and attend["effective"].interpret

    def close(got, want):
        got, want = np.asarray(got), np.asarray(want)
        np.testing.assert_allclose(got, want, rtol=0, atol=0.025 * np.abs(want).max())

    for i in range(6):
        close(logits[:, i], want_logits[:, i])
    for got, want in zip(jax.tree_util.tree_leaves(cache),
                         jax.tree_util.tree_leaves(want_cache)):
        close(got, want)


# every kind of decode cache: lane-aligned heads (K/V rows scattered into
# the stack), narrow heads (rows written into the layer's own cache), Mamba2
# state and hybrid layers, mLSTM/sLSTM state, cross-attention's static
# K/V, a sliding window
CACHE_SPECS = {
    "aligned": ALIGNED,
    "qwen3-smoke": get_arch("qwen3-1.7b").smoke_spec_fn(),
    "zamba2-smoke": get_arch("zamba2-2.7b").smoke_spec_fn(),
    "xlstm-smoke": get_arch("xlstm-1.3b").smoke_spec_fn(),
    "whisper-smoke": get_arch("whisper-medium").smoke_spec_fn(),
    "window": ModelSpec(name="window", d_model=64, vocab=256,
                        layers=(transformer_layer(64, 4, 2, 128, window=5),) * 3),
    "aligned-window": ModelSpec(name="aligned-window", d_model=128, vocab=256,
                                layers=(transformer_layer(128, 2, 1, 256, window=5,
                                                          d_head=128),) * 2),
}


@pytest.mark.parametrize("name,context,want", [
    ("aligned", None, 1),
    ("qwen3-smoke", None, 0),  # 16-wide heads
    ("zamba2-smoke", None, 0),  # narrow heads, at the `high` precision
    ("whisper-smoke", None, 0),
    ("aligned-window", None, 0),
    ("aligned", "highest", 0),
    ("aligned", "mesh", 0),
])
def test_attention_kernel_engages_by_shape_and_spec(name, context, want):
    """Decode reads a stacked cache through the attention kernel, one call
    a segment, where the heads are lane-aligned; narrow heads, a sliding
    window, a precision the kernel's one bf16 pass would change and a
    mesh that shards the cache keep XLA's masked read of the layer."""
    import contextlib

    from repro.distributed.api import sharding_context
    from repro.distributed.sharding import default_rules

    model = LM(CACHE_SPECS[name])
    params = _params(model.spec)
    args = (params, model.init_cache(params, 2, 16, enc_out=_enc_out(model, params, 2),
                                     dtype=jnp.float32),
            jnp.ones((2, 1), jnp.int32), jnp.zeros((2,), jnp.int32))
    if context == "highest":
        scope = jax.default_matmul_precision("highest")
    elif context == "mesh":
        mesh = jax.sharding.Mesh(np.array(jax.devices()[:1]), ("data",))
        scope = sharding_context(mesh, default_rules(mesh))
    else:
        scope = contextlib.nullcontext()
    with scope:
        calls = _attention_calls(_calls(model.decode, args))
    assert len(calls) == want


@pytest.mark.parametrize("name,platform,want", [
    # a 300-position cache read in blocks of 128: slots at positions 4
    # and 255, then 5 and 256 (one, two, one and three blocks, the third
    # holding the cache's last 44 positions), and two idle slots, one
    # block each
    ("aligned", "tpu", (128 + 256 + 2 * 128) + (128 + 300 + 2 * 128)),
    ("aligned", "cpu", 2 * 4 * 300),  # XLA reads the whole layer
    ("zamba2-smoke", "tpu", 2 * 4 * 300),  # no kernel for its heads
])
def test_engine_counts_attended_positions(name, platform, want, monkeypatch):
    """``attended_positions`` adds, each step, the K/V positions each
    slot's decode attention reads: its length rounded up to the kernel's
    block where the kernel runs, else ``max_context``."""
    from repro.launch.traffic import Request

    monkeypatch.setattr(ServingEngine, "_platform", lambda self: platform)
    spec = CACHE_SPECS[name]
    engine = ServingEngine(LM(spec), _params(spec), max_batch=4, queue_limit=4,
                           max_context=300)
    assert engine._attention_block == (128 if (name, platform) == ("aligned", "tpu") else None)
    for i, n in enumerate((4, 255)):
        engine._join(Request(id=i, arrival_s=0.0, prompt_len=n, gen_len=8, token_seed=i))
    engine._decode_step()
    engine._decode_step()
    assert engine.valid_positions == (5 + 256) + (6 + 257)
    assert engine.attended_positions == want


def _enc_out(model, params, batch):
    if not model.spec.encoder_layers:
        return None
    frames = jax.random.normal(jax.random.PRNGKey(1), (batch, 6, model.spec.d_model))
    return model.encode(params, frames)


def _carried_and_unrolled(spec, pos):
    """``LM.decode`` with each segment's cache carried through its layer
    scan, and through the same walk unrolled (``scan_layers=False``),
    from one cache already holding a few steps."""
    model = LM(spec)
    params = _params(spec)
    cache = model.init_cache(params, 4, 16, enc_out=_enc_out(model, params, 4),
                             dtype=jnp.float32)
    step = jax.jit(model.decode)
    for i in range(3):
        tokens = (jnp.arange(4, dtype=jnp.int32)[:, None] * 7 + i) % spec.vocab
        _, cache = step(params, cache, tokens, jnp.asarray(pos, jnp.int32) + i)
    tokens = (jnp.arange(4, dtype=jnp.int32)[:, None] + 5) % spec.vocab
    args = (params, cache, tokens, jnp.asarray(pos, jnp.int32) + 3)
    unrolled = LM(dataclasses.replace(spec, scan_layers=False))
    return step(*args), jax.jit(unrolled.decode)(*args)


@pytest.mark.parametrize("pos", [(0, 5, 2, 9), 4], ids=["per-slot", "scalar"])
@pytest.mark.parametrize("name", list(CACHE_SPECS))
def test_carried_cache_decode_is_bit_identical_to_unrolled_walk(name, pos):
    (logits, cache), (want_logits, want_cache) = _carried_and_unrolled(CACHE_SPECS[name], pos)
    np.testing.assert_array_equal(np.asarray(logits), np.asarray(want_logits))
    assert jax.tree_util.tree_structure(cache) == jax.tree_util.tree_structure(want_cache)
    for got, want in zip(jax.tree_util.tree_leaves(cache),
                         jax.tree_util.tree_leaves(want_cache)):
        assert got.dtype == want.dtype
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("vector", [True, False], ids=["vector-pos", "scalar-pos"])
@pytest.mark.parametrize("name", list(CACHE_SPECS))
def test_apply_prefill_and_decode_agree_at_every_position(name, vector):
    """The one walk, three ways: ``apply``'s logits over a prompt,
    ``prefill``'s, and those of a decode loop over the prompt, one token
    a step at a per-slot position vector (or the scalar every slot
    shares), agree at every position (f32 on the CPU)."""
    spec = CACHE_SPECS[name]
    model = LM(spec)
    params = _params(spec)
    batch, length = 2, 8
    enc_out = _enc_out(model, params, batch)
    tokens = jax.random.randint(jax.random.PRNGKey(2), (batch, length), 0, spec.vocab)
    empty = model.init_cache(params, batch, 16, enc_out=enc_out, dtype=jnp.float32)

    applied = jax.jit(model.apply)(params, tokens, enc_out=enc_out)
    prefilled, _ = jax.jit(model.prefill)(params, empty, tokens)
    step, cache, decoded = jax.jit(model.decode), empty, []
    for t in range(length):
        pos = jnp.full((batch,), t, jnp.int32) if vector else t
        logits, cache = step(params, cache, tokens[:, t:t + 1], pos)
        decoded.append(logits)
    decoded = jnp.concatenate(decoded, axis=1)

    assert applied.shape == prefilled.shape == decoded.shape == (batch, length, spec.vocab)
    for got in (prefilled, decoded):
        assert float(jnp.max(jnp.abs(got - applied))) < 1e-4


def _cache_bytes(cache):
    return sum(leaf.nbytes for leaf in jax.tree_util.tree_leaves(cache))


def test_engine_decode_updates_its_cache_in_place(monkeypatch):
    """The engine's decode program on a TPU (here its CPU lowering) takes
    the cache donated and writes it in place: XLA aliases every byte of
    it to the new cache, and copies no stacked K/V leaf (a copy per leaf
    and one more was what a donated cache cost when each layer's K/V went
    through the scan as xs and ys).  Two steps in a row still serve the
    tokens that each request gives alone."""
    import re

    monkeypatch.setattr(ServingEngine, "_platform", lambda self: "tpu")
    spec = get_arch("qwen3-1.7b").smoke_spec_fn()
    model, params = LM(spec), _params(spec)
    engine = ServingEngine(model, params, max_batch=8, queue_limit=8, max_context=256)
    nbytes = _cache_bytes(engine.cache)
    assert engine.decode_cache_donated_bytes == nbytes
    tokens = jax.ShapeDtypeStruct((8, 1), jnp.int32)
    compiled = engine.decode.lower(engine.params, engine.cache, tokens,
                                   jax.ShapeDtypeStruct((8,), jnp.int32)).compile()
    assert compiled.memory_analysis().alias_size_in_bytes == nbytes
    kv = {"f32[" + ",".join(map(str, leaf.shape)) + "]"
          for path, leaf in jax.tree_util.tree_leaves_with_path(engine.cache)
          if path[-1].key in ("k", "v")}
    copies = re.findall(r"= (f32\[[\d,]+\])\S* copy\(", compiled.as_text())
    assert kv and not kv & set(copies)

    from repro.launch.traffic import TrafficSpec

    requests = TrafficSpec(seed=2, n_requests=2, arrival="burst", prompt_lens={4: 0.5, 6: 0.5},
                           gen_lens={3: 1.0}).requests()
    for req in requests:
        engine._join(req)
    engine._decode_step()
    engine._decode_step()
    served = {s["req"].id: s["out"] for s in engine.slots if s is not None}
    served.update({r["id"]: r["tokens"] for r in engine.completed})
    for req in requests:
        cache = model.init_cache(params, 1, 256, dtype=jnp.float32)
        logits, cache = model.prefill(params, cache, jnp.asarray(req.prompt_tokens(spec.vocab)[None]))
        alone = [int(jnp.argmax(logits[0, -1]))]
        for pos in range(req.prompt_len, req.prompt_len + 2):
            logits, cache = model.decode(params, cache, jnp.array([[alone[-1]]], jnp.int32),
                                         jnp.array([pos]))
            alone.append(int(jnp.argmax(logits[0, 0])))
        assert served[req.id] == alone


@pytest.mark.parametrize("platform", ["tpu", "cpu"])
@pytest.mark.parametrize("name", ["zamba2-smoke", "xlstm-smoke", "whisper-smoke"])
def test_engine_donates_the_whole_cache_on_a_tpu(name, platform, monkeypatch):
    """On a TPU every leaf of the cache, be it K/V, recurrent state or
    cross-attention K/V, is donated to the decode program; elsewhere none
    is, and the cache a step was given stays valid."""
    monkeypatch.setattr(ServingEngine, "_platform", lambda self: platform)
    spec = CACHE_SPECS[name]
    engine = ServingEngine(LM(spec), _params(spec), max_batch=2, queue_limit=2, max_context=16)
    nbytes = _cache_bytes(engine.cache)
    assert engine.decode_cache_donated_bytes == (nbytes if platform == "tpu" else 0)
    given = engine.cache
    _, engine.cache = engine.decode(engine.params, given, jnp.ones((2, 1), jnp.int32),
                                    jnp.zeros((2,), jnp.int32))
    deleted = {leaf.is_deleted() for leaf in jax.tree_util.tree_leaves(given)}
    assert deleted == {platform == "tpu"}
