"""Per-kernel validation: shape/dtype sweeps vs pure-jnp oracles
(interpret mode executes the kernel bodies on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

hypothesis = pytest.importorskip("hypothesis", reason="hypothesis not installed (see requirements-dev.txt)")
from hypothesis import given, settings, strategies as st

from repro.kernels import ops, ref

KEY = jax.random.PRNGKey(0)


def _rand(key, shape, dtype):
    x = jax.random.normal(key, shape, jnp.float32)
    return x.astype(dtype)


def _tol(dtype):
    return 2e-2 if dtype == jnp.bfloat16 else 2e-5


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("s,h,kh,d", [(128, 4, 2, 64), (256, 2, 2, 32), (128, 8, 1, 64)])
def test_flash_attention_sweep(s, h, kh, d, dtype):
    ks = jax.random.split(KEY, 3)
    q = _rand(ks[0], (2, s, h, d), dtype)
    k = _rand(ks[1], (2, s, kh, d), dtype)
    v = _rand(ks[2], (2, s, kh, d), dtype)
    out = ops.flash_attention(q, k, v, causal=True, block_q=64, block_kv=64)
    want = ref.flash_attention_ref(
        q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3),
        causal=True,
    ).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(want, np.float32),
                               atol=4 * _tol(dtype), rtol=4 * _tol(dtype))


def test_flash_attention_sliding_window():
    ks = jax.random.split(KEY, 3)
    q = _rand(ks[0], (1, 256, 4, 32), jnp.float32)
    k = _rand(ks[1], (1, 256, 2, 32), jnp.float32)
    v = _rand(ks[2], (1, 256, 2, 32), jnp.float32)
    out = ops.flash_attention(q, k, v, causal=True, window=64, block_q=64, block_kv=64)
    want = ref.flash_attention_ref(
        q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3),
        causal=True, window=64,
    ).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=1e-4, rtol=1e-4)


@settings(max_examples=8, deadline=None)
@given(
    s=st.sampled_from([64, 128, 192]),
    heads=st.sampled_from([(2, 1), (4, 2), (4, 4)]),
    d=st.sampled_from([32, 64]),
)
def test_flash_attention_property(s, heads, d):
    h, kh = heads
    ks = jax.random.split(jax.random.PRNGKey(s * h * d), 3)
    q = _rand(ks[0], (1, s, h, d), jnp.float32)
    k = _rand(ks[1], (1, s, kh, d), jnp.float32)
    v = _rand(ks[2], (1, s, kh, d), jnp.float32)
    out = ops.flash_attention(q, k, v, causal=True, block_q=64, block_kv=64)
    want = ref.flash_attention_ref(
        q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3),
        causal=True,
    ).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=1e-4, rtol=1e-4)


# ---------------------------------------------------------------------------
# ssm scan (mamba2 / SSD)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("l,h,p,g,n,chunk", [
    (64, 4, 32, 2, 16, 16), (128, 2, 64, 1, 32, 32), (96, 3, 16, 3, 8, 16),
])
def test_ssm_scan_sweep(l, h, p, g, n, chunk, dtype):
    ks = jax.random.split(KEY, 5)
    x = _rand(ks[0], (2, l, h, p), dtype)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (2, l, h)))
    a = -jnp.exp(jax.random.normal(ks[2], (h,)) * 0.3)
    bm = _rand(ks[3], (2, l, g, n), dtype)
    cm = _rand(ks[4], (2, l, g, n), dtype)
    y, st_ = ops.ssm_scan(x, dt, a, bm, cm, chunk=chunk)
    yref, stref = ref.ssm_scan_ref(
        x, dt, a, jnp.repeat(bm, h // g, 2), jnp.repeat(cm, h // g, 2), chunk=chunk
    )
    np.testing.assert_allclose(np.asarray(y, np.float32), np.asarray(yref, np.float32),
                               atol=8 * _tol(dtype), rtol=8 * _tol(dtype))
    np.testing.assert_allclose(np.asarray(st_), np.asarray(stref),
                               atol=8 * _tol(dtype), rtol=8 * _tol(dtype))


def test_ssm_scan_matches_recurrence():
    """Chunked kernel == step-by-step recurrence (the strictest oracle)."""
    from repro.nn.ssm import ssd_recurrent_step

    l, h, p, n = 32, 2, 16, 8
    ks = jax.random.split(KEY, 5)
    x = _rand(ks[0], (1, l, h, p), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (1, l, h)))
    a = -jnp.exp(jax.random.normal(ks[2], (h,)) * 0.3)
    bm = _rand(ks[3], (1, l, 1, n), jnp.float32)
    cm = _rand(ks[4], (1, l, 1, n), jnp.float32)
    y, _ = ops.ssm_scan(x, dt, a, bm, cm, chunk=8)
    state = jnp.zeros((1, h, n, p))
    outs = []
    for t in range(l):
        yt, state = ssd_recurrent_step(state, x[:, t], dt[:, t], a, bm[:, t], cm[:, t])
        outs.append(yt[:, None])
    want = jnp.concatenate(outs, 1)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), atol=1e-4, rtol=1e-3)


# ---------------------------------------------------------------------------
# mlstm scan
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("l,h,p,chunk", [(64, 2, 32, 16), (128, 4, 16, 32)])
def test_mlstm_scan_sweep(l, h, p, chunk):
    ks = jax.random.split(KEY, 5)
    q = _rand(ks[0], (2, l, h, p), jnp.float32)
    k = _rand(ks[1], (2, l, h, p), jnp.float32)
    v = _rand(ks[2], (2, l, h, p), jnp.float32)
    il = jax.random.normal(ks[3], (2, l, h)) * 2.0
    fl = jax.nn.log_sigmoid(jax.random.normal(ks[4], (2, l, h)) + 3.0)
    hout, _ = ops.mlstm_scan(q, k, v, il, fl, chunk=chunk)
    want = ref.mlstm_scan_ref(q, k, v, il, fl)
    np.testing.assert_allclose(np.asarray(hout), np.asarray(want), atol=2e-4, rtol=2e-3)


@settings(max_examples=6, deadline=None)
@given(chunk=st.sampled_from([8, 16, 32]), gate_bias=st.sampled_from([-2.0, 1.0, 5.0]))
def test_mlstm_chunk_invariance(chunk, gate_bias):
    """Output must not depend on the chunk size (pure reformulation)."""
    l, h, p = 64, 2, 16
    ks = jax.random.split(jax.random.PRNGKey(int(gate_bias * 10) + chunk), 5)
    q = _rand(ks[0], (1, l, h, p), jnp.float32)
    k = _rand(ks[1], (1, l, h, p), jnp.float32)
    v = _rand(ks[2], (1, l, h, p), jnp.float32)
    il = jax.random.normal(ks[3], (1, l, h))
    fl = jax.nn.log_sigmoid(jax.random.normal(ks[4], (1, l, h)) + gate_bias)
    h1, _ = ops.mlstm_scan(q, k, v, il, fl, chunk=chunk)
    want = ref.mlstm_scan_ref(q, k, v, il, fl)
    np.testing.assert_allclose(np.asarray(h1), np.asarray(want), atol=3e-4, rtol=3e-3)


# ---------------------------------------------------------------------------
# decode matmul (one layer of a stacked f32 weight)
# ---------------------------------------------------------------------------

# qwen3-1.7b's decode projections, 8 slots, 2 of its 28 layers
QWEN3_DECODE = {"q": (2048, 2048), "kv": (2048, 1024), "mlp_in": (2048, 6144),
                "down": (6144, 2048)}


def _stack(key, k, n, layers=2):
    return _rand(key, (layers, k, n), jnp.float32) * k ** -0.5


@pytest.mark.parametrize("layer", [0, 1])
@pytest.mark.parametrize("proj", sorted(QWEN3_DECODE))
def test_decode_matmul_matches_reference(proj, layer):
    k, n = QWEN3_DECODE[proj]
    ks = jax.random.split(KEY, 2)
    x = _rand(ks[0], (8, k), jnp.float32)
    w = _stack(ks[1], k, n)
    out = ops.decode_matmul(x, w, jnp.int32(layer))
    want = ref.decode_matmul_ref(x, w, layer)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("layer", [0, 1])
def test_decode_matmul_reads_leading_columns_of_a_k_minor_stack(layer):
    """zamba2's Mamba2 in_proj at its published widths (2560 x 10448):
    the kernel takes the first 10240 columns of the stack handed over
    as (L, N, K)."""
    ks = jax.random.split(KEY, 2)
    x = _rand(ks[0], (8, 2560), jnp.float32)
    w = _stack(ks[1], 2560, 10448)
    out = ops.decode_matmul(x, jnp.swapaxes(w, 1, 2), jnp.int32(layer), n=10240, k_minor=True)
    want = ref.decode_matmul_ref(x, w, layer)[:, :10240]
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("k_minor", [False, True], ids=["k-major", "k-minor"])
@pytest.mark.parametrize("proj", ["kv", "down"])
def test_decode_matmul_three_passes_is_bf16_3x(proj, k_minor):
    """``passes=3`` is XLA's ``high`` precision: the reference's bf16_3x
    product, and far nearer the float32 product than one pass."""
    k, n = QWEN3_DECODE[proj]
    ks = jax.random.split(KEY, 2)
    x = _rand(ks[0], (8, k), jnp.float32)
    w = _stack(ks[1], k, n)
    stack = jnp.swapaxes(w, 1, 2) if k_minor else w
    out = np.asarray(ops.decode_matmul(x, stack, jnp.int32(1), k_minor=k_minor, passes=3))
    want = np.asarray(ref.decode_matmul_ref(x, w, 1, passes=3))
    np.testing.assert_allclose(out, want, atol=1e-5, rtol=1e-5)
    exact = np.asarray(jnp.dot(x, w[1], precision=jax.lax.Precision.HIGHEST))
    one = np.asarray(ref.decode_matmul_ref(x, w, 1))
    assert np.abs(out - exact).max() * 100 < np.abs(one - exact).max()


@pytest.mark.parametrize("proj", ["kv", "down"])
def test_decode_matmul_follows_a_scanned_layer_index(proj):
    """The layer index as decode's scan hands it in: traced, one per
    step, over a stack of three layers."""
    k, n = QWEN3_DECODE[proj]
    ks = jax.random.split(KEY, 2)
    x = _rand(ks[0], (8, k), jnp.float32)
    w = _stack(ks[1], k, n, layers=3)
    _, out = jax.lax.scan(lambda c, layer: (c, ops.decode_matmul(x, w, layer)),
                          None, jnp.arange(3, dtype=jnp.int32))
    for layer in range(3):
        np.testing.assert_allclose(np.asarray(out[layer]),
                                   np.asarray(ref.decode_matmul_ref(x, w, layer)),
                                   atol=1e-5, rtol=1e-5)


# ---------------------------------------------------------------------------
# decode attention (one token over one layer of a stacked K/V cache)
# ---------------------------------------------------------------------------

# 4 slots at positions 0, block - 1, block and T - 1 of a 37-position
# cache read in blocks of 8: one block, one whole block, one row into the
# second, and the last block, which runs past the stack
DECODE_BLOCK, DECODE_T = 8, 37
DECODE_POS = (0, DECODE_BLOCK - 1, DECODE_BLOCK, DECODE_T - 1)


def _poison(stack, layer, lengths):
    """``stack`` with every layer but ``layer`` NaN, and in ``layer`` each
    slot's blocks wholly past its last valid one."""
    start = -(-lengths // DECODE_BLOCK) * DECODE_BLOCK
    past = jnp.arange(stack.shape[2])[None, :] >= start[:, None]  # (B, T)
    poisoned = past[None, :, :, None, None] | (jnp.arange(stack.shape[0]) != layer)[
        :, None, None, None, None]
    return jnp.where(poisoned, jnp.nan, stack).astype(stack.dtype)


@pytest.mark.parametrize("poisoned", [False, True], ids=["clean", "poisoned"])
@pytest.mark.parametrize("layer", [0, 2])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("group", [1, 2])
def test_decode_attention_reads_each_slots_valid_blocks(group, dtype, layer, poisoned):
    """Ragged per-slot lengths against the masked grouped attention of
    the same bf16 operands.  Poisoned, the blocks no slot may read hold
    NaN (so does every other layer): the output is finite and the same to
    the bit, so the kernel neither fetched nor summed them."""
    kheads, dh = 2, 128
    ks = jax.random.split(KEY, 3)
    q = _rand(ks[0], (len(DECODE_POS), kheads * group, dh), jnp.float32)
    k = _rand(ks[1], (3, len(DECODE_POS), DECODE_T, kheads, dh), dtype)
    v = _rand(ks[2], (3, len(DECODE_POS), DECODE_T, kheads, dh), dtype)
    lengths = jnp.asarray(DECODE_POS, jnp.int32) + 1
    schedule = {"block_kv": DECODE_BLOCK}

    def run(k, v):
        return ops.decode_attention(q, k, v, jnp.int32(layer), lengths, scale=dh ** -0.5,
                                    schedule=schedule)

    out = run(k, v)
    want = ref.decode_attention_ref(q, k.astype(jnp.float32), v.astype(jnp.float32), layer,
                                    lengths, scale=dh ** -0.5)
    assert out.shape == q.shape and out.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=5e-3, rtol=0)
    if poisoned:
        bad = run(_poison(k, layer, lengths), _poison(v, layer, lengths))
        assert np.isfinite(np.asarray(bad)).all()
        np.testing.assert_array_equal(np.asarray(bad), np.asarray(out))
