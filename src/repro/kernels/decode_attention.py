"""One-token GQA attention over one layer of a stacked K/V cache (Pallas).

Decode attends each serving slot's new query to the rows that slot has
written, positions ``[0, length)``, but the cache reserves
``max_context`` positions a slot.  Sliced out of the stack and masked,
every step reads all of them.  Here each slot reads its K/V in blocks of
``block_t`` positions, and only the blocks that hold a valid row: the
index map of a block wholly past the slot's length points at the slot's
last valid block, which is already in VMEM, so the pipeline issues no
copy for it, and ``pl.when`` skips its compute.  Positions at or past
the length inside the last block are masked.

The stack is read in place: a block is ``(block_t, K, Dh)`` of layer
``layer`` and slot ``b`` of the ``(L, B, T, K, Dh)`` stack, whole
``(K, Dh)`` tiles (qwen3: ``(8, 128)`` f32).  Per K/V head the kernel
takes a strided ``(block_t, Dh)`` view and runs two MXU products, q
against K and the probabilities against V, with an online softmax
(``m``, ``l``, ``acc``) in VMEM scratch across the blocks.

Precision is one bf16 pass, as XLA's default makes of f32 operands: q,
K, the probabilities and V are rounded to bf16 in VMEM and the products
accumulated in f32.

Grid: ``(B, ceil(T / block_t))``, the blocks innermost and in order.
The layer and the lengths are scalar-prefetch operands, read by the
index maps.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
_MIB = 1 << 20
_VMEM_CAP = 100 * _MIB


def _kernel(layer_ref, lengths_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            block_t, seq, scale):
    del layer_ref  # read by the index maps only
    j = pl.program_id(1)
    length = lengths_ref[pl.program_id(0)]
    bf16 = jnp.bfloat16

    def dot(a, b, contract):  # of bf16 operands, whatever the default precision
        return jax.lax.dot_general(a.astype(bf16), b.astype(bf16), (contract, ((), ())),
                                   precision=jax.lax.Precision.DEFAULT,
                                   preferred_element_type=jnp.float32)

    @pl.when(j == 0)
    def _():
        m_ref[...] = jnp.full(m_ref.shape, NEG_INF, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    @pl.when(j * block_t < length)
    def _():
        start = j * block_t
        valid = start + jax.lax.broadcasted_iota(jnp.int32, (1, block_t), 1) < length
        in_bounds = start + jax.lax.broadcasted_iota(jnp.int32, (block_t, 1), 0) < seq
        for h in range(k_ref.shape[1]):
            k, v = k_ref[:, h, :], v_ref[:, h, :]  # (block_t, Dh)
            if seq % block_t:
                # the last block runs past the stack, and what lies there
                # is not the cache's: keep it out of the sums
                v = jnp.where(in_bounds, v, 0)
            s = jnp.where(valid, dot(q_ref[h], k, ((1,), (1,))) * scale, NEG_INF)  # (G, block_t)
            m_prev = m_ref[h]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m_prev - m_new)
            l_ref[h] = alpha * l_ref[h] + jnp.sum(p, axis=1, keepdims=True)
            acc_ref[h] = alpha * acc_ref[h] + dot(p, v, ((1,), (0,)))
            m_ref[h] = m_new

    @pl.when(j == pl.num_programs(1) - 1)
    def _():
        o_ref[...] = acc_ref[...] / l_ref[...]


def _vmem_limit(block_t, kheads, dh, itemsize):
    """K and V blocks double-buffered, doubled for their bf16 copies and
    the scores, with headroom."""
    blocks = 2 * 2 * block_t * max(kheads, 8) * dh * itemsize
    return min(_VMEM_CAP, max(32 * _MIB, 2 * blocks))


@functools.partial(jax.jit, static_argnames=("scale", "block_t", "interpret"))
def decode_attention_stacked(q, k, v, layer, lengths, *, scale, block_t, interpret=False):
    """Attention of each slot's query over positions ``[0, lengths[b])``
    of layer ``layer`` of the stacked cache.

    q: (B, H, Dh); k, v: (L, B, T, K, Dh), H a multiple of K, query head
    ``h`` reading K/V head ``h // (H // K)``; layer: int32 scalar;
    lengths: (B,) int32 in ``[1, T]``.  Returns (B, H, Dh) float32.
    """
    b, heads, dh = q.shape
    _, _, seq, kheads, _ = k.shape
    group = heads // kheads
    assert heads == group * kheads and k.shape == v.shape, (q.shape, k.shape, v.shape)
    nblocks = pl.cdiv(seq, block_t)

    def kv_map(i, j, layer_ref, lengths_ref):
        # past the slot's last valid block: that block again, so no copy
        last = (lengths_ref[i] - 1) // block_t
        return layer_ref[0], i, jnp.minimum(j, last), 0, 0

    kv_spec = pl.BlockSpec((pl.Squeezed(), pl.Squeezed(), block_t, kheads, dh), kv_map)
    q_spec = pl.BlockSpec((pl.Squeezed(), kheads, group, dh), lambda i, j, *_: (i, 0, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, nblocks),
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=q_spec,
        scratch_shapes=[pltpu.VMEM((kheads, group, 1), jnp.float32),
                        pltpu.VMEM((kheads, group, 1), jnp.float32),
                        pltpu.VMEM((kheads, group, dh), jnp.float32)],
    )
    out = pl.pallas_call(
        functools.partial(_kernel, block_t=block_t, seq=seq, scale=scale),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kheads, group, dh), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_vmem_limit(block_t, kheads, dh, k.dtype.itemsize)),
        cost_estimate=pl.CostEstimate(
            flops=4 * b * heads * seq * dh,
            bytes_accessed=2 * b * seq * kheads * dh * k.dtype.itemsize + 2 * q.size * 4,
            transcendentals=b * heads * seq),
        interpret=interpret,
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), lengths.astype(jnp.int32),
      q.reshape(b, kheads, group, dh), k, v)
    return out.reshape(b, heads, dh)
