"""Chunkwise mLSTM (xLSTM matrix memory) for TPU (Pallas).

Same TPU chunking strategy as the SSD kernel: intra-chunk gated attention
panels on the MXU, inter-chunk (C, n, m) matrix-memory state carried in
VMEM scratch across the sequential chunk axis.  Exponential gates are
stabilized with the running max ``m`` exactly as the recurrent oracle.

Head-major layout as in the SSD kernel: q/k/v are (chunk, P) tiles of
(B, H, L, P) arrays and each chunk's gates arrive as one (1, chunk) row.

Grid: (batch, heads, n_chunks)   [chunks sequential]
Per-block: q/k/v (Q, P); gates (1, Q); state C (P, P), n (1, P),
m (1, 1) f32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.scan_util import col, cumsum_col, row

BIG_NEG = -1e6
# v5e has 128 MiB of VMEM.  Mosaic's default scoped limit runs out at
# P = 1024 with chunk 512 in f32 (a tuner candidate): the (P, P) state,
# its update and the (chunk, P) blocks do not fit
_MIB = 1 << 20
_VMEM_CAP = 100 * _MIB


def _mlstm_kernel(
    q_ref, k_ref, v_ref, i_ref, f_ref,
    h_ref,
    c_ref, n_ref, m_ref,  # scratch: (P, P), (1, P), (1, 1)
    *,
    scale: float,
):
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        c_ref[...] = jnp.zeros_like(c_ref)
        n_ref[...] = jnp.zeros_like(n_ref)
        m_ref[...] = jnp.full_like(m_ref, BIG_NEG)

    q = q_ref[0, 0].astype(jnp.float32)  # (Q, P)
    k = k_ref[0, 0].astype(jnp.float32) * scale
    v = v_ref[0, 0].astype(jnp.float32)
    ig = i_ref[0, 0, 0].astype(jnp.float32)  # (1, Q) log input gate
    fg = f_ref[0, 0, 0].astype(jnp.float32)  # (1, Q) log forget gate

    fcum_i = cumsum_col(fg)  # (Q, 1) inclusive
    fcum_j = row(fcum_i)  # (1, Q)
    ftot = jnp.sum(fg, axis=1, keepdims=True)  # (1, 1)
    m_prev = m_ref[...]  # (1, 1)
    c_prev = c_ref[...]
    n_prev = n_ref[...]  # (1, P)

    # intra log-weights a[i,j] = fcum_i - fcum_j + ig_j (j<=i); inter b[i]
    chunk = q.shape[0]
    iidx = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    jidx = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    a_log = jnp.where(jidx <= iidx, fcum_i - fcum_j + ig, -jnp.inf)
    b_log = fcum_i + m_prev  # (Q, 1)
    m_i = jnp.maximum(jnp.max(a_log, axis=1, keepdims=True), b_log)
    m_i = jnp.maximum(m_i, BIG_NEG)

    intra_w = jnp.exp(a_log - m_i)  # (Q, Q)
    inter_w = jnp.exp(b_log - m_i)  # (Q, 1)

    qk = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    s_intra = qk * intra_w
    h_num = jax.lax.dot_general(s_intra, v, (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
    h_num += jax.lax.dot_general(q, c_prev, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32) * inter_w
    denom = jnp.sum(s_intra, axis=1, keepdims=True)
    denom += jnp.sum(q * n_prev, axis=1, keepdims=True) * inter_w
    denom = jnp.maximum(jnp.abs(denom), jnp.exp(-m_i))
    h_ref[0, 0] = (h_num / denom).astype(h_ref.dtype)

    # state update to chunk end
    w_log = ftot - fcum_i + col(ig)  # (Q, 1)
    m_next = jnp.maximum(ftot + m_prev, jnp.max(w_log, axis=0, keepdims=True))
    m_next = jnp.maximum(m_next, BIG_NEG)
    kw = k * jnp.exp(w_log - m_next)  # (Q, P)
    # (1, P): Mosaic cannot broadcast a (1, 1) over sublanes and lanes
    # at once, so the (P, P) update takes the decay as a row
    carry = jnp.exp(jnp.broadcast_to(ftot + m_prev - m_next, n_prev.shape))
    c_ref[...] = carry * c_prev + jax.lax.dot_general(
        kw, v, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    n_ref[...] = carry * n_prev + jnp.sum(kw, axis=0, keepdims=True)
    m_ref[...] = m_next


def _vmem_limit(chunk: int, p: int, itemsize: int) -> int:
    """Scoped-VMEM budget: double-buffered q/k/v/h blocks, the (P, P)
    state plus one update-sized temporary, and a few (Q, Q) / (Q, P)
    f32 panels; doubled for headroom and capped below v5e's VMEM."""
    blocks = 2 * 4 * chunk * p * itemsize
    state = 2 * p * p * 4
    panels = 6 * chunk * max(chunk, p) * 4
    return min(_VMEM_CAP, max(32 * _MIB, 2 * (blocks + state + panels)))


def mlstm_scan_bhlp(q, k, v, i_log, f_log, *, chunk=128, interpret=False):
    """q/k/v: (B, H, L, P); i_log/f_log: (B, H, L).  Returns h (B, H, L, P)."""
    b, h, l, p = q.shape
    assert l % chunk == 0, (l, chunk)
    nc = l // chunk
    kernel = functools.partial(_mlstm_kernel, scale=p ** -0.5)
    seq_spec = pl.BlockSpec((1, 1, chunk, p), lambda ib, ih, ic: (ib, ih, ic, 0))
    gate_spec = pl.BlockSpec((1, 1, 1, 1, chunk),
                             lambda ib, ih, ic: (ib, ih, ic, 0, 0))
    gates = [g.reshape(b, h, nc, 1, chunk) for g in (i_log, f_log)]
    return pl.pallas_call(
        kernel,
        grid=(b, h, nc),
        in_specs=[seq_spec, seq_spec, seq_spec, gate_spec, gate_spec],
        out_specs=seq_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, l, p), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((p, p), jnp.float32),
            pltpu.VMEM((1, p), jnp.float32),
            pltpu.VMEM((1, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_vmem_limit(chunk, p, q.dtype.itemsize)),
        interpret=interpret,
    )(q, k, v, *gates)
