"""Mamba2 chunked state-space scan for TPU (Pallas).

TPU adaptation of the GPU SSD kernels (which rely on warp scans): the
sequence is chunked; intra-chunk interactions become two MXU matmuls
((C B^T) decay-weighted panel and its product with X), and the inter-chunk
state recurrence rides the *sequential* trailing grid dimension with the
(d_state x d_head) state carried in VMEM scratch — no cross-kernel
synchronization needed, unlike the GPU two-pass formulation.

Layout is head-major so every block satisfies Mosaic's (8, 128) tiling
rule: sequence blocks are (chunk, P) / (chunk, N) tiles of (B, H, L, ·)
arrays, and each chunk's dt arrives as one (1, chunk) row of a
(B, H, n_chunks, 1, chunk) view.  The per-head decay rate ``a`` is a
scalar read from SMEM.  B/C stay in group layout (B, G, L, N); the index
map picks head ``h``'s group, so nothing is repeated per head.

Grid: (batch, heads, n_chunks)   [chunks sequential]
Per-block shapes (VMEM): x (Q, P), dt (1, Q), B/C (Q, N), state (N, P) f32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.scan_util import col, cumsum_col, row


def _ssm_kernel(
    x_ref, dt_ref, a_ref, b_ref, c_ref,
    y_ref, state_out_ref,
    state_ref,  # scratch (N, P) f32
    *,
    n_chunks: int,
):
    ih = pl.program_id(1)
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    x = x_ref[0, 0].astype(jnp.float32)  # (Q, P)
    dt = dt_ref[0, 0, 0].astype(jnp.float32)  # (1, Q)
    bmat = b_ref[0, 0].astype(jnp.float32)  # (Q, N)
    cmat = c_ref[0, 0].astype(jnp.float32)  # (Q, N)

    da = dt * a_ref[ih]  # (1, Q) log-decay; a is negative
    cs_i = cumsum_col(da)  # (Q, 1) inclusive
    cs_j = row(cs_i)  # (1, Q)
    total = jnp.sum(da, axis=1, keepdims=True)  # (1, 1)

    # intra-chunk: att[i,j] = (C_i . B_j) exp(cs_i - cs_j) dt_j, j <= i
    cb = jax.lax.dot_general(cmat, bmat, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)  # (Q, Q)
    chunk = cb.shape[0]
    iidx = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    jidx = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    logdecay = jnp.where(jidx <= iidx, cs_i - cs_j, -jnp.inf)
    att = cb * jnp.exp(logdecay) * dt
    y = jax.lax.dot_general(att, x, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)  # (Q, P)

    # inter-chunk: y += (C exp(cs)) @ state
    state = state_ref[...]
    y += jax.lax.dot_general(cmat * jnp.exp(cs_i), state,
                             (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)

    # state update: S <- exp(total) S + sum_j exp(total - cs_j) dt_j B_j x_j
    w = jnp.exp(total - cs_i) * col(dt)  # (Q, 1)
    s_chunk = jax.lax.dot_general(bmat * w, x,
                                  (((0,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)  # (N, P)
    state_ref[...] = jnp.exp(total) * state + s_chunk

    y_ref[0, 0] = y.astype(y_ref.dtype)

    @pl.when(ic == n_chunks - 1)
    def _final():
        state_out_ref[0, 0] = state_ref[...].astype(state_out_ref.dtype)


def ssm_scan_bhlp(x, dt, a, b_mat, c_mat, *, chunk=128, interpret=False):
    """Chunked SSD scan, head-major.

    x: (B, H, L, P); dt: (B, H, L) [post-softplus]; a: (H,) negative;
    b_mat/c_mat: (B, G, L, N) with H % G == 0 (head h reads group
    h // (H // G)).  Returns (y (B, H, L, P), final_state (B, H, N, P) f32).
    """
    b, h, l, p = x.shape
    g, n = b_mat.shape[1], b_mat.shape[-1]
    assert l % chunk == 0, (l, chunk)
    assert h % g == 0, (h, g)
    rep = h // g
    nc = l // chunk
    dt_rows = dt.reshape(b, h, nc, 1, chunk)

    kernel = functools.partial(_ssm_kernel, n_chunks=nc)
    seq = lambda ib, ih, ic: (ib, ih, ic, 0)  # noqa: E731
    grp = lambda ib, ih, ic: (ib, ih // rep, ic, 0)  # noqa: E731
    y, state = pl.pallas_call(
        kernel,
        grid=(b, h, nc),
        in_specs=[
            pl.BlockSpec((1, 1, chunk, p), seq),
            pl.BlockSpec((1, 1, 1, 1, chunk), lambda ib, ih, ic: (ib, ih, ic, 0, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1, chunk, n), grp),
            pl.BlockSpec((1, 1, chunk, n), grp),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, chunk, p), seq),
            pl.BlockSpec((1, 1, n, p), lambda ib, ih, ic: (ib, ih, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, l, p), x.dtype),
            jax.ShapeDtypeStruct((b, h, n, p), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((n, p), jnp.float32)],
        interpret=interpret,
    )(x, dt_rows, a.astype(jnp.float32), b_mat, c_mat)
    return y, state
