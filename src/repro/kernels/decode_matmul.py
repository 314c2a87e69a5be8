"""Decode projection that reads one layer of a stacked f32 weight (Pallas).

A decode step multiplies a few activation rows (one per serving slot) by
every projection weight of every layer, so it is bound by reading the
weights.  The layer scan keeps each weight stacked as ``(L, K, N)``
float32.  Handed a slice of such a stack, the TPU compiler rounds the
*whole stack* to bf16 for the MXU and hoists that rounding out of the
layer loop: each step then reads the f32 stack, writes a bf16 copy and
reads the copy back.  Here the rounding happens in VMEM, on the tile
that is being multiplied, so each f32 tile of layer ``l`` leaves HBM
once and nothing else of the stack is touched.

At ``passes=3`` the product is XLA's ``HIGH`` precision (bf16_3x): each
f32 operand is split into a bf16 high part and a bf16 remainder, and the
remainders' products with the other high part are added, which keeps
about 16 bits of each operand.

Grid: ``(N tiles, K tiles)``, K innermost; the output block stays in
VMEM across K and accumulates there.  The layer index is a
scalar-prefetch operand read by the weight's index map.  The tiles may
cover only the leading ``n`` columns of a wider stack, and the stack
may come K-minor, as ``(L, N, K)``: the TPU lays out a weight whose N
is not lane-aligned so (zamba2's Mamba2 ``in_proj``, 10,448 wide, gives
the kernel its first 10,240 columns, read as rows of its K-minor
layout).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_MIB = 1 << 20
_VMEM_CAP = 100 * _MIB


def _kernel(layer_ref, x_ref, w_ref, o_ref, *, k_minor, passes):
    del layer_ref  # read by the index maps only
    contract = (1,) if k_minor else (0,)

    def dot(a, b):  # of bf16 operands, whatever the default precision
        return jax.lax.dot_general(a, b, (((1,), contract), ((), ())),
                                   precision=jax.lax.Precision.DEFAULT,
                                   preferred_element_type=jnp.float32)

    x, w = x_ref[...], w_ref[...]
    x_hi, w_hi = x.astype(jnp.bfloat16), w.astype(jnp.bfloat16)
    part = dot(x_hi, w_hi)
    if passes == 3:
        x_lo = (x.astype(jnp.float32) - x_hi.astype(jnp.float32)).astype(jnp.bfloat16)
        w_lo = (w - w_hi.astype(jnp.float32)).astype(jnp.bfloat16)
        part += dot(x_hi, w_lo) + dot(x_lo, w_hi)

    @pl.when(pl.program_id(1) == 0)
    def _():
        o_ref[...] = part

    @pl.when(pl.program_id(1) > 0)
    def _():
        o_ref[...] += part


def _vmem_limit(m, block_k, block_n):
    """Double-buffered weight tile, x and output, doubled for the
    in-kernel bf16 parts and headroom."""
    tiles = 2 * block_k * block_n * 4
    rows = 2 * (m * block_k + m * block_n) * 4
    return min(_VMEM_CAP, max(32 * _MIB, 2 * (tiles + rows)))


@functools.partial(jax.jit, static_argnames=("block_k", "block_n", "n", "k_minor",
                                             "passes", "interpret"))
def decode_matmul_stacked(x, w, layer, *, block_k, block_n, n=None, k_minor=False,
                          passes=1, interpret=False):
    """``x @ bf16(w[layer][:, :n])``, or ``x @ bf16(w[layer][:n]).T`` with
    ``k_minor``; at ``passes=3``, the bf16_3x product instead.

    x: (M, K); w: (L, K, N) float32, or (L, N, K) with ``k_minor``;
    layer: int32 scalar; ``n`` at most N, all of it by default.
    ``block_k`` divides K and ``block_n`` divides ``n``.  Returns (M, n)
    float32, accumulated in f32 from bf16 operands.
    """
    assert passes in (1, 3), passes
    m, kdim = x.shape
    wk, wn = (2, 1) if k_minor else (1, 2)
    n = w.shape[wn] if n is None else n
    assert w.shape[wk] == kdim and kdim % block_k == 0 and n % block_n == 0, (
        w.shape, n, block_k, block_n)
    assert n <= w.shape[wn], (w.shape, n)
    if k_minor:
        w_spec = pl.BlockSpec((pl.Squeezed(), block_n, block_k),
                              lambda j, k, layer_ref: (layer_ref[0], j, k))
    else:
        w_spec = pl.BlockSpec((pl.Squeezed(), block_k, block_n),
                              lambda j, k, layer_ref: (layer_ref[0], k, j))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n // block_n, kdim // block_k),
        in_specs=[pl.BlockSpec((m, block_k), lambda j, k, layer_ref: (0, k)), w_spec],
        out_specs=pl.BlockSpec((m, block_n), lambda j, k, layer_ref: (0, j)),
    )
    return pl.pallas_call(
        functools.partial(_kernel, k_minor=k_minor, passes=passes),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_vmem_limit(m, block_k, block_n)),
        cost_estimate=pl.CostEstimate(
            flops=2 * passes * m * kdim * n,
            bytes_accessed=kdim * n * 4 + m * n * 4 + m * kdim * x.dtype.itemsize,
            transcendentals=0),
        interpret=interpret,
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), x, w)
