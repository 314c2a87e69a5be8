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

Grid: ``(N tiles, K tiles)``, K innermost; the output block stays in
VMEM across K and accumulates there.  The layer index is a
scalar-prefetch operand read by the weight's index map.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_MIB = 1 << 20
_VMEM_CAP = 100 * _MIB


def _kernel(layer_ref, x_ref, w_ref, o_ref):
    del layer_ref  # read by the index maps only
    part = jnp.dot(x_ref[...].astype(jnp.bfloat16), w_ref[...].astype(jnp.bfloat16),
                   preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(1) == 0)
    def _():
        o_ref[...] = part

    @pl.when(pl.program_id(1) > 0)
    def _():
        o_ref[...] += part


def _vmem_limit(m, block_k, block_n):
    """Double-buffered weight tile, x and output, doubled for the
    in-kernel bf16 copies and headroom."""
    tiles = 2 * block_k * block_n * 4
    rows = 2 * (m * block_k + m * block_n) * 4
    return min(_VMEM_CAP, max(32 * _MIB, 2 * (tiles + rows)))


@functools.partial(jax.jit, static_argnames=("block_k", "block_n", "interpret"))
def decode_matmul_stacked(x, w, layer, *, block_k, block_n, interpret=False):
    """``x @ bf16(w[layer])``.

    x: (M, K); w: (L, K, N) float32; layer: int32 scalar.  ``block_k``
    divides K and ``block_n`` divides N.  Returns (M, N) float32,
    accumulated in f32 from bf16 operands.
    """
    m, kdim = x.shape
    n = w.shape[2]
    assert w.shape[1] == kdim and kdim % block_k == 0 and n % block_n == 0, (
        w.shape, block_k, block_n)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n // block_n, kdim // block_k),
        in_specs=[pl.BlockSpec((m, block_k), lambda j, k, layer_ref: (0, k)),
                  pl.BlockSpec((pl.Squeezed(), block_k, block_n),
                               lambda j, k, layer_ref: (layer_ref[0], k, j))],
        out_specs=pl.BlockSpec((m, block_n), lambda j, k, layer_ref: (0, j)),
    )
    return pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_vmem_limit(m, block_k, block_n)),
        cost_estimate=pl.CostEstimate(
            flops=2 * m * kdim * n,
            bytes_accessed=kdim * n * 4 + m * n * 4 + m * kdim * x.dtype.itemsize,
            transcendentals=0),
        interpret=interpret,
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), x, w)
