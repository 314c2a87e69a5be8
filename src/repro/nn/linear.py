"""Projections by a weight matrix, or by one layer of a stacked weight.

Decode runs a stack segment's layers in a ``lax.scan``.  Fed the
stacked f32 weights as the scan's ``xs``, each layer's matmul rounds its
slice to bf16 for the MXU (one pass, the default precision), and the
TPU compiler hoists that rounding out of the loop as a pass over the
whole stack: every step then reads the f32 stack, writes a bf16 copy
and reads the copy back.  So decode keeps the weights that
:func:`streams` accepts out of ``xs`` and hands the layer a
:class:`LayerWeight` (the stack and the scan's layer index).  On a TPU
:func:`dense` multiplies by it with
:func:`repro.kernels.ops.decode_matmul`, which rounds each tile in VMEM
as it reads it.  Elsewhere it slices the layer out and multiplies as
for an array: the CPU's f32 dot is a full f32 product, which the
kernel's rounding would change.  Of a weight whose width is not
lane-aligned the kernel takes the leading whole tiles of the default
schedule's width (zamba2's Mamba2 ``in_proj``: 10,240 of 10,448
columns, its z and x) and XLA the rest; the TPU lays such a stack out
with its contracted axis minor, so the kernel reads it as ``(L, N, K)``
(a view, not a copy).  Under the ``high`` default precision (bf16_3x,
three passes) the kernel makes the same three passes, so streaming keeps
the product XLA would give.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.kernels.schedule import LANES, default_schedule

# default matmul precisions by the bf16 passes they make of f32 operands
_PASSES = {None: 1, "default": 1, "fastest": 1, "bfloat16": 1,
           "high": 3, "bfloat16_3x": 3, "tensorfloat32": 3}


def bf16_passes():
    """The bf16 passes the default matmul precision makes of f32
    operands, or None where it is one a kernel does not give
    (``highest``)."""
    return _PASSES.get(jax.config.jax_default_matmul_precision)


class LayerWeight(NamedTuple):
    """Layer ``layer`` of a stacked ``(L, K, N)`` weight, not sliced."""

    stack: jax.Array
    layer: jax.Array  # int32 scalar


def kernel_columns(n: int) -> int:
    """The leading columns of an ``n``-wide weight that the kernel
    multiplies: all where ``n`` is lane-aligned, else as many whole tiles
    of the default schedule's width as fit."""
    if n % LANES == 0:
        return n
    tile = default_schedule("decode_matmul").block_n
    return n - n % tile


def streams(w) -> bool:
    """Whether decode reads stacked weight ``w`` a layer at a time: an
    f32 ``(L, K, N)`` stack with K lane-aligned and kernel columns in N,
    under a precision that the kernel's passes give (bf16 weights have
    nothing to round; at ``highest`` the kernel would change the
    product)."""
    return (getattr(w, "dtype", None) == jnp.float32 and w.ndim == 3
            and w.shape[1] % LANES == 0 and kernel_columns(w.shape[2]) > 0
            and bf16_passes() is not None)


def _matrix(w):
    if isinstance(w, LayerWeight):
        return jax.lax.dynamic_index_in_dim(w.stack, w.layer, keepdims=False)
    return w


def _einsum(x, w):
    return jnp.einsum("bsd,dh->bsh", x, _matrix(w))


def dense(x, w):
    """``x @ w`` on the last axis of ``x`` (B, S, K).  ``w`` is a (K, N)
    array or a LayerWeight, which a TPU multiplies by in the kernel."""
    if not isinstance(w, LayerWeight):
        return _einsum(x, w)

    # a fresh closure on purpose: traced branches are cached by function,
    # and a cached branch would hide its kernel call from the recorder
    def kernel(x, w):
        from repro.kernels import ops as kops

        b, s, k = x.shape
        width = w.stack.shape[2]
        n = kernel_columns(width)
        passes = bf16_passes()
        if n == width:
            y = kops.decode_matmul(x.reshape(b * s, k), w.stack, w.layer, passes=passes,
                                   platform="tpu")
            return y.reshape(b, s, -1)
        y = kops.decode_matmul(x.reshape(b * s, k), jnp.swapaxes(w.stack, 1, 2), w.layer,
                               n=n, k_minor=True, passes=passes,
                               platform="tpu").reshape(b, s, -1)
        # the rest of the layer's columns alone, not the whole layer
        rest = jax.lax.dynamic_slice(w.stack, (w.layer, 0, n), (1, k, width - n))[0]
        return jnp.concatenate([y, _einsum(x, rest)], axis=-1)

    return jax.lax.platform_dependent(x, w, tpu=kernel, default=_einsum)
