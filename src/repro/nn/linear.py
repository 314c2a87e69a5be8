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
kernel's rounding would change.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.kernels.schedule import LANES

# default matmul precisions that round f32 operands to bf16 in one pass
_ONE_PASS = (None, "default", "fastest", "bfloat16")


class LayerWeight(NamedTuple):
    """Layer ``layer`` of a stacked ``(L, K, N)`` weight, not sliced."""

    stack: jax.Array
    layer: jax.Array  # int32 scalar


def streams(w) -> bool:
    """Whether decode reads stacked weight ``w`` a layer at a time: an
    f32 ``(L, K, N)`` stack with K and N lane-aligned, under a precision
    that rounds the operands to bf16 (bf16 weights have nothing to
    round; at a higher precision the kernel would change the product)."""
    return (getattr(w, "dtype", None) == jnp.float32 and w.ndim == 3
            and w.shape[1] % LANES == 0 and w.shape[2] % LANES == 0
            and jax.config.jax_default_matmul_precision in _ONE_PASS)


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
        y = kops.decode_matmul(x.reshape(b * s, k), w.stack, w.layer, platform="tpu")
        return y.reshape(b, s, -1)

    return jax.lax.platform_dependent(x, w, tpu=kernel, default=_einsum)
