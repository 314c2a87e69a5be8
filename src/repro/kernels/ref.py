"""Pure-jnp oracles for every Pallas kernel.

These delegate to the nn-substrate reference implementations so the
kernels are validated against exactly the math the models use.
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.nn.attention import grouped_attention, make_mask
from repro.nn.ssm import ssd_chunked
from repro.nn.xlstm import mlstm_recurrent


def flash_attention_ref(q, k, v, *, causal=True, window=None, scale=None):
    """q: (B, H, S, D); k/v: (B, KH, T, D) -> (B, H, S, D)."""
    b, h, s, d = q.shape
    t = k.shape[2]
    scale = scale if scale is not None else d ** -0.5
    q_ = q.transpose(0, 2, 1, 3)  # (B, S, H, D)
    k_ = k.transpose(0, 2, 1, 3)
    v_ = v.transpose(0, 2, 1, 3)
    mask = make_mask(s, t, causal, window)
    out = grouped_attention(q_, k_, v_, mask, scale)
    return out.transpose(0, 2, 1, 3)


def ssm_scan_ref(x, dt, a, b_mat, c_mat, *, chunk=128):
    """x: (B, L, H, P), b/c pre-expanded to per-head (B, L, H, N)."""
    return ssd_chunked(x, dt, a, b_mat, c_mat, chunk)


def mlstm_scan_ref(q, k, v, i_log, f_log):
    """Recurrent oracle (per-step), the strictest reference."""
    h, _ = mlstm_recurrent(q, k, v, i_log, f_log)
    return h


def decode_matmul_ref(x, w, layer, passes=1):
    """x: (M, K); w: (L, K, N) -> x @ w[layer], bf16 operands, f32 sums;
    at ``passes=3`` the bf16_3x product: the high parts' product plus
    each high part times the other operand's bf16 remainder."""
    def dot(a, b):
        return jnp.dot(a, b, preferred_element_type=jnp.float32)

    bf16 = jnp.bfloat16
    x_hi, w_hi = x.astype(bf16), w[layer].astype(bf16)
    out = dot(x_hi, w_hi)
    if passes == 3:
        x_lo = (x - x_hi.astype(jnp.float32)).astype(bf16)
        w_lo = (w[layer] - w_hi.astype(jnp.float32)).astype(bf16)
        out = out + dot(x_hi, w_lo) + dot(x_lo, w_hi)
    return out


def decode_attention_ref(q, k, v, layer, lengths, *, scale):
    """q: (B, H, Dh); k/v: (L, B, T, K, Dh) -> (B, H, Dh): the masked
    grouped attention of each slot's query over positions
    ``[0, lengths[b])`` of layer ``layer``, from bf16-rounded q, K and V
    (the kernel's operands) with f32 scores and probabilities."""
    def bf16(x):
        return x.astype(jnp.bfloat16).astype(jnp.float32)

    t = k.shape[2]
    mask = (jnp.arange(t)[None, :] < lengths[:, None])[:, None, None, None, :]
    out = grouped_attention(bf16(q)[:, None], bf16(k[layer]), bf16(v[layer]), mask, scale)
    return out[:, 0]
