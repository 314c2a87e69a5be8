"""Plain ``jax.numpy`` pieces shared by the references in
``bench/reference/``.  Nothing here imports the program.

Every function takes ``prec`` (the matmul precision, ``HIGHEST`` for the
reference) and computes in the dtype of its inputs; statistics of norms
and softmax are taken in float32, as a lower-precision serving path
would also take them.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
DEFAULT = jax.lax.Precision.DEFAULT


def mm(x, w, prec):
    return jnp.matmul(x, w.astype(x.dtype), precision=prec)


def rmsnorm(x, scale, eps):
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (y * scale.astype(jnp.float32)).astype(x.dtype)


def rope(x, theta):
    """Rotary embedding over the last axis, halves rotated (not
    interleaved); x: (T, heads, head_dim), positions 0..T-1."""
    t, _, dh = x.shape
    half = dh // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half].astype(jnp.float32), x[..., half:].astype(jnp.float32)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1).astype(x.dtype)


def causal_attention(q, k, v, prec):
    """q: (T, H, dh); k, v: (T, K, dh) with H a multiple of K."""
    t, h, dh = q.shape
    kh = k.shape[1]
    q = q.reshape(t, kh, h // kh, dh)
    s = jnp.einsum("tkgd,skd->kgts", q, k, precision=prec).astype(jnp.float32)
    s = s * dh ** -0.5
    mask = jnp.tril(jnp.ones((t, t), bool))
    s = jnp.where(mask, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1).astype(v.dtype)
    o = jnp.einsum("kgts,skd->tkgd", p, v, precision=prec)
    return o.reshape(t, h * dh)


def gelu_tanh(x):
    xf = x.astype(jnp.float32)
    y = 0.5 * xf * (1.0 + jnp.tanh((2.0 / jnp.pi) ** 0.5 * (xf + 0.044715 * xf ** 3)))
    return y.astype(x.dtype)


def silu(x):
    xf = x.astype(jnp.float32)
    return (xf * jax.nn.sigmoid(xf)).astype(x.dtype)
