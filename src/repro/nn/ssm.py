"""Mamba2 (state-space duality) block, TPU-native chunked formulation.

The GPU reference implementations use warp-level scans; on TPU we use the
*chunked parallel form*: the sequence is split into chunks of ``chunk``
steps, intra-chunk interactions become MXU matmuls, and the inter-chunk
state recurrence is a short `lax.scan` over ``L/chunk`` carries.  The same
decomposition is implemented as a Pallas kernel in
``repro/kernels/ssm_scan.py`` with this module's ``ssd_chunked`` (via
``repro/kernels/ref.py``) as its oracle.

Layout conventions:
  x     (B, L, H, P)   inner activations, H heads of dim P
  dt    (B, L, H)      softplus-discretized step sizes
  A     (H,)           negative per-head decay rates
  B_, C_ (B, L, G, N)  input/output projections, G groups, state size N
State: (B, H, N, P).
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.nn import initializers as init
from repro.nn.linear import dense
from repro.nn.types import P as Param


@dataclasses.dataclass(frozen=True)
class Mamba2Config:
    d_model: int
    d_state: int = 64
    d_head: int = 64
    expand: int = 2
    n_groups: int = 1
    conv_width: int = 4
    chunk: int = 128
    impl: str = "xla"  # "xla" | "pallas"
    norm_eps: float = 1e-6  # of the gated RMSNorm before out_proj

    @property
    def d_inner(self) -> int:
        return self.expand * self.d_model

    @property
    def n_heads(self) -> int:
        return self.d_inner // self.d_head


# the weights multiplied by ``nn.linear.dense``, which the decode of a
# layer scan may hand in a layer at a time (``nn/linear.py``)
PROJECTIONS = ("in_proj", "out_proj")


def mamba2_init(cfg: Mamba2Config, key, dtype=jnp.float32):
    d_in = cfg.d_inner
    conv_dim = d_in + 2 * cfg.n_groups * cfg.d_state
    proj_out = 2 * d_in + 2 * cfg.n_groups * cfg.d_state + cfg.n_heads
    k1, k2, k3, k4 = jax.random.split(key, 4)
    return {
        "in_proj": Param(init.scaled_normal(k1, (cfg.d_model, proj_out), dtype), ("embed", "mlp")),
        "conv_w": Param(init.scaled_normal(k2, (cfg.conv_width, conv_dim), dtype, fan_in=cfg.conv_width), (None, "mlp")),
        "conv_b": Param(jnp.zeros((conv_dim,), dtype), ("mlp",)),
        "A_log": Param(jnp.log(jnp.linspace(1.0, 16.0, cfg.n_heads)).astype(jnp.float32), (None,)),
        "D": Param(jnp.ones((cfg.n_heads,), jnp.float32), (None,)),
        "dt_bias": Param(jnp.zeros((cfg.n_heads,), jnp.float32), (None,)),
        "norm_scale": Param(jnp.ones((d_in,), dtype), ("mlp",)),
        "out_proj": Param(init.scaled_normal(k3, (d_in, cfg.d_model), dtype, fan_in=d_in), ("mlp", "embed")),
    }


def causal_conv1d(x, w, b, state=None):
    """Depthwise causal conv.  x: (B,L,C), w: (W,C).

    ``state`` (B, W-1, C) holds the inputs before ``x`` (zeros when not
    given); with it, also returns the last W-1 inputs, the state after
    ``x`` (one decode step, or a prompt from the cache).
    """
    width, length = w.shape[0], x.shape[1]
    prev = state if state is not None else jnp.zeros(
        x.shape[:1] + (width - 1,) + x.shape[2:], x.dtype)
    xp = jnp.concatenate([prev, x], axis=1)
    # (B, L, W, C) windows via stacked slices (W is tiny: 4).
    windows = jnp.stack([xp[:, i : i + length] for i in range(width)], axis=2)
    y = jnp.einsum("blwc,wc->blc", windows, w) + b
    return y if state is None else (y, xp[:, length:])


def _segsum_cumsum(a):
    """Inclusive cumsum over the chunk axis (axis=-2 of (..., Q, H))."""
    return jnp.cumsum(a, axis=-2)


def ssd_chunked(x, dt, A, B_, C_, chunk, initial_state=None):
    """Chunked SSD scan.  Shapes per module docstring; returns (y, final_state).

    y: (B, L, H, P);  final_state: (B, H, N, P).  ``initial_state``
    (B, H, N, P) is the state before the first step (zeros by default).
    """
    b, l, h, p = x.shape
    g, n = B_.shape[2], B_.shape[3]
    assert l % chunk == 0, f"seq {l} must divide chunk {chunk}"
    nc, q = l // chunk, chunk
    rep = h // g

    xc = x.reshape(b, nc, q, h, p)
    dtc = dt.reshape(b, nc, q, h).astype(jnp.float32)
    Bc = jnp.repeat(B_.reshape(b, nc, q, g, n), rep, axis=3)  # (b,nc,q,h,n)
    Cc = jnp.repeat(C_.reshape(b, nc, q, g, n), rep, axis=3)

    a = dtc * A[None, None, None, :]  # (b,nc,q,h) log-decay, negative
    cs = _segsum_cumsum(a)  # inclusive cumsum within chunk
    total = cs[:, :, -1]  # (b,nc,h)

    # Intra-chunk: att[i,j] = (C_i . B_j) exp(cs_i - cs_j) dt_j for j <= i.
    cb = jnp.einsum("bcqhn,bckhn->bchqk", Cc, Bc).astype(jnp.float32)
    cs_i = cs.transpose(0, 1, 3, 2)[:, :, :, :, None]  # (b,nc,h,q_i,1)
    cs_j = cs.transpose(0, 1, 3, 2)[:, :, :, None, :]  # (b,nc,h,1,q_j)
    tri = jnp.tril(jnp.ones((q, q), bool))[None, None, None]
    # Mask in log-space BEFORE exp so j>i never overflows.
    decay = jnp.exp(jnp.where(tri, cs_i - cs_j, -jnp.inf))  # (b,nc,h,q_i,q_j)
    att = cb * decay * dtc.transpose(0, 1, 3, 2)[:, :, :, None, :]
    y_intra = jnp.einsum("bchqk,bckhp->bcqhp", att.astype(x.dtype), xc)

    # Chunk states: S_c = sum_j exp(total - cs_j) dt_j B_j x_j  -> (b,nc,h,n,p)
    w_state = jnp.exp(total[:, :, None, :] - cs) * dtc  # (b,nc,q,h)
    s_chunk = jnp.einsum("bcqhn,bcqh,bcqhp->bchnp", Bc.astype(jnp.float32), w_state,
                         xc.astype(jnp.float32))

    # Inter-chunk recurrence over nc.
    def step(carry, inp):
        s_prev = carry
        tot_c, s_c = inp
        s_next = jnp.exp(tot_c)[:, :, None, None] * s_prev + s_c
        return s_next, s_prev

    init_s = (jnp.zeros((b, h, n, p), jnp.float32) if initial_state is None
              else initial_state.astype(jnp.float32))
    final_state, s_carry = jax.lax.scan(
        step,
        init_s,
        (total.transpose(1, 0, 2), s_chunk.transpose(1, 0, 2, 3, 4)),
    )
    s_carry = s_carry.transpose(1, 0, 2, 3, 4)  # (b,nc,h,n,p): state entering chunk c

    y_inter = jnp.einsum("bcqhn,bchnp->bcqhp", (Cc.astype(jnp.float32) * jnp.exp(cs)[..., None]),
                         s_carry)
    y = (y_intra.astype(jnp.float32) + y_inter).reshape(b, l, h, p)
    return y.astype(x.dtype), final_state


def ssd_recurrent_step(state, x_t, dt_t, A, B_t, C_t):
    """One decode step.  state: (B,H,N,P); x_t: (B,H,P); dt_t: (B,H);
    B_t/C_t: (B,G,N).  Returns (y_t, new_state)."""
    h, g = x_t.shape[1], B_t.shape[1]
    rep = h // g
    Bh = jnp.repeat(B_t, rep, axis=1).astype(jnp.float32)  # (B,H,N)
    Ch = jnp.repeat(C_t, rep, axis=1).astype(jnp.float32)
    dtf = dt_t.astype(jnp.float32)
    da = jnp.exp(dtf * A[None, :])  # (B,H)
    upd = jnp.einsum("bhn,bh,bhp->bhnp", Bh, dtf, x_t.astype(jnp.float32))
    new_state = da[:, :, None, None] * state + upd
    y = jnp.einsum("bhn,bhnp->bhp", Ch, new_state)
    return y.astype(x_t.dtype), new_state


def _split_proj(cfg: Mamba2Config, zxbcdt):
    d_in, gn = cfg.d_inner, cfg.n_groups * cfg.d_state
    z = zxbcdt[..., :d_in]
    xbc = zxbcdt[..., d_in : 2 * d_in + 2 * gn]
    dt_raw = zxbcdt[..., 2 * d_in + 2 * gn :]
    return z, xbc, dt_raw


def _gated_norm(cfg: Mamba2Config, y, z, scale):
    """RMSNorm of ``y * silu(z)`` over each group's ``d_inner / n_groups``
    channels."""
    yf = (y * jax.nn.silu(z.astype(jnp.float32))).astype(jnp.float32)
    grouped = yf.reshape(yf.shape[:-1] + (cfg.n_groups, -1))
    var = jnp.mean(jnp.square(grouped), axis=-1, keepdims=True)
    yf = (grouped * (var + cfg.norm_eps) ** -0.5).reshape(yf.shape)
    return (yf * scale.astype(jnp.float32)).astype(y.dtype)


def mamba2_apply(params, cfg: Mamba2Config, x, cache=None):
    """Full-sequence forward.  x: (B, L, d_model) -> (B, L, d_model).

    With ``cache`` (:func:`init_ssm_cache`: the convolution window and
    state to continue from), returns (y, the cache after the last
    position): a prompt's prefill, the chunked scan handing back its
    final state.
    """
    b, l, _ = x.shape
    d_in, gn = cfg.d_inner, cfg.n_groups * cfg.d_state
    zxbcdt = dense(x, params["in_proj"])
    z, xbc, dt_raw = _split_proj(cfg, zxbcdt)
    if cache is None:
        xbc = causal_conv1d(xbc, params["conv_w"], params["conv_b"])
    else:
        xbc, conv_state = causal_conv1d(xbc, params["conv_w"], params["conv_b"],
                                        state=cache["conv"].astype(xbc.dtype))
    xbc = jax.nn.silu(xbc)
    xs = xbc[..., :d_in].reshape(b, l, cfg.n_heads, cfg.d_head)
    B_ = xbc[..., d_in : d_in + gn].reshape(b, l, cfg.n_groups, cfg.d_state)
    C_ = xbc[..., d_in + gn :].reshape(b, l, cfg.n_groups, cfg.d_state)
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + params["dt_bias"])
    A = -jnp.exp(params["A_log"])
    chunk = min(cfg.chunk, l)
    while l % chunk:
        chunk -= 1
    if cfg.impl == "pallas" and cache is None:
        from repro.kernels import ops as kops

        y, _ = kops.ssm_scan(xs, dt, A, B_, C_, chunk=chunk)
    else:
        y, state = ssd_chunked(xs, dt, A, B_, C_, chunk,
                               initial_state=None if cache is None else cache["state"])
    y = (y + xs * params["D"][None, None, :, None]).astype(x.dtype)
    y = y.reshape(b, l, d_in)
    y = _gated_norm(cfg, y, z, params["norm_scale"])
    out = dense(y, params["out_proj"])
    if cache is None:
        return out
    return out, {"conv": conv_state.astype(cache["conv"].dtype), "state": state}


def init_ssm_cache(cfg: Mamba2Config, batch, dtype=jnp.float32):
    conv_dim = cfg.d_inner + 2 * cfg.n_groups * cfg.d_state
    return {
        "conv": jnp.zeros((batch, cfg.conv_width - 1, conv_dim), dtype),
        "state": jnp.zeros((batch, cfg.n_heads, cfg.d_state, cfg.d_head), jnp.float32),
    }


def mamba2_decode(params, cfg: Mamba2Config, x, cache):
    """One-token decode.  x: (B, 1, d_model)."""
    b = x.shape[0]
    d_in, gn = cfg.d_inner, cfg.n_groups * cfg.d_state
    zxbcdt = dense(x, params["in_proj"])
    z, xbc, dt_raw = _split_proj(cfg, zxbcdt)
    xbc_t, conv_state = causal_conv1d(
        xbc, params["conv_w"], params["conv_b"], state=cache["conv"].astype(xbc.dtype)
    )
    xbc_t = jax.nn.silu(xbc_t)[:, 0]  # (B, conv_dim)
    x_t = xbc_t[..., :d_in].reshape(b, cfg.n_heads, cfg.d_head)
    B_t = xbc_t[..., d_in : d_in + gn].reshape(b, cfg.n_groups, cfg.d_state)
    C_t = xbc_t[..., d_in + gn :].reshape(b, cfg.n_groups, cfg.d_state)
    dt = jax.nn.softplus(dt_raw[:, 0].astype(jnp.float32) + params["dt_bias"])
    A = -jnp.exp(params["A_log"])
    y_t, state = ssd_recurrent_step(
        cache["state"], x_t, dt, A, B_t, C_t
    )
    y_t = (y_t + x_t * params["D"][None, :, None]).astype(x.dtype)
    y = y_t.reshape(b, 1, d_in)
    y = _gated_norm(cfg, y, z, params["norm_scale"])
    out = dense(y, params["out_proj"])
    return out, {"conv": conv_state.astype(cache["conv"].dtype), "state": state}
