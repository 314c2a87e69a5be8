"""Plain reference of Zamba2 hybrids (Zamba2-2.7B,
https://huggingface.co/Zyphra/Zamba2-2.7B; arXiv:2411.15242), after
``transformers``' ``modeling_zamba2.py``: token embedding; layer by
layer a pre-norm Mamba2 mixer with its residual; before the Mamba2 of
each hybrid layer (``hybrid_layer_ids``), shared block ``i %
num_mem_blocks`` of its ``i``-th invocation on the hidden state beside
the embedding output: RMSNorm over both, causal attention with scores
scaled by ``(head_dim / 2)^-0.5`` and no rotary embedding, RMSNorm, a
gated exact-GELU MLP whose gate and up products gain the invocation's
own low-rank adapter, then the invocation's own ``linear``; its output
is added to the Mamba2 layer's input before that layer's norm and not
to the residual stream.  A final RMSNorm and the head tied to the
embedding.  No cache, no batching, no kernels: one full forward over a
sequence.

Mamba2 is the plain recurrence, a scan over time: a depthwise causal
convolution with bias, then SiLU; ``dt = softplus(dt_raw + dt_bias)``,
``A = -exp(A_log)``; ``h_t = exp(dt A) h_{t-1} + dt x_t (x) B_t``,
``y_t = C_t . h_t + D x_t``; ``y * silu(z)``, an RMSNorm over each
group's ``d_inner / ngroups`` channels, then ``out_proj``.

Departures from ``modeling_zamba2.py``: its torch path clamps ``dt``
below at ``time_step_min`` (0.001), which its CUDA path does not; here
``dt`` is not clamped.  The fused ``gate_up_proj`` is read as the two
halves ``w_gate`` and ``w_up``, with the adapter's second factor
``lora_b`` holding the gate's columns first, as the fused weight does.

It reads the program's parameter tree by path (``param_shapes``): runs
of plain Mamba2 layers stack on a leading axis as ``seg_<k>``, each
hybrid layer is a segment of its own that also holds its ``adapter``
and ``linear``, and the shared blocks are ``shared_<b>``.  It never
reads the program's code.
"""
from __future__ import annotations

from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp

from lib import refmath as R


def _dims(c):
    d = c["hidden_size"]
    d_in = c["mamba_expand"] * d
    gn = c["mamba_ngroups"] * c["mamba_d_state"]
    return d, d_in, gn, d_in + 2 * gn


def segments(c) -> List[Tuple[str, int, bool]]:
    """``(name, layers, hybrid)`` of each segment, in order."""
    hybrid = set(c["hybrid_layer_ids"])
    out: List[Tuple[str, int, bool]] = []
    for i in range(c["num_hidden_layers"]):
        if i in hybrid or not out or out[-1][2]:
            out.append((f"seg_{len(out)}", 1, i in hybrid))
        else:
            out[-1] = (out[-1][0], out[-1][1] + 1, False)
    return out


def param_shapes(c) -> Dict[str, Tuple[int, ...]]:
    d, d_in, gn, conv = _dims(c)
    h, w, ff, r = c["n_mamba_heads"], c["mamba_d_conv"], c["intermediate_size"], c["adapter_rank"]
    da, heads, dh = c["attention_hidden_size"], c["num_attention_heads"], c["attention_head_dim"]
    out = {"embed": (c["vocab_size"], d), "final_norm/scale": (d,)}
    for name, n, hybrid in segments(c):
        m = f"{name}/sub_0"
        out.update({
            f"{m}/norm/scale": (n, d),
            f"{m}/inner/in_proj": (n, d, d_in + conv + h),
            f"{m}/inner/conv_w": (n, w, conv),
            f"{m}/inner/conv_b": (n, conv),
            f"{m}/inner/A_log": (n, h),
            f"{m}/inner/D": (n, h),
            f"{m}/inner/dt_bias": (n, h),
            f"{m}/inner/norm_scale": (n, d_in),
            f"{m}/inner/out_proj": (n, d_in, d),
        })
        if hybrid:
            out.update({f"{name}/adapter/lora_a": (1, d, r),
                        f"{name}/adapter/lora_b": (1, r, 2 * ff),
                        f"{name}/linear": (1, d, d)})
    for b in range(c["num_mem_blocks"]):
        a, f = f"shared_{b}/sub_0", f"shared_{b}/sub_1"
        out.update({
            f"{a}/norm/scale": (da,),
            f"{a}/inner/wq": (da, heads * dh),
            f"{a}/inner/wk": (da, c["num_key_value_heads"] * dh),
            f"{a}/inner/wv": (da, c["num_key_value_heads"] * dh),
            f"{a}/inner/wo": (heads * dh, d),
            f"{f}/norm/scale": (d,),
            f"{f}/inner/w_gate": (d, ff),
            f"{f}/inner/w_up": (d, ff),
            f"{f}/inner/w_down": (ff, d),
        })
    return out


def _mamba(p, x, c, prec):
    """One Mamba2 mixer over x (T, d), already normed."""
    d, d_in, gn, conv = _dims(c)
    h, n, g, w = c["n_mamba_heads"], c["mamba_d_state"], c["mamba_ngroups"], c["mamba_d_conv"]
    t, hp = x.shape[0], c["mamba_headdim"]
    zxbcdt = R.mm(x, p["in_proj"], prec)
    z, xbc, dt_raw = zxbcdt[:, :d_in], zxbcdt[:, d_in:d_in + conv], zxbcdt[:, d_in + conv:]
    xp = jnp.concatenate([jnp.zeros((w - 1, conv), xbc.dtype), xbc])
    xbc = sum(xp[k:k + t] * p["conv_w"][k].astype(x.dtype) for k in range(w))
    xbc = R.silu(xbc + p["conv_b"].astype(x.dtype))
    xs = xbc[:, :d_in].reshape(t, h, hp).astype(jnp.float32)
    rep = h // g
    bs = jnp.repeat(xbc[:, d_in:d_in + gn].reshape(t, g, n), rep, axis=1).astype(jnp.float32)
    cs = jnp.repeat(xbc[:, d_in + gn:].reshape(t, g, n), rep, axis=1).astype(jnp.float32)
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + p["dt_bias"].astype(jnp.float32))
    a = -jnp.exp(p["A_log"].astype(jnp.float32))

    def step(state, inp):  # state (H, N, P)
        x_t, b_t, c_t, dt_t = inp
        state = (jnp.exp(dt_t * a)[:, None, None] * state
                 + dt_t[:, None, None] * b_t[:, :, None] * x_t[:, None, :])
        return state, jnp.einsum("hn,hnp->hp", c_t, state, precision=prec)

    _, y = jax.lax.scan(step, jnp.zeros((h, n, hp), jnp.float32), (xs, bs, cs, dt))
    y = (y + p["D"].astype(jnp.float32)[:, None] * xs).reshape(t, d_in)
    y = y * jax.nn.silu(z.astype(jnp.float32))
    grouped = y.reshape(t, g, d_in // g)
    y = grouped * jax.lax.rsqrt(jnp.mean(grouped * grouped, -1, keepdims=True) + c["rms_norm_eps"])
    y = (y.reshape(t, d_in) * p["norm_scale"].astype(jnp.float32)).astype(x.dtype)
    return R.mm(y, p["out_proj"], prec)


def _attention(p, x, c, prec):
    """Causal attention of x (T, 2d), no rope, scale (head_dim / 2)^-0.5."""
    t = x.shape[0]
    heads, kh, dh = c["num_attention_heads"], c["num_key_value_heads"], c["attention_head_dim"]
    q = R.mm(x, p["wq"], prec).reshape(t, kh, heads // kh, dh)
    k = R.mm(x, p["wk"], prec).reshape(t, kh, dh)
    v = R.mm(x, p["wv"], prec).reshape(t, kh, dh)
    s = jnp.einsum("tkgd,skd->kgts", q, k, precision=prec).astype(jnp.float32)
    s = s * (dh / 2) ** -0.5
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -jnp.inf)
    pr = jax.nn.softmax(s, axis=-1).astype(v.dtype)
    o = jnp.einsum("kgts,skd->tkgd", pr, v, precision=prec).reshape(t, heads * dh)
    return R.mm(o, p["wo"], prec)


def _shared(w, b, lp, x, emb, c, prec):
    """Shared block ``b`` with one invocation's adapter and linear."""
    eps, ff = c["rms_norm_eps"], c["intermediate_size"]
    a, f = f"shared_{b}/sub_0", f"shared_{b}/sub_1"
    y = R.rmsnorm(jnp.concatenate([x, emb], -1), w[f"{a}/norm/scale"], eps)
    y = _attention({k: w[f"{a}/inner/{k}"] for k in ("wq", "wk", "wv", "wo")}, y, c, prec)
    y = R.rmsnorm(y, w[f"{f}/norm/scale"], eps)
    low = R.mm(R.mm(y, lp["lora_a"], prec), lp["lora_b"], prec)
    gate = R.mm(y, w[f"{f}/inner/w_gate"], prec) + low[:, :ff]
    up = R.mm(y, w[f"{f}/inner/w_up"], prec) + low[:, ff:]
    y = R.mm(gelu(gate) * up, w[f"{f}/inner/w_down"], prec)
    return R.mm(y, lp["linear"], prec)


def gelu(x):
    """Exact GELU, ``x * Phi(x)``."""
    xf = x.astype(jnp.float32)
    return (0.5 * xf * (1.0 + jax.scipy.special.erf(xf / 2 ** 0.5))).astype(x.dtype)


def forward(w, tokens, c, dtype, prec):
    """Logits (T, vocab) in float32 for ``tokens`` (T,), computed in
    ``dtype`` with matmul precision ``prec``; the recurrent state, the
    time steps and the norms' statistics in float32."""
    eps = c["rms_norm_eps"]
    emb = w["embed"].astype(dtype)[tokens]
    x = emb
    invocation = 0
    for name, n, hybrid in segments(c):
        layers = {k[len(name) + 1:]: w[k].astype(dtype) for k in w
                  if k.startswith(f"{name}/")}

        def layer(x, p, lift=None):
            y = x if lift is None else x + lift
            y = R.rmsnorm(y, p["sub_0/norm/scale"], eps)
            inner = {k[len("sub_0/inner/"):]: v for k, v in p.items()
                     if k.startswith("sub_0/inner/")}
            return x + _mamba(inner, y, c, prec), None

        if hybrid:
            p = {k: v[0] for k, v in layers.items()}
            lp = {"lora_a": p["adapter/lora_a"], "lora_b": p["adapter/lora_b"],
                  "linear": p["linear"]}
            lift = _shared(w, invocation % c["num_mem_blocks"], lp, x, emb, c, prec)
            x, _ = layer(x, p, lift)
            invocation += 1
        else:
            x, _ = jax.lax.scan(layer, x, layers)
    x = R.rmsnorm(x, w["final_norm/scale"], eps)
    return jnp.matmul(x, w["embed"].astype(dtype).T, precision=prec).astype(jnp.float32)
