"""Plain reference of Qwen3 dense decoders (Qwen3-1.7B,
https://huggingface.co/Qwen/Qwen3-1.7B): token embedding; per layer a
pre-norm grouped-query attention block (RMSNorm on each query and key
head, rotary embedding with halves rotated, causal softmax) and a
pre-norm SwiGLU block; a final RMSNorm and the head tied to the
embedding.  No cache, no batching, no kernels: one full forward over a
sequence.

It reads the program's parameter tree by path (``seg_0/sub_0/inner/wq``
holds every layer's query projection on its leading axis) and never the
program's code.
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from lib import refmath as R


def param_shapes(c) -> Dict[str, Tuple[int, ...]]:
    d, n, ff, v = c["hidden_size"], c["num_hidden_layers"], c["intermediate_size"], c["vocab_size"]
    h, k, dh = c["num_attention_heads"], c["num_key_value_heads"], c["head_dim"]
    a, m = "seg_0/sub_0", "seg_0/sub_1"
    return {
        "embed": (v, d),
        "final_norm/scale": (d,),
        f"{a}/norm/scale": (n, d),
        f"{a}/inner/wq": (n, d, h * dh),
        f"{a}/inner/wk": (n, d, k * dh),
        f"{a}/inner/wv": (n, d, k * dh),
        f"{a}/inner/wo": (n, h * dh, d),
        f"{a}/inner/q_norm": (n, dh),
        f"{a}/inner/k_norm": (n, dh),
        f"{m}/norm/scale": (n, d),
        f"{m}/inner/w_gate": (n, d, ff),
        f"{m}/inner/w_up": (n, d, ff),
        f"{m}/inner/w_down": (n, ff, d),
    }


def forward(w, tokens, c, dtype, prec):
    """Logits (T, vocab) in float32 for ``tokens`` (T,), computed in
    ``dtype`` with matmul precision ``prec``."""
    h, k, dh = c["num_attention_heads"], c["num_key_value_heads"], c["head_dim"]
    eps, theta = c["rms_norm_eps"], c["rope_theta"]
    t = tokens.shape[0]
    a, m = "seg_0/sub_0", "seg_0/sub_1"
    layers = {name: w[name] for name in param_shapes(c)
              if name.startswith("seg_0/")}

    def layer(x, p):
        y = R.rmsnorm(x, p[f"{a}/norm/scale"], eps)
        q = R.mm(y, p[f"{a}/inner/wq"], prec).reshape(t, h, dh)
        kk = R.mm(y, p[f"{a}/inner/wk"], prec).reshape(t, k, dh)
        vv = R.mm(y, p[f"{a}/inner/wv"], prec).reshape(t, k, dh)
        q = R.rope(R.rmsnorm(q, p[f"{a}/inner/q_norm"], eps), theta)
        kk = R.rope(R.rmsnorm(kk, p[f"{a}/inner/k_norm"], eps), theta)
        x = x + R.mm(R.causal_attention(q, kk, vv, prec), p[f"{a}/inner/wo"], prec)
        y = R.rmsnorm(x, p[f"{m}/norm/scale"], eps)
        g = R.silu(R.mm(y, p[f"{m}/inner/w_gate"], prec))
        x = x + R.mm(g * R.mm(y, p[f"{m}/inner/w_up"], prec), p[f"{m}/inner/w_down"], prec)
        return x, None

    x = w["embed"].astype(dtype)[tokens]
    x, _ = jax.lax.scan(layer, x, layers)
    x = R.rmsnorm(x, w["final_norm/scale"], eps)
    return jnp.matmul(x, w["embed"].astype(dtype).T, precision=prec).astype(jnp.float32)
