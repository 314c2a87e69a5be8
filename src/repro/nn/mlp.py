"""Feed-forward blocks: SwiGLU / GELU / squared-ReLU / ReLU variants,
and a low-rank adapter on a gated block's gate and up products."""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from repro.nn import initializers as init
from repro.nn.linear import dense
from repro.nn.types import P


def squared_relu(x):
    r = jax.nn.relu(x)
    return r * r


ACTIVATIONS = {
    "silu": jax.nn.silu,
    "gelu": jax.nn.gelu,  # tanh approximation
    "gelu_exact": functools.partial(jax.nn.gelu, approximate=False),  # erf
    "relu": jax.nn.relu,
    "squared_relu": squared_relu,
    "identity": lambda x: x,
}


@dataclasses.dataclass(frozen=True)
class MLPConfig:
    d_model: int
    d_ff: int
    activation: str = "silu"
    gated: bool = True  # SwiGLU-style gate when True
    use_bias: bool = False



# the weights multiplied by ``nn.linear.dense``, which the decode of a
# layer scan may hand in a layer at a time (``nn/linear.py``)
PROJECTIONS = ("w_gate", "w_up", "w_down")


def mlp_init(cfg: MLPConfig, key, dtype=jnp.float32):
    kg, ku, kd = jax.random.split(key, 3)
    params = {
        "w_up": P(init.scaled_normal(ku, (cfg.d_model, cfg.d_ff), dtype), ("embed", "mlp")),
        "w_down": P(init.scaled_normal(kd, (cfg.d_ff, cfg.d_model), dtype, fan_in=cfg.d_ff), ("mlp", "embed")),
    }
    if cfg.gated:
        params["w_gate"] = P(init.scaled_normal(kg, (cfg.d_model, cfg.d_ff), dtype), ("embed", "mlp"))
    if cfg.use_bias:
        params["b_up"] = P(jnp.zeros((cfg.d_ff,), dtype), ("mlp",))
        params["b_down"] = P(jnp.zeros((cfg.d_model,), dtype), ("embed",))
    return params


def adapter_init(cfg: MLPConfig, rank: int, key, dtype=jnp.float32):
    """A rank-``rank`` adapter of a gated block: ``x @ lora_a @ lora_b``
    adds to the gate (first ``d_ff`` columns) and up products."""
    ka, kb = jax.random.split(key)
    return {
        "lora_a": P(init.scaled_normal(ka, (cfg.d_model, rank), dtype), ("embed", None)),
        "lora_b": P(init.scaled_normal(kb, (rank, 2 * cfg.d_ff), dtype, fan_in=rank), (None, "mlp")),
    }


def mlp_apply(params, cfg: MLPConfig, x, adapter=None):
    act = ACTIVATIONS[cfg.activation]
    up = dense(x, params["w_up"])
    if cfg.use_bias:
        up = up + params["b_up"]
    if cfg.gated:
        gate = dense(x, params["w_gate"])
        if adapter is not None:
            low = dense(dense(x, adapter["lora_a"]), adapter["lora_b"])
            gate, up = gate + low[..., : cfg.d_ff], up + low[..., cfg.d_ff :]
        h = act(gate) * up
    else:
        h = act(up)
    out = dense(h, params["w_down"])
    if cfg.use_bias:
        out = out + params["b_down"]
    return out
