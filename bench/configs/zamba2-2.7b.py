"""Least work of zamba2-2.7b's prefill and decode step, from the published
shapes in ``zamba2-2.7b.json`` and its stated dtypes (see
``bench/lib/work.py`` for what is counted).

A token multiplies by every Mamba2 layer's projections, by a shared
block, its adapter and its ``linear`` at each of the 9 hybrid
invocations, and by the tied head once; the two shared blocks' weights
are read once a step however often they run.  Beside the matmuls: the
9 invocations' attention over each query's valid context, and per token
and Mamba2 layer the depthwise convolution (2 FLOPs per tap and
channel) and the SSM (5 per state element: decay, input and add, then
the read-out's multiply and add).  Bytes: every weight once; K/V at
each slot's valid positions in each invocation's cache; each active
slot's Mamba2 state and convolution window read and written once a
decode step, written once a prefill.
"""
from __future__ import annotations

from typing import Sequence

from lib.work import Work, attention_context_flops, causal_pairs, kv_bytes_per_token

ITEMSIZE = {"float32": 4, "bfloat16": 2}


def _mamba(c):
    """(matmul weights, other weights, conv channels) of one Mamba2 layer."""
    d, h = c["hidden_size"], c["n_mamba_heads"]
    d_in = c["mamba_expand"] * d
    conv = d_in + 2 * c["mamba_ngroups"] * c["mamba_d_state"]
    matmul = d * (d_in + conv + h) + d_in * d
    other = c["mamba_d_conv"] * conv + conv + 3 * h + d_in + d  # conv, A/D/dt, norms
    return matmul, other, conv


def _block(c):
    """(matmul weights, norm weights) of one shared block."""
    d, da, ff = c["hidden_size"], c["attention_hidden_size"], c["intermediate_size"]
    q = c["num_attention_heads"] * c["attention_head_dim"]
    kv = c["num_key_value_heads"] * c["attention_head_dim"]
    return da * (q + 2 * kv) + q * d + 3 * d * ff, da + d


def _invocation(c) -> int:
    """Weights of one hybrid invocation's own: adapter and linear."""
    d, ff, r = c["hidden_size"], c["intermediate_size"], c["adapter_rank"]
    return d * r + r * 2 * ff + d * d


def matmul_params(c) -> int:
    """Weights a token multiplies by: every Mamba2 layer, a shared block
    and the invocation's own at each hybrid layer, the tied head once."""
    n_inv = len(c["hybrid_layer_ids"])
    return (c["num_hidden_layers"] * _mamba(c)[0] + n_inv * (_block(c)[0] + _invocation(c))
            + c["vocab_size"] * c["hidden_size"])


def params(c) -> int:
    """Every weight of the model once."""
    n_inv = len(c["hybrid_layer_ids"])
    m, other, _ = _mamba(c)
    return (c["num_hidden_layers"] * (m + other) + c["num_mem_blocks"] * sum(_block(c))
            + n_inv * _invocation(c) + c["vocab_size"] * c["hidden_size"] + c["hidden_size"])


def weight_bytes(c) -> int:
    return params(c) * ITEMSIZE[c["weight_dtype"]]


def _kv(c) -> int:
    return len(c["hybrid_layer_ids"]) * kv_bytes_per_token(
        c["num_key_value_heads"], c["attention_head_dim"], ITEMSIZE[c["cache_dtype"]])


def ssm_bytes(c) -> int:
    """One slot's Mamba2 state (float32) and convolution window, all layers."""
    _, _, conv = _mamba(c)
    state = c["n_mamba_heads"] * c["mamba_d_state"] * c["mamba_headdim"] * 4
    window = (c["mamba_d_conv"] - 1) * conv * ITEMSIZE[c["cache_dtype"]]
    return c["num_hidden_layers"] * (state + window)


def _per_token_flops(c) -> float:
    _, _, conv = _mamba(c)
    ssm = 5 * c["n_mamba_heads"] * c["mamba_d_state"] * c["mamba_headdim"]
    return 2.0 * matmul_params(c) + c["num_hidden_layers"] * (2 * c["mamba_d_conv"] * conv + ssm)


def _attn(c, pairs: int) -> float:
    return len(c["hybrid_layer_ids"]) * attention_context_flops(
        c["num_attention_heads"], c["attention_head_dim"], pairs)


def prefill(c, prompt_len: int) -> Work:
    """One prompt of ``prompt_len`` tokens: its K/V and the final state
    written once."""
    return Work(_per_token_flops(c) * prompt_len + _attn(c, causal_pairs(prompt_len)),
                weight_bytes(c) + _kv(c) * prompt_len + ssm_bytes(c))


def decode(c, contexts: Sequence[int]) -> Work:
    """One step over the active slots; ``contexts[i]`` is slot i's valid
    positions, its new token included."""
    n = sum(contexts)
    return Work(_per_token_flops(c) * len(contexts) + _attn(c, n),
                weight_bytes(c) + _kv(c) * n + 2 * ssm_bytes(c) * len(contexts))
