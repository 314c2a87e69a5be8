"""Least work of qwen3-1.7b's prefill and decode step, from the published
shapes in ``qwen3-1.7b.json`` and its stated dtypes (see
``bench/lib/work.py`` for what is counted)."""
from __future__ import annotations

from typing import Sequence

from lib.work import Work, attention_context_flops, causal_pairs, kv_bytes_per_token

ITEMSIZE = {"float32": 4, "bfloat16": 2}


def matmul_params(c) -> int:
    """Weights a token multiplies by: every layer's projections and
    SwiGLU, and the tied head once."""
    d, ff = c["hidden_size"], c["intermediate_size"]
    h, k, dh = c["num_attention_heads"], c["num_key_value_heads"], c["head_dim"]
    layer = d * h * dh + 2 * d * k * dh + h * dh * d + 3 * d * ff
    return c["num_hidden_layers"] * layer + c["vocab_size"] * d


def weight_bytes(c) -> int:
    d, dh = c["hidden_size"], c["head_dim"]
    norms = c["num_hidden_layers"] * (2 * d + 2 * dh) + d
    return (matmul_params(c) + norms) * ITEMSIZE[c["weight_dtype"]]


def _kv(c) -> int:
    return c["num_hidden_layers"] * kv_bytes_per_token(
        c["num_key_value_heads"], c["head_dim"], ITEMSIZE[c["cache_dtype"]])


def _attn(c, pairs: int) -> float:
    return c["num_hidden_layers"] * attention_context_flops(
        c["num_attention_heads"], c["head_dim"], pairs)


def prefill(c, prompt_len: int) -> Work:
    """One prompt of ``prompt_len`` tokens: its K/V written once."""
    return Work(2.0 * matmul_params(c) * prompt_len + _attn(c, causal_pairs(prompt_len)),
                weight_bytes(c) + _kv(c) * prompt_len)


def decode(c, contexts: Sequence[int]) -> Work:
    """One step over the active slots; ``contexts[i]`` is slot i's valid
    positions, its new token included."""
    n = sum(contexts)
    return Work(2.0 * matmul_params(c) * len(contexts) + _attn(c, n),
                weight_bytes(c) + _kv(c) * n)
