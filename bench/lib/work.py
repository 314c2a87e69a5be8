"""The least work of a prefill or a decode step, from a configuration's
published shapes and stated dtypes (never from the implementation).

A configuration's work file (``bench/configs/<config>.py``) builds its
counts from these pieces.  What is counted:

* FLOPs: 2 per weight a token multiplies by (the tied head once), plus
  attention's score and value products over each query's valid context;
* bytes: every weight the step multiplies by, once (a tied embedding as
  the head; an untied input table only at the rows looked up), and K/V at
  each slot's valid positions.

Not counted: the cache copy that a non-donated decode makes, attention
over the whole preallocated capacity, and activations.  Those are waste
a faster program may remove; counting them would let it read over 100%.
"""
from __future__ import annotations

import dataclasses
from typing import Dict


@dataclasses.dataclass(frozen=True)
class Work:
    flops: float = 0.0
    bytes: float = 0.0

    def __add__(self, other: "Work") -> "Work":
        return Work(self.flops + other.flops, self.bytes + other.bytes)

    def least_s(self, peaks: Dict[str, float]) -> float:
        return max(self.flops / peaks["flops_bf16"],
                   self.bytes / peaks["hbm_bytes_per_s"])

    def bound(self, peaks: Dict[str, float]) -> str:
        compute = self.flops / peaks["flops_bf16"]
        return "compute" if compute >= self.bytes / peaks["hbm_bytes_per_s"] else "memory"


def causal_pairs(prompt_len: int) -> int:
    """(query, key) pairs of a causal prompt: S(S+1)/2."""
    return prompt_len * (prompt_len + 1) // 2


def attention_context_flops(n_heads: int, head_dim: int, pairs: int) -> float:
    """Scores and values over ``pairs`` (query, key) pairs: 2 products of
    ``head_dim`` multiply-adds per head each."""
    return 4.0 * n_heads * head_dim * pairs


def kv_bytes_per_token(n_kv_heads: int, head_dim: int, itemsize: int) -> int:
    return 2 * n_kv_heads * head_dim * itemsize
