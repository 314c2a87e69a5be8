"""Model specification IR.

A model is: embedding -> [LayerSpec, ...] -> final norm -> LM head.
Each LayerSpec is a tuple of residual *sub-blocks* (pre-norm residual:
``h = h + f(norm(h))``).  A standard transformer layer is
``(attention, mlp)``; a Mamba2 layer is ``(mamba2,)``; an xLSTM layer is
``(mlstm,)`` or ``(slstm,)``; a DBRX layer is ``(attention, moe)``.  A
*hybrid* layer (zamba2) first runs one of the model's weight-tied
:class:`SharedBlocks`, whose output joins its first sub-block's input.

The same IR is produced both by the hand-written architecture configs
(`repro/configs/*.py`) and by the NAS ModelBuilder when the search space
targets LM backbones — this is the "unified interface" of the paper
(§IV) instantiated for pod-scale models.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional, Tuple

from repro.nn.attention import AttentionConfig
from repro.nn.mlp import MLPConfig
from repro.nn.moe import MoEConfig
from repro.nn.ssm import Mamba2Config
from repro.nn.xlstm import MLSTMConfig, SLSTMConfig

SUBBLOCK_KINDS = (
    "attention",
    "cross_attention",
    "mlp",
    "moe",
    "mamba2",
    "mlstm",
    "slstm",
)


@dataclasses.dataclass(frozen=True)
class SubBlock:
    kind: str
    cfg: Any  # one of the nn config dataclasses (frozen => hashable)

    def __post_init__(self):
        assert self.kind in SUBBLOCK_KINDS, self.kind


@dataclasses.dataclass(frozen=True)
class LayerSpec:
    subs: Tuple[SubBlock, ...]
    # runs a shared block first (ModelSpec.shared); never stacked with
    # another layer, since each invocation picks its own block
    hybrid: bool = False


@dataclasses.dataclass(frozen=True)
class SharedBlocks:
    """zamba2's weight-tied transformer blocks (arXiv:2411.15242).

    The i-th hybrid layer runs block ``i % n`` on ``[h; e]``, the hidden
    state beside the embedding output (``2 d_model`` wide), with no
    residual inside: ``T = mlp(norm(attention(norm([h; e]))))``.  The
    MLP adds the invocation's own rank-``adapter_rank`` adapter to its
    gate and up products, and the invocation's own ``linear``
    (``d_model x d_model``) carries ``T`` into the layer, whose first
    sub-block ``f`` then gives ``h + f(norm(h + linear(T)))``.
    """

    layer: LayerSpec  # (attention from 2 d_model to d_model, gated mlp)
    n: int
    adapter_rank: int

    def __post_init__(self):
        assert [s.kind for s in self.layer.subs] == ["attention", "mlp"], self.layer


@dataclasses.dataclass(frozen=True)
class ModelSpec:
    name: str
    d_model: int
    vocab: int
    layers: Tuple[LayerSpec, ...]
    norm: str = "rmsnorm"
    norm_eps: Optional[float] = None  # None: the norm's own default
    tie_embeddings: bool = False
    embed_scale: bool = False  # gemma-style sqrt(d_model) embedding scaling
    positional: str = "rope"  # "rope" | "learned" | "none"
    max_position: int = 1 << 20  # learned-positional table size cap
    # Encoder (whisper): encoder layers run non-causally on frame embeddings;
    # decoder layers gain cross-attention to the encoder output.
    encoder_layers: Tuple[LayerSpec, ...] = ()
    cross_attention_every: int = 1  # decoder layers with cross-attn (1 = all)
    frontend: Optional[str] = None  # None | "audio_stub" | "vision_stub"
    num_prefix_tokens: int = 0  # vlm: patch-embedding prefix length
    logit_softcap: Optional[float] = None
    remat: bool = True
    # remat_policy: None = save nothing (max recompute, min memory);
    # "dots" = jax.checkpoint_policies.dots_with_no_batch_dims_saveable
    # (save matmul outputs, recompute elementwise only — trades memory for
    # a ~1.5x cut in recompute FLOPs; a §Perf lever).
    remat_policy: Optional[str] = None
    # scan_layers=True: lax.scan over stacked segment params (fast compile,
    # production).  False: the same scans unrolled — used by the dry-run
    # cost lowering because XLA's HloCostAnalysis counts while bodies once.
    scan_layers: bool = True
    shared: Optional[SharedBlocks] = None  # run by the hybrid layers
    # the default matmul precision of apply, hidden, prefill and decode
    # (``jax.default_matmul_precision``); None leaves JAX's default
    matmul_precision: Optional[str] = None

    @property
    def n_layers(self) -> int:
        return len(self.layers)

    def is_subquadratic(self) -> bool:
        """True when decode state is O(1) in context (SSM/recurrent archs,
        possibly with sliding-window attention)."""
        shared = (self.shared.layer,) if self.shared else ()
        for layer in self.layers + shared:
            for sub in layer.subs:
                if sub.kind == "attention" and sub.cfg.window is None:
                    return False
                if sub.kind == "cross_attention":
                    return False
        return True


def transformer_layer(
    d_model: int,
    n_heads: int,
    n_kv_heads: int,
    d_ff: int,
    *,
    activation: str = "silu",
    gated: bool = True,
    qk_norm: bool = False,
    attn_bias: bool = False,
    mlp_bias: bool = False,
    window: Optional[int] = None,
    rope: bool = True,
    d_head: Optional[int] = None,
    rope_theta: float = 10000.0,
) -> LayerSpec:
    """Convenience constructor for a standard decoder layer."""
    return LayerSpec(
        subs=(
            SubBlock(
                "attention",
                AttentionConfig(
                    d_model=d_model,
                    n_heads=n_heads,
                    n_kv_heads=n_kv_heads,
                    d_head=d_head,
                    use_bias=attn_bias,
                    qk_norm=qk_norm,
                    rope=rope,
                    rope_theta=rope_theta,
                    causal=True,
                    window=window,
                ),
            ),
            SubBlock(
                "mlp",
                MLPConfig(d_model, d_ff, activation=activation, gated=gated, use_bias=mlp_bias),
            ),
        )
    )


def moe_layer(
    d_model: int,
    n_heads: int,
    n_kv_heads: int,
    d_ff: int,
    n_experts: int,
    top_k: int,
    *,
    qk_norm: bool = False,
    dense_residual: bool = False,
    activation: str = "silu",
    capacity_factor: float = 1.25,
    rope_theta: float = 10000.0,
) -> LayerSpec:
    return LayerSpec(
        subs=(
            SubBlock(
                "attention",
                AttentionConfig(
                    d_model=d_model,
                    n_heads=n_heads,
                    n_kv_heads=n_kv_heads,
                    qk_norm=qk_norm,
                    rope=True,
                    rope_theta=rope_theta,
                    causal=True,
                ),
            ),
            SubBlock(
                "moe",
                MoEConfig(
                    d_model=d_model,
                    d_ff=d_ff,
                    n_experts=n_experts,
                    top_k=top_k,
                    capacity_factor=capacity_factor,
                    activation=activation,
                    dense_residual=dense_residual,
                ),
            ),
        )
    )
