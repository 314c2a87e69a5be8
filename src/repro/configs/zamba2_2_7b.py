"""zamba2-2.7b [hybrid]: Zyphra/Zamba2-2.7B as published
(https://huggingface.co/Zyphra/Zamba2-2.7B/blob/main/config.json;
arXiv:2411.15242).

54 Mamba2 layers (d_model 2560, d_inner 5120 in 80 heads of 64, state
64, one group, conv 4); layers 6, 12, 18, 24, 30, 36, 42, 47 and 51 are
hybrid: before the Mamba2 layer they run one of two weight-tied
transformer blocks, alternating A B A B ..., on the hidden state beside
the embedding output (5120 wide): attention in 32 heads of 160 (no rope,
scores scaled by (160/2)^-0.5, 5120 -> 2560) and a gated exact-GELU MLP
of 10240 with the invocation's own rank-128 adapter on its gate and up
products, then the invocation's own 2560x2560 ``linear``
(``repro.models.specs.SharedBlocks``).  RMSNorm eps 1e-5, also in
Mamba2's gated norm; vocab 32000, tied head.

Three values follow the Zamba2 family rather than the defaults of
``transformers``' ``Zamba2Config()``: ``mamba_headdim`` 64 with
``n_mamba_heads`` 80 (the default gives 8 heads of 640) and
``num_mem_blocks`` 2 (default 1), as the family's published configs set
them; ``use_mem_rope`` false is that default.

Its matmuls run at the ``high`` precision (bf16_3x: three bf16 passes of
each f32 product).  At one pass, the default, the served tokens of this
54-layer hybrid on random weights lie as far from the float32 reference
as those of a model computed wholly in bf16.

Long-context note (DESIGN.md §shape-cell skips): at long_500k the shared
attention runs with a 4096-token sliding window; the Mamba2 state is the
O(1) context carrier.
"""
from repro.configs.base import ArchConfig
from repro.models.specs import LayerSpec, ModelSpec, SharedBlocks, SubBlock
from repro.nn.attention import AttentionConfig
from repro.nn.mlp import MLPConfig
from repro.nn.ssm import Mamba2Config

EPS = 1e-5
HYBRID_LAYER_IDS = (6, 12, 18, 24, 30, 36, 42, 47, 51)


def _spec(name, d, n_layers, hybrid_ids, *, n_heads, d_ff, d_state, d_head_ssm,
          adapter_rank, vocab, window=None, chunk=128):
    mamba = SubBlock("mamba2", Mamba2Config(
        d, d_state=d_state, d_head=d_head_ssm, expand=2, n_groups=1, chunk=chunk,
        norm_eps=EPS))
    layers = tuple(LayerSpec(subs=(mamba,), hybrid=i in hybrid_ids) for i in range(n_layers))
    d_attn = 2 * d  # the block reads [hidden; embedding]
    d_head = d_attn // n_heads
    block = LayerSpec(subs=(
        SubBlock("attention", AttentionConfig(
            d_attn, n_heads, n_heads, d_head=d_head, rope=False, window=window,
            softmax_scale=(d_head / 2) ** -0.5, d_out=d)),
        SubBlock("mlp", MLPConfig(d, d_ff, activation="gelu_exact", gated=True)),
    ))
    return ModelSpec(
        name=name, d_model=d, vocab=vocab, layers=layers, norm="rmsnorm", norm_eps=EPS,
        positional="none", tie_embeddings=True, matmul_precision="high",
        shared=SharedBlocks(block, n=2, adapter_rank=adapter_rank))


def spec_fn(long_context: bool = False) -> ModelSpec:
    return _spec("zamba2-2.7b", 2560, 54, HYBRID_LAYER_IDS, n_heads=32, d_ff=10240,
                 d_state=64, d_head_ssm=64, adapter_rank=128, vocab=32000,
                 window=4096 if long_context else None)


def smoke_spec_fn() -> ModelSpec:
    """Both shared blocks, each run twice (four invocations, adapters
    and linears), between runs of plain Mamba2 layers."""
    return _spec("zamba2-smoke", 64, 9, (2, 4, 5, 7), n_heads=4, d_ff=128, d_state=16,
                 d_head_ssm=16, adapter_rank=8, vocab=512, chunk=8)


ARCH = ArchConfig(
    name="zamba2-2.7b", family="hybrid",
    spec_fn=spec_fn, smoke_spec_fn=smoke_spec_fn,
    supports_long_context=True,
    source="https://huggingface.co/Zyphra/Zamba2-2.7B/blob/main/config.json",
)
