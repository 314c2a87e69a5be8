"""Dev smoke: tiny versions of each family, forward + decode parity."""
import jax
import jax.numpy as jnp

from repro.models.lm import LM
from repro.models.specs import (LayerSpec, ModelSpec, SubBlock, moe_layer,
                                transformer_layer)
from repro.nn.moe import MoEConfig
from repro.nn.ssm import Mamba2Config
from repro.nn.xlstm import MLSTMConfig, SLSTMConfig
from repro.nn.types import split, param_count

key = jax.random.PRNGKey(0)


def check(name, spec, decode=True):
    model = LM(spec)
    annotated = model.init(key, jnp.float32)
    params, axes = split(annotated)
    tokens = jax.random.randint(key, (2, 16), 0, spec.vocab)
    logits = model.apply(params, tokens)
    assert logits.shape == (2, 16, spec.vocab), logits.shape
    assert jnp.isfinite(logits).all(), f"{name}: non-finite logits"
    print(f"{name}: fwd ok, params={param_count(params):,}")
    if decode:
        cache = model.init_cache(params, 2, 32)
        lg, cache = model.decode(params, tokens[:, :1], cache, 0) if False else model.decode(params, cache, tokens[:, :1], 0)
        assert lg.shape == (2, 1, spec.vocab)
        assert jnp.isfinite(lg).all(), f"{name}: non-finite decode"
        print(f"{name}: decode ok")


d = 64
dense = ModelSpec(
    name="tiny-dense", d_model=d, vocab=128,
    layers=(transformer_layer(d, 4, 2, 128, qk_norm=True),) * 3,
)
check("dense", dense)

moe = ModelSpec(
    name="tiny-moe", d_model=d, vocab=128,
    layers=(moe_layer(d, 4, 2, 96, n_experts=4, top_k=2, dense_residual=True),) * 2,
)
check("moe", moe)

mamba = ModelSpec(
    name="tiny-mamba", d_model=d, vocab=128,
    layers=(LayerSpec(subs=(SubBlock("mamba2", Mamba2Config(d, d_state=16, d_head=16, chunk=8)),)),) * 2,
    positional="none",
)
check("mamba", mamba)

xl = ModelSpec(
    name="tiny-xlstm", d_model=d, vocab=128,
    layers=(
        LayerSpec(subs=(SubBlock("mlstm", MLSTMConfig(d, n_heads=2, chunk=8)),)),
        LayerSpec(subs=(SubBlock("slstm", SLSTMConfig(d, n_heads=2)),)),
    ),
    positional="none",
)
check("xlstm", xl)

# hybrid: Mamba2 layers, two of which run the shared blocks (zamba2)
from repro.configs.zamba2_2_7b import _spec as zamba2_spec

hybrid = zamba2_spec("tiny-hybrid", d, 4, (1, 3), n_heads=4, d_ff=128, d_state=16,
                     d_head_ssm=16, adapter_rank=8, vocab=128, chunk=8)
check("hybrid", hybrid)

# enc-dec (whisper-like)
from repro.nn.attention import AttentionConfig
from repro.nn.mlp import MLPConfig

enc_layer = LayerSpec(subs=(
    SubBlock("attention", AttentionConfig(d, 4, 4, causal=False, rope=False)),
    SubBlock("mlp", MLPConfig(d, 128, activation="gelu", gated=False, use_bias=True)),
))
dec_layer = LayerSpec(subs=(
    SubBlock("attention", AttentionConfig(d, 4, 4, causal=True, rope=False)),
    SubBlock("cross_attention", AttentionConfig(d, 4, 4, causal=False, rope=False)),
    SubBlock("mlp", MLPConfig(d, 128, activation="gelu", gated=False, use_bias=True)),
))
encdec = ModelSpec(
    name="tiny-encdec", d_model=d, vocab=128,
    layers=(dec_layer,) * 2, encoder_layers=(enc_layer,) * 2,
    norm="layernorm", positional="learned", max_position=64,
)
model = LM(encdec)
annotated = model.init(key, jnp.float32)
params, axes = split(annotated)
frames = jax.random.normal(key, (2, 12, d))
enc_out = model.encode(params, frames)
tokens = jax.random.randint(key, (2, 16), 0, 128)
logits = model.apply(params, tokens, enc_out=enc_out)
assert logits.shape == (2, 16, 128)
assert jnp.isfinite(logits).all()
cache = model.init_cache(params, 2, 32, enc_out=enc_out)
lg, cache = model.decode(params, cache, tokens[:, :1], 0)
assert lg.shape == (2, 1, 128) and jnp.isfinite(lg).all()
print("encdec: fwd+decode ok")

# vlm-style prefix embeddings
pg = ModelSpec(name="tiny-vlm", d_model=d, vocab=128,
               layers=(transformer_layer(d, 4, 1, 128),) * 2, num_prefix_tokens=4)
model = LM(pg)
params, axes = split(model.init(key, jnp.float32))
tokens = jax.random.randint(key, (2, 16), 0, 128)
pe = jax.random.normal(key, (2, 4, d))
logits = model.apply(params, tokens, prefix_embeds=pe)
assert logits.shape == (2, 16, 128) and jnp.isfinite(logits).all()
print("vlm: fwd ok")

print("ALL DEV SMOKE PASSED")
