"""zamba2 as published, against the plain reference
(``bench/reference/zamba2.py``), and the reference against
``transformers``' Zamba2, on seeded random weights on the CPU.

Tolerance against the reference: max |program - reference| <= 1e-4 x
max |reference logit|, both at ``HIGHEST`` matmul precision in float32.
What is left is summation order (the chunked SSD of ``LM.apply`` against
the reference's recurrence, fused against separate matmuls), measured
here at about 5e-6 of the logit scale; a wrong norm, mask, block or
adapter moves the logits by a sizeable share of that scale.
"""
import functools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_arch
from repro.launch.serve import ServingEngine
from repro.models.lm import LM
from repro.nn.types import split

BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "bench")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from lib import refmath as R  # noqa: E402
from lib import weights as W  # noqa: E402
from lib.harness import config_dims, program_shapes  # noqa: E402
from lib.registry import load_cell  # noqa: E402

TOL = 1e-4
SEED = 2**31 + 77


@functools.lru_cache(maxsize=None)
def _smoke():
    cell = load_cell("zamba2-2.7b.chat-closed")
    dims = config_dims(cell, smoke=True)
    model = LM(get_arch("zamba2-2.7b").smoke_spec_fn())
    shapes = program_shapes(model, jnp.float32)
    assert shapes == cell.reference.param_shapes(dims)
    return cell.reference, dims, model, shapes


@functools.lru_cache(maxsize=None)
def _apply():
    return jax.jit(_smoke()[2].apply)


@functools.lru_cache(maxsize=None)
def _made(seed):
    return W.make(seed, _smoke()[3], jnp.float32)


def _weights(shapes, seed=SEED):
    """A fresh ``{path: array}`` of the smoke model's weights."""
    assert shapes == _smoke()[3]
    return dict(_made(seed))


def _reference(ref, flat, tokens, dims):
    with jax.default_matmul_precision("highest"):
        return np.asarray(ref.forward(flat, jnp.asarray(tokens), dims, jnp.float32, R.HIGHEST))


def _close(got, want):
    assert got.shape == want.shape
    assert np.abs(got - want).max() <= TOL * np.abs(want).max()


def test_spec_is_the_published_2_7b():
    spec = get_arch("zamba2-2.7b").spec()
    mamba = {layer.subs[0].cfg for layer in spec.layers}
    assert len(spec.layers) == 54 and len(mamba) == 1
    (m,) = mamba
    assert (m.d_inner, m.n_heads, m.d_head, m.d_state, m.n_groups, m.conv_width,
            m.norm_eps) == (5120, 80, 64, 64, 1, 4, 1e-5)
    assert [i for i, layer in enumerate(spec.layers) if layer.hybrid] == [
        6, 12, 18, 24, 30, 36, 42, 47, 51]
    model = LM(spec)
    assert list(model.block.values()) == ["shared_0", "shared_1"] * 4 + ["shared_0"]
    attn, mlp = (s.cfg for s in spec.shared.layer.subs)
    assert (attn.d_model, attn.n_heads, attn.n_kv_heads, attn.head_dim, attn.out_dim) == (
        5120, 32, 32, 160, 2560)
    assert attn.scale == pytest.approx(80 ** -0.5) and not attn.rope and attn.window is None
    assert (mlp.d_ff, mlp.activation, mlp.gated) == (10240, "gelu_exact", True)
    assert (spec.shared.n, spec.shared.adapter_rank) == (2, 128)
    assert (spec.norm_eps, spec.vocab, spec.tie_embeddings) == (1e-5, 32000, True)
    assert get_arch("zamba2-2.7b").spec(long_context=True).shared.layer.subs[0].cfg.window == 4096
    params, _ = split(jax.eval_shape(functools.partial(model.init, dtype=jnp.float32),
                                     jax.random.PRNGKey(0)))
    assert sum(int(x.size) for x in jax.tree_util.tree_leaves(params)) == 2_662_214_560


def test_apply_matches_the_reference():
    ref, dims, model, shapes = _smoke()
    flat = _weights(shapes)
    tokens = np.random.default_rng(3).integers(0, dims["vocab_size"], 19).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(_apply()(W.unflatten_paths(flat), jnp.asarray(tokens[None]))[0])
    _close(got, _reference(ref, flat, tokens, dims))


def test_prefill_then_decode_of_two_slots_matches_the_reference():
    """Two requests prefilled at batch 1 and merged into slots of the
    batched cache, then decoded together at different depths,
    teacher-forced: every logit against the reference's full forward."""
    ref, dims, model, shapes = _smoke()
    flat = _weights(shapes)
    params = W.unflatten_paths(flat)
    rng = np.random.default_rng(5)
    prompts, steps = (7, 12), 6
    seqs = [rng.integers(0, dims["vocab_size"], s + steps).astype(np.int32) for s in prompts]
    with jax.default_matmul_precision("highest"):
        engine = ServingEngine(model, params, max_batch=2, queue_limit=2, max_context=32)
        got = [[], []]
        for slot, (seq, s) in enumerate(zip(seqs, prompts)):
            single = model.init_cache(params, 1, 32, dtype=jnp.float32)
            logits, single = engine._prefill_jit(params, single, jnp.asarray(seq[None, :s]))
            engine._merge_slot(single, slot)
            got[slot].append(np.asarray(logits[0]))
        for i in range(steps - 1):
            tokens = jnp.asarray([[seq[s + i]] for seq, s in zip(seqs, prompts)], jnp.int32)
            pos = jnp.asarray([s + i for s in prompts], jnp.int32)
            logits, engine.cache = engine.decode(params, engine.cache, tokens, pos)
            for slot in range(2):
                got[slot].append(np.asarray(logits[slot]))
    for slot, seq in enumerate(seqs):
        _close(np.concatenate(got[slot]), _reference(ref, flat, seq[:-1], dims))


def test_prefill_continues_from_a_filled_cache():
    """A prompt prefilled in two parts, the second from the cache the
    first left (Mamba2's chunked scan from that state and convolution
    window, attention over the cached prefix), gives the logits and the
    cache of one prefill of the whole prompt."""
    _, dims, model, shapes = _smoke()
    params = W.unflatten_paths(_weights(shapes))
    tokens = jnp.asarray(np.random.default_rng(7).integers(0, dims["vocab_size"], 13)[None],
                         jnp.int32)
    with jax.default_matmul_precision("highest"):
        empty = model.init_cache(params, 1, 16, dtype=jnp.float32)
        whole, whole_cache = model.prefill(params, empty, tokens)
        first, cache = model.prefill(params, empty, tokens[:, :5])
        second, cache = model.prefill(params, cache, tokens[:, 5:], pos_offset=5)
    _close(np.asarray(jnp.concatenate([first, second], axis=1)), np.asarray(whole))
    for got, want in zip(jax.tree_util.tree_leaves(cache), jax.tree_util.tree_leaves(whole_cache)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=0,
                                   atol=TOL * max(1.0, float(np.abs(want).max())))


@pytest.mark.parametrize("invocation", [0, 1, 2, 3])
def test_an_invocation_uses_its_own_adapter_and_linear_and_its_block(invocation):
    """With every other invocation's ``linear`` zeroed, the logits move
    under a change to this invocation's adapter, its linear or its
    block (``invocation % 2``), and under a change to no other
    invocation's or block's."""
    ref, dims, model, shapes = _smoke()
    base = _weights(shapes)
    hybrid = [name for name, _, h in ref.segments(dims) if h]
    for other in hybrid:
        if other != hybrid[invocation]:
            base[f"{other}/linear"] = jnp.zeros_like(base[f"{other}/linear"])
    tokens = jnp.asarray(np.arange(19, dtype=np.int32)[None] % dims["vocab_size"])
    apply = _apply()
    with jax.default_matmul_precision("highest"):
        before = np.asarray(apply(W.unflatten_paths(base), tokens))

        def moved(path):
            changed = dict(base, **{path: base[path] * 1.5 + 0.01})
            after = np.asarray(apply(W.unflatten_paths(changed), tokens))
            return np.abs(after - before).max() > 100 * TOL * np.abs(before).max()

        for j, name in enumerate(hybrid):
            assert moved(f"{name}/adapter/lora_b") == (j == invocation)
        assert moved(f"{hybrid[invocation]}/linear")
        for b in range(2):
            assert moved(f"shared_{b}/sub_1/inner/w_up") == (b == invocation % 2)


def test_engine_counts_the_positions_of_the_invocation_caches():
    """K/V lives only in the hybrid layers' invocation caches; the
    engine's position counters count one such cache's positions a slot."""
    _, dims, model, shapes = _smoke()
    params = W.unflatten_paths(_weights(shapes))
    engine = ServingEngine(model, params, max_batch=2, queue_limit=2, max_context=24)
    kv = [k for k, v in engine.cache.items() if "shared" in v]
    assert len(kv) == 4 and all(engine.cache[k]["shared"]["k"].shape[2] == 24 for k in kv)
    assert engine.capacity_positions == 2 * 24

    class Req:
        def __init__(self, i, n):
            self.id, self.prompt_len, self.gen_len = i, n, 4

        def prompt_tokens(self, vocab):
            return np.arange(self.prompt_len, dtype=np.int32) % vocab

    engine._join(Req(0, 5))
    engine._join(Req(1, 9))
    engine._decode_step()
    engine._decode_step()
    assert engine.valid_positions == (6 + 10) + (7 + 11)


def test_reference_matches_transformers_zamba2():
    """The reference's equations are the published code's: a tiny
    ``Zamba2ForCausalLM`` with two shared blocks, weights copied by
    path, gives the same logits within 1e-4 (float32; measured about
    2e-6).  The prompt stays within one of the published 256-token
    chunks: ``transformers``' torch path (the one that runs without
    CUDA) passes the SSM state between chunks over the wrong axis."""
    pytest.importorskip("transformers")
    torch = pytest.importorskip("torch")
    from transformers import Zamba2Config, Zamba2ForCausalLM

    ref, dims, _, shapes = _smoke()
    blocks = ["hybrid" if i in dims["hybrid_layer_ids"] else "mamba"
              for i in range(dims["num_hidden_layers"])]
    cfg = Zamba2Config(
        vocab_size=dims["vocab_size"], hidden_size=dims["hidden_size"],
        num_hidden_layers=len(blocks), layers_block_type=blocks,
        mamba_d_state=dims["mamba_d_state"], mamba_d_conv=dims["mamba_d_conv"],
        mamba_expand=dims["mamba_expand"], mamba_ngroups=dims["mamba_ngroups"],
        n_mamba_heads=dims["n_mamba_heads"], intermediate_size=dims["intermediate_size"],
        hidden_act="gelu", num_attention_heads=dims["num_attention_heads"],
        num_mem_blocks=dims["num_mem_blocks"], adapter_rank=dims["adapter_rank"],
        use_mem_rope=False, rms_norm_eps=dims["rms_norm_eps"], chunk_size=256,
        tie_word_embeddings=True, time_step_min=1e-9)  # the clamp never binds
    assert (cfg.attention_head_dim, cfg.mamba_headdim) == (
        dims["attention_head_dim"], dims["mamba_headdim"])
    flat = {k: np.asarray(v) for k, v in _weights(shapes, seed=9).items()}
    rng = np.random.default_rng(0)
    for k in flat:  # norm scales away from 1, so that a misplaced one shows
        if k.endswith("scale"):
            flat[k] = (1 + 0.1 * rng.standard_normal(flat[k].shape)).astype(np.float32)
    model = Zamba2ForCausalLM(cfg).eval()
    t = lambda a: torch.tensor(np.ascontiguousarray(a))
    sd = {"model.embed_tokens.weight": t(flat["embed"]), "lm_head.weight": t(flat["embed"]),
          "model.final_layernorm.weight": t(flat["final_norm/scale"])}
    layer = invocation = 0
    for name, n, hybrid in ref.segments(dims):
        for j in range(n):
            p = lambda k: flat[f"{name}/{k}"][j]
            pre = f"model.layers.{layer}." + ("mamba_decoder." if hybrid else "")
            sd[pre + "input_layernorm.weight"] = t(p("sub_0/norm/scale"))
            for ours, theirs in (("in_proj", "in_proj.weight"), ("out_proj", "out_proj.weight")):
                sd[pre + "mamba." + theirs] = t(p(f"sub_0/inner/{ours}").T)
            sd[pre + "mamba.conv1d.weight"] = t(p("sub_0/inner/conv_w").T[:, None, :])
            for ours, theirs in (("conv_b", "conv1d.bias"), ("A_log", "A_log"), ("D", "D"),
                                 ("dt_bias", "dt_bias"), ("norm_scale", "norm.weight")):
                sd[pre + "mamba." + theirs] = t(p(f"sub_0/inner/{ours}"))
            if hybrid:
                blk = lambda k: flat[f"shared_{invocation % 2}/{k}"]
                s = f"model.layers.{layer}.shared_transformer."
                sd[f"model.layers.{layer}.linear.weight"] = t(p("linear").T)
                sd[s + "input_layernorm.weight"] = t(blk("sub_0/norm/scale"))
                for q in "qkvo":
                    sd[s + f"self_attn.{q}_proj.weight"] = t(blk(f"sub_0/inner/w{q}").T)
                sd[s + "pre_ff_layernorm.weight"] = t(blk("sub_1/norm/scale"))
                sd[s + "feed_forward.gate_up_proj.weight"] = t(np.concatenate(
                    [blk("sub_1/inner/w_gate"), blk("sub_1/inner/w_up")], 1).T)
                sd[s + "feed_forward.down_proj.weight"] = t(blk("sub_1/inner/w_down").T)
                adapter = s + f"feed_forward.gate_up_proj_adapter_list.{invocation}."
                sd[adapter + "0.weight"] = t(p("adapter/lora_a").T)
                sd[adapter + "1.weight"] = t(p("adapter/lora_b").T)
                invocation += 1
            layer += 1
    # a shared block's parameters appear under every layer that runs it;
    # what is left unset is only such another name of a set one
    missing = model.load_state_dict(sd, strict=False).missing_keys
    assert all(".shared_transformer." in k for k in missing), missing
    tokens = np.random.default_rng(1).integers(0, dims["vocab_size"], 24)
    with torch.no_grad():
        theirs = model(torch.tensor(tokens[None]), use_cache=False).logits[0].numpy()
    ours = _reference(ref, {k: jnp.asarray(v) for k, v in flat.items()}, tokens, dims)
    assert np.abs(ours - theirs).max() <= 1e-4
