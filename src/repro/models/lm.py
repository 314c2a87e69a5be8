"""Unified LM executor for all assigned architectures.

Consecutive identical layers are grouped into *segments*; each segment's
parameters are stacked on a leading ``layers`` axis and executed with
``jax.lax.scan`` (bounded compile time for 96-layer models, and the scan
body is the natural remat unit).  Weight-shared layers (zamba2's shared
attention block) hold one parameter set but per-invocation KV caches.

Decode runs against preallocated caches (attention KV / SSM state /
mLSTM matrix state), one token per step, positions passed explicitly.
Encoder-decoder (whisper) adds a non-causal encoder stack and
cross-attention caches precomputed from the encoder output.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.distributed.api import constrain
from repro.models.specs import LayerSpec, ModelSpec, SubBlock
from repro.nn import attention as attn
from repro.nn import initializers as init
from repro.nn import linear
from repro.nn import moe as moe_mod
from repro.nn import mlp as mlp_mod
from repro.nn import ssm as ssm_mod
from repro.nn import xlstm as xlstm_mod
from repro.nn.norms import NORM_APPLY, NORM_INIT
from repro.nn.types import P


@dataclasses.dataclass(frozen=True)
class Segment:
    kind: str  # "stack" | "shared"
    spec: LayerSpec
    count: int
    name: str


def build_segments(layers: Tuple[LayerSpec, ...], prefix: str = "seg") -> Tuple[Segment, ...]:
    segments = []
    i = 0
    while i < len(layers):
        spec = layers[i]
        if spec.shared:
            segments.append(Segment("shared", spec, 1, f"{prefix}_{len(segments)}"))
            i += 1
            continue
        j = i
        while j < len(layers) and layers[j] == spec and not layers[j].shared:
            j += 1
        segments.append(Segment("stack", spec, j - i, f"{prefix}_{len(segments)}"))
        i = j
    return tuple(segments)


# ---------------------------------------------------------------------------
# sub-block dispatch
# ---------------------------------------------------------------------------

def _sub_init(sub: SubBlock, key, dtype):
    if sub.kind in ("attention", "cross_attention"):
        return attn.attention_init(sub.cfg, key, dtype)
    if sub.kind == "mlp":
        return mlp_mod.mlp_init(sub.cfg, key, dtype)
    if sub.kind == "moe":
        return moe_mod.moe_init(sub.cfg, key, dtype)
    if sub.kind == "mamba2":
        return ssm_mod.mamba2_init(sub.cfg, key, dtype)
    if sub.kind == "mlstm":
        return xlstm_mod.mlstm_init(sub.cfg, key, dtype)
    if sub.kind == "slstm":
        return xlstm_mod.slstm_init(sub.cfg, key, dtype)
    raise ValueError(sub.kind)


def _sub_apply(sub: SubBlock, params, x, *, positions, enc_out):
    if sub.kind == "attention":
        return attn.attention_apply(params, sub.cfg, x, positions=positions)
    if sub.kind == "cross_attention":
        return attn.attention_apply(params, sub.cfg, x, kv_x=enc_out)
    if sub.kind == "mlp":
        return mlp_mod.mlp_apply(params, sub.cfg, x)
    if sub.kind == "moe":
        return moe_mod.moe_apply(params, sub.cfg, x)
    if sub.kind == "mamba2":
        return ssm_mod.mamba2_apply(params, sub.cfg, x)
    if sub.kind == "mlstm":
        return xlstm_mod.mlstm_block_apply(params, sub.cfg, x)
    if sub.kind == "slstm":
        return xlstm_mod.slstm_block_apply(params, sub.cfg, x)
    raise ValueError(sub.kind)


def _sub_cache_init(sub: SubBlock, batch, max_seq, enc_len, dtype):
    if sub.kind == "attention":
        return attn.init_kv_cache(sub.cfg, batch, max_seq, dtype)
    if sub.kind == "cross_attention":
        return attn.init_kv_cache(sub.cfg, batch, enc_len, dtype)
    if sub.kind == "mamba2":
        return ssm_mod.init_ssm_cache(sub.cfg, batch)
    if sub.kind == "mlstm":
        return xlstm_mod.init_mlstm_cache(sub.cfg, batch)
    if sub.kind == "slstm":
        return xlstm_mod.init_slstm_cache(sub.cfg, batch)
    return {}


def _sub_prefill(sub: SubBlock, params, x, cache, pos_offset):
    """Full-sequence forward that also fills the decode cache.

    Attention runs through the same full-sequence kernel dispatch as
    :func:`attention_apply` and writes the whole prompt's K/V in one
    shot.  Recurrent kinds (mamba2/mlstm/slstm) ingest the prompt with a
    ``lax.scan`` of their decode step — one compiled program, batched
    over the prompt, and bitwise identical to the token-by-token loop it
    replaces.  Returns (y (B,S,d), new_cache).
    """
    if sub.kind == "attention":
        return attn.attention_prefill(params, sub.cfg, x, cache, pos_offset)
    if sub.kind == "cross_attention":
        return attn.cross_attention_cached(params, sub.cfg, x, cache), cache
    if sub.kind == "mlp":
        return mlp_mod.mlp_apply(params, sub.cfg, x), cache
    if sub.kind == "moe":
        return moe_mod.moe_apply(params, sub.cfg, x), cache

    def body(carry, x_t):
        y_t, new_carry = _sub_decode(sub, params, x_t[:, None], carry, 0)
        return new_carry, y_t[:, 0]

    new_cache, ys = jax.lax.scan(body, cache, x.transpose(1, 0, 2))
    return ys.transpose(1, 0, 2), new_cache


def _sub_decode(sub: SubBlock, params, x, cache, pos):
    """Returns (y, new_cache)."""
    if sub.kind == "attention":
        return attn.attention_decode(params, sub.cfg, x, cache, pos)
    if sub.kind == "cross_attention":
        # cross KV is precomputed and static during decode
        q_only = attn.cross_attention_cached(params, sub.cfg, x, cache)
        return q_only, cache
    if sub.kind == "mamba2":
        return ssm_mod.mamba2_decode(params, sub.cfg, x, cache)
    if sub.kind == "mlstm":
        return xlstm_mod.mlstm_block_decode(params, sub.cfg, x, cache)
    if sub.kind == "slstm":
        return xlstm_mod.slstm_block_apply(params, sub.cfg, x, cache=cache)
    if sub.kind == "mlp":
        return mlp_mod.mlp_apply(params, sub.cfg, x), cache
    if sub.kind == "moe":
        return moe_mod.moe_apply(params, sub.cfg, x), cache
    raise ValueError(sub.kind)


# the projection weights that decode may read a layer at a time
# (``nn/linear.py``), by sub-block kind; each module owns its list
_STREAMED = {"attention": attn.PROJECTIONS, "mlp": mlp_mod.PROJECTIONS}


def _split_streamed(layer: LayerSpec, stacked):
    """Take the weights :func:`linear.streams` accepts out of a stack
    segment's params: returns (the rest, {(sub key, name): stack})."""
    rest, streamed = {}, {}
    for i, sub in enumerate(layer.subs):
        key = f"sub_{i}"
        inner = dict(stacked[key]["inner"])
        for name in _STREAMED.get(sub.kind, ()):
            if linear.streams(inner.get(name)):
                streamed[(key, name)] = inner.pop(name)
        rest[key] = {**stacked[key], "inner": inner}
    return rest, streamed


def _with_layer(params, streamed, layer):
    """One layer's params with each streamed stack as a LayerWeight."""
    params = {key: {**sp, "inner": dict(sp["inner"])} for key, sp in params.items()}
    for (key, name), stack in streamed.items():
        params[key]["inner"][name] = linear.LayerWeight(stack, layer)
    return params


# ---------------------------------------------------------------------------
# layer = sequence of pre-norm residual sub-blocks
# ---------------------------------------------------------------------------

class LM:
    def __init__(self, spec: ModelSpec):
        self.spec = spec
        self.segments = build_segments(spec.layers)
        self.enc_segments = build_segments(spec.encoder_layers, prefix="enc")

    # -- init ---------------------------------------------------------------

    def _layer_init(self, layer: LayerSpec, key, dtype):
        params = {}
        keys = jax.random.split(key, len(layer.subs))
        for i, (sub, k) in enumerate(zip(layer.subs, keys)):
            params[f"sub_{i}"] = {
                "norm": NORM_INIT[self.spec.norm](self.spec.d_model, dtype),
                "inner": _sub_init(sub, k, dtype),
            }
        return params

    def init(self, key, dtype=jnp.float32):
        spec = self.spec
        keys = jax.random.split(key, 8 + len(self.segments) + len(self.enc_segments))
        params: Dict[str, Any] = {}
        params["embed"] = P(
            init.normal(keys[0], (spec.vocab, spec.d_model), dtype, stddev=0.02),
            ("vocab", "embed"),
        )
        if spec.positional == "learned":
            params["pos_embed"] = P(
                init.normal(keys[1], (spec.max_position, spec.d_model), dtype, stddev=0.02),
                (None, "embed"),
            )
        if not spec.tie_embeddings:
            params["head"] = P(
                init.normal(keys[2], (spec.d_model, spec.vocab), dtype, stddev=0.02),
                ("embed", "vocab"),
            )
        params["final_norm"] = NORM_INIT[spec.norm](spec.d_model, dtype)
        kidx = 3
        shared_done = False
        for seg, k in zip(self.segments, keys[kidx : kidx + len(self.segments)]):
            if seg.kind == "shared":
                if not shared_done:
                    params["shared"] = self._layer_init(seg.spec, k, dtype)
                    shared_done = True
                continue
            layer_keys = jax.random.split(k, seg.count)
            params[seg.name] = jax.vmap(
                functools.partial(self._layer_init, seg.spec, dtype=dtype)
            )(layer_keys)
        kidx += len(self.segments)
        if self.enc_segments:
            params["enc_final_norm"] = NORM_INIT[spec.norm](spec.d_model, dtype)
            for seg, k in zip(self.enc_segments, keys[kidx : kidx + len(self.enc_segments)]):
                layer_keys = jax.random.split(k, seg.count)
                params[seg.name] = jax.vmap(
                    functools.partial(self._layer_init, seg.spec, dtype=dtype)
                )(layer_keys)
        return params

    # -- forward ------------------------------------------------------------

    def _layer_apply(self, layer: LayerSpec, params, h, *, positions, enc_out):
        for i, sub in enumerate(layer.subs):
            sp = params[f"sub_{i}"]
            with jax.named_scope(sub.kind):
                x = NORM_APPLY[self.spec.norm](sp["norm"], h)
                y = _sub_apply(sub, sp["inner"], x, positions=positions, enc_out=enc_out)
                h = h + y
        return h

    def _run_segments(self, segments, params, h, *, positions, enc_out):
        for seg in segments:
            if seg.kind == "shared":
                h = self._layer_apply(seg.spec, params["shared"], h, positions=positions, enc_out=enc_out)
                h = constrain(h, ("batch", None, None))
                continue

            def body(carry, layer_params, _seg=seg):
                out = self._layer_apply(
                    _seg.spec, layer_params, carry, positions=positions, enc_out=enc_out
                )
                return out, None

            if self.spec.remat:
                policy = None
                if self.spec.remat_policy == "dots":
                    policy = jax.checkpoint_policies.dots_with_no_batch_dims_saveable
                body = jax.checkpoint(body, prevent_cse=False, policy=policy)
            if seg.count == 1:
                h, _ = body(h, jax.tree_util.tree_map(lambda x: x[0], params[seg.name]))
            elif not self.spec.scan_layers:
                for i in range(seg.count):
                    h, _ = body(h, jax.tree_util.tree_map(lambda x, _i=i: x[_i], params[seg.name]))
            else:
                h, _ = jax.lax.scan(body, h, params[seg.name])
            h = constrain(h, ("batch", None, None))
        return h

    def _embed(self, params, tokens, prefix_embeds):
        with jax.named_scope("embed"):
            h = jnp.take(params["embed"], tokens, axis=0)
            if self.spec.embed_scale:
                h = h * (self.spec.d_model ** 0.5)
            if prefix_embeds is not None:
                npfx = prefix_embeds.shape[1]
                h = jnp.concatenate([prefix_embeds.astype(h.dtype), h[:, npfx:]], axis=1)
        return h

    def _head(self, params, h):
        with jax.named_scope("head"):
            h = NORM_APPLY[self.spec.norm](params["final_norm"], h)
            if self.spec.tie_embeddings:
                logits = jnp.einsum("bsd,vd->bsv", h, params["embed"])
            else:
                logits = jnp.einsum("bsd,dv->bsv", h, params["head"])
            if self.spec.logit_softcap:
                c = self.spec.logit_softcap
                logits = jnp.tanh(logits / c) * c
        return logits

    def encode(self, params, frames):
        """Encoder stack on precomputed frame embeddings (stub frontend)."""
        h = frames
        if self.spec.positional == "learned":
            h = h + params["pos_embed"][: h.shape[1]][None].astype(h.dtype)
        positions = jnp.arange(h.shape[1])[None]
        h = self._run_segments(self.enc_segments, params, h, positions=positions, enc_out=None)
        return NORM_APPLY[self.spec.norm](params["enc_final_norm"], h)

    def hidden(self, params, tokens, *, prefix_embeds=None, enc_out=None, positions=None):
        """Full-sequence forward -> final normed hidden states (B, S, d).

        Used with :func:`repro.train.loss.chunked_cross_entropy` so the
        (B, S, vocab) logits never materialize at once.
        """
        h = self._embed(params, tokens, prefix_embeds)
        if positions is None:
            positions = jnp.arange(tokens.shape[1])[None]
        if self.spec.positional == "learned":
            h = h + params["pos_embed"][: h.shape[1]][None].astype(h.dtype)
        h = constrain(h, ("batch", None, None))
        h = self._run_segments(self.segments, params, h, positions=positions, enc_out=enc_out)
        return NORM_APPLY[self.spec.norm](params["final_norm"], h)

    def head_weight(self, params):
        """(weight, transposed): logits = h @ w or einsum('bsd,vd', h, w)."""
        if self.spec.tie_embeddings:
            return params["embed"], True
        return params["head"], False

    def apply(self, params, tokens, *, prefix_embeds=None, enc_out=None, positions=None):
        """Full-sequence forward -> logits (B, S, vocab)."""
        h = self._embed(params, tokens, prefix_embeds)
        if positions is None:
            positions = jnp.arange(tokens.shape[1])[None]
        if self.spec.positional == "learned":
            h = h + params["pos_embed"][: h.shape[1]][None].astype(h.dtype)
        h = constrain(h, ("batch", None, None))
        h = self._run_segments(self.segments, params, h, positions=positions, enc_out=enc_out)
        return self._head(params, h)

    # -- decode -------------------------------------------------------------

    def _layer_cache(self, layer: LayerSpec, params_layer, batch, max_seq, enc_len, enc_out, dtype):
        cache = {}
        for i, sub in enumerate(layer.subs):
            c = _sub_cache_init(sub, batch, max_seq, enc_len, dtype)
            if sub.kind == "cross_attention" and enc_out is not None:
                c = attn.precompute_cross_kv(params_layer[f"sub_{i}"]["inner"], sub.cfg, enc_out, dtype)
            cache[f"sub_{i}"] = c
        return cache

    def init_cache(self, params, batch, max_seq, *, enc_out=None, dtype=jnp.bfloat16):
        """Build the full decode cache pytree (segment-stacked)."""
        enc_len = enc_out.shape[1] if enc_out is not None else 0
        cache: Dict[str, Any] = {}
        shared_idx = 0
        for seg in self.segments:
            if seg.kind == "shared":
                cache[f"shared_{shared_idx}"] = self._layer_cache(
                    seg.spec, params["shared"], batch, max_seq, enc_len, enc_out, dtype
                )
                shared_idx += 1
                continue
            one = lambda i: self._layer_cache(
                seg.spec,
                jax.tree_util.tree_map(lambda x: x[i], params[seg.name]),
                batch, max_seq, enc_len, enc_out, dtype,
            )
            if any(sub.kind == "cross_attention" for sub in seg.spec.subs):
                layer_caches = [one(i) for i in range(seg.count)]
                cache[seg.name] = jax.tree_util.tree_map(
                    lambda *xs: jnp.stack(xs), *layer_caches
                )
            else:
                c0 = one(0)
                cache[seg.name] = jax.tree_util.tree_map(
                    lambda x: jnp.tile(x[None], (seg.count,) + (1,) * x.ndim), c0
                )
        return cache

    # -- cache sharding metadata ---------------------------------------------

    _CACHE_AXES = {
        "attention": {"k": ("batch", "kv_seq", "kv_heads", None), "v": ("batch", "kv_seq", "kv_heads", None)},
        "cross_attention": {"k": ("batch", "kv_seq", "kv_heads", None), "v": ("batch", "kv_seq", "kv_heads", None)},
        "mamba2": {"conv": ("batch", None, "mlp"), "state": ("batch", "heads", None, None)},
        "mlstm": {"conv": ("batch", None, "mlp"), "c": ("batch", "heads", "mlp", None), "n": ("batch", "heads", "mlp"), "m": ("batch", "heads")},
        "slstm": {"conv": ("batch", None, None), "c": ("batch", "heads", "mlp"), "n": ("batch", "heads", "mlp"), "m": ("batch", "heads", "mlp"), "h": ("batch", "heads", "mlp")},
        "mlp": {},
        "moe": {},
    }

    def cache_axes(self):
        """Logical-axis tree matching :meth:`init_cache`'s structure.

        Stacked (per-segment) leaves gain a leading layers dim; the
        sharding resolver pads missing leading axes with None, so the
        same tuples serve both stacked and shared entries.
        """
        axes: Dict[str, Any] = {}
        shared_idx = 0
        for seg in self.segments:
            entry = {
                f"sub_{i}": dict(self._CACHE_AXES[sub.kind])
                for i, sub in enumerate(seg.spec.subs)
            }
            if seg.kind == "shared":
                axes[f"shared_{shared_idx}"] = entry
                shared_idx += 1
            else:
                axes[seg.name] = entry
        return axes

    def _layer_decode(self, layer: LayerSpec, params, cache, h, pos):
        new_cache = {}
        for i, sub in enumerate(layer.subs):
            sp = params[f"sub_{i}"]
            with jax.named_scope(sub.kind):
                x = NORM_APPLY[self.spec.norm](sp["norm"], h)
                y, new_cache[f"sub_{i}"] = _sub_decode(sub, sp["inner"], x, cache[f"sub_{i}"], pos)
                h = h + y
        return h, new_cache

    def _layer_prefill(self, layer: LayerSpec, params, cache, h, pos_offset):
        new_cache = {}
        for i, sub in enumerate(layer.subs):
            sp = params[f"sub_{i}"]
            with jax.named_scope(sub.kind):
                x = NORM_APPLY[self.spec.norm](sp["norm"], h)
                y, new_cache[f"sub_{i}"] = _sub_prefill(
                    sub, sp["inner"], x, cache[f"sub_{i}"], pos_offset)
                h = h + y
        return h, new_cache

    def prefill(self, params, cache, tokens, pos_offset=0):
        """Batched prefill: the whole prompt in one full-sequence forward
        that also fills the decode caches.  tokens: (B, S) int32.

        Returns (logits (B, S, vocab), new_cache); decoding continues
        from ``pos = pos_offset + S`` with :meth:`decode`.  Replaces the
        token-by-token ``decode`` loop over the prompt (quadratic in
        prompt length, and meaningless to measure prefill latency on).
        """
        h = self._embed(params, tokens, None)
        s = tokens.shape[1]
        if self.spec.positional == "learned":
            pe = jax.lax.dynamic_slice_in_dim(
                params["pos_embed"], pos_offset, s, axis=0)
            h = h + pe[None].astype(h.dtype)
        new_cache: Dict[str, Any] = {}
        shared_idx = 0
        for seg in self.segments:
            if seg.kind == "shared":
                key = f"shared_{shared_idx}"
                h, new_cache[key] = self._layer_prefill(
                    seg.spec, params["shared"], cache[key], h, pos_offset)
                shared_idx += 1
                continue

            def body(carry, inp, _seg=seg):
                lp, lc = inp
                out, nc = self._layer_prefill(_seg.spec, lp, lc, carry, pos_offset)
                return out, nc

            if seg.count == 1:
                take0 = lambda t: jax.tree_util.tree_map(lambda x: x[0], t)
                h, nc = body(h, (take0(params[seg.name]), take0(cache[seg.name])))
                new_cache[seg.name] = jax.tree_util.tree_map(lambda x: x[None], nc)
            elif not self.spec.scan_layers:
                takei = lambda t, i: jax.tree_util.tree_map(lambda x: x[i], t)
                ncs = []
                for i in range(seg.count):
                    h, nc = body(h, (takei(params[seg.name], i), takei(cache[seg.name], i)))
                    ncs.append(nc)
                new_cache[seg.name] = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *ncs)
            else:
                h, new_cache[seg.name] = jax.lax.scan(
                    body, h, (params[seg.name], cache[seg.name])
                )
            h = constrain(h, ("batch", None, None))
        return self._head(params, h), new_cache

    def decode(self, params, cache, tokens, pos):
        """One-step decode.  tokens: (B, 1) int32; pos: scalar int32 or
        an int32 vector (B,) of per-sequence positions (continuous
        batching: each serving slot decodes at its own depth).

        Returns (logits (B, 1, vocab), new_cache).
        """
        h = self._embed(params, tokens, None)
        pos = jnp.asarray(pos, jnp.int32)
        if self.spec.positional == "learned":
            if pos.ndim == 1:
                pe = jnp.take(params["pos_embed"], pos, axis=0)[:, None]
            else:
                pe = jax.lax.dynamic_slice_in_dim(params["pos_embed"], pos, 1, axis=0)[None]
            h = h + pe.astype(h.dtype)
        new_cache: Dict[str, Any] = {}
        shared_idx = 0
        for seg in self.segments:
            if seg.kind == "shared":
                key = f"shared_{shared_idx}"
                h, new_cache[key] = self._layer_decode(seg.spec, params["shared"], cache[key], h, pos)
                shared_idx += 1
                continue

            def body(carry, inp, _seg=seg):
                lp, lc = inp
                out, nc = self._layer_decode(_seg.spec, lp, lc, carry, pos)
                return out, nc

            if seg.count == 1:
                take0 = lambda t: jax.tree_util.tree_map(lambda x: x[0], t)
                h, nc = body(h, (take0(params[seg.name]), take0(cache[seg.name])))
                new_cache[seg.name] = jax.tree_util.tree_map(lambda x: x[None], nc)
            elif not self.spec.scan_layers:
                takei = lambda t, i: jax.tree_util.tree_map(lambda x: x[i], t)
                ncs = []
                for i in range(seg.count):
                    h, nc = body(h, (takei(params[seg.name], i), takei(cache[seg.name], i)))
                    ncs.append(nc)
                new_cache[seg.name] = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *ncs)
            else:
                # streamed weights are closed over, not sliced as xs
                rest, streamed = _split_streamed(seg.spec, params[seg.name])

                def layer_body(carry, inp, _body=body, _streamed=streamed):
                    layer, lp, lc = inp
                    return _body(carry, (_with_layer(lp, _streamed, layer), lc))

                h, new_cache[seg.name] = jax.lax.scan(
                    layer_body, h,
                    (jnp.arange(seg.count, dtype=jnp.int32), rest, cache[seg.name]))
            h = constrain(h, ("batch", None, None))
        return self._head(params, h), new_cache
