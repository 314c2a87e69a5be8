"""Unified LM executor for all assigned architectures.

Consecutive identical layers are grouped into *segments*; each segment's
parameters are stacked on a leading ``layers`` axis and executed with
``jax.lax.scan`` (bounded compile time for 96-layer models, and the scan
body is the natural remat unit).  zamba2's hybrid layers run one of the
model's weight-tied shared blocks (``shared_<b>``) on the hidden state
beside the embedding output; each invocation keeps its own adapter,
``linear`` and K/V cache under its own segment.

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
    spec: LayerSpec
    count: int
    name: str


def build_segments(layers: Tuple[LayerSpec, ...], prefix: str = "seg") -> Tuple[Segment, ...]:
    """Runs of identical layers, each stacked on a leading axis.  A hybrid
    layer is a segment of its own: each invocation picks its block."""
    segments = []
    i = 0
    while i < len(layers):
        spec = layers[i]
        j = i + 1
        while not spec.hybrid and j < len(layers) and layers[j] == spec:
            j += 1
        segments.append(Segment(spec, j - i, f"{prefix}_{len(segments)}"))
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


def _sub_step(sub: SubBlock, params, x, cache):
    """A sub-block other than self-attention on the layer's own cache:
    one token, or for cross-attention, mlp and moe a whole sequence.
    Returns (y, new cache); a cache it returns as it came
    (cross-attention's static K/V; mlp and moe hold none) is unchanged."""
    if sub.kind == "cross_attention":
        return attn.cross_attention_cached(params, sub.cfg, x, cache), cache
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


def _sub_prefill(sub: SubBlock, params, x, cache: attn.LayerCache, pos_offset):
    """Full-sequence forward that also fills the layer's cache.

    Attention runs through the same full-sequence kernel dispatch as
    :func:`attention_apply` and writes the whole prompt's K/V rows into
    the stack in one shot.  Every other kind runs on the layer's own
    cache (:meth:`attn.LayerCache.update`): Mamba2 its chunked scan from
    the cache's state, handing back the state after the prompt; mLSTM
    and sLSTM a ``lax.scan`` of their decode step — one compiled
    program, batched over the prompt, and bitwise identical to the
    token-by-token loop it replaces.  Returns (y (B,S,d), the stack).
    """
    if sub.kind == "attention":
        return attn.attention_prefill(params, sub.cfg, x, cache, pos_offset)

    def run(own):
        if sub.kind == "mamba2":
            return ssm_mod.mamba2_apply(params, sub.cfg, x, own)
        if sub.kind not in ("mlstm", "slstm"):
            return _sub_step(sub, params, x, own)

        def body(carry, x_t):
            y_t, new_carry = _sub_step(sub, params, x_t[:, None], carry)
            return new_carry, y_t[:, 0]

        new, ys = jax.lax.scan(body, own, x.transpose(1, 0, 2))
        return ys.transpose(1, 0, 2), new

    return cache.update(run)


def _sub_decode(sub: SubBlock, params, x, cache: attn.LayerCache, pos):
    """One token against the layer's cache; returns (y, the stack).
    Attention writes its K/V rows into the stack itself; every other
    kind decodes on the layer's own cache (:meth:`attn.LayerCache.update`)."""
    if sub.kind == "attention":
        return attn.attention_decode(params, sub.cfg, x, cache, pos)
    return cache.update(lambda own: _sub_step(sub, params, x, own))


# the projection weights that decode may read a layer at a time
# (``nn/linear.py``), by sub-block kind; each module owns its list
_STREAMED = {"attention": attn.PROJECTIONS, "mlp": mlp_mod.PROJECTIONS,
             "mamba2": ssm_mod.PROJECTIONS}
# and a hybrid layer's own: its adapter and linear
_HYBRID_STREAMED = (("adapter", "lora_a"), ("adapter", "lora_b"), ("linear",))


def _streamed_paths(layer: LayerSpec):
    paths = [(f"sub_{i}", "inner", name)
             for i, sub in enumerate(layer.subs) for name in _STREAMED.get(sub.kind, ())]
    return paths + list(_HYBRID_STREAMED) * layer.hybrid


def _get(tree, path):
    for key in path:
        if not isinstance(tree, dict) or key not in tree:
            return None
        tree = tree[key]
    return tree


def _set(tree, path, value):
    """A copy of nested dict ``tree`` with ``path`` set (or, for None,
    removed)."""
    key, rest = path[0], path[1:]
    out = dict(tree)
    if rest:
        out[key] = _set(tree[key], rest, value)
    elif value is None:
        del out[key]
    else:
        out[key] = value
    return out


def _split_streamed(layer: LayerSpec, stacked):
    """Take the weights :func:`linear.streams` accepts out of a stack
    segment's params: returns (the rest, {path: stack})."""
    rest, streamed = stacked, {}
    for path in _streamed_paths(layer):
        if linear.streams(_get(stacked, path)):
            streamed[path] = _get(stacked, path)
            rest = _set(rest, path, None)
    return rest, streamed


def _with_layer(params, streamed, layer):
    """One layer's params with each streamed stack as a LayerWeight."""
    for path, stack in streamed.items():
        params = _set(params, path, linear.LayerWeight(stack, layer))
    return params


# ---------------------------------------------------------------------------
# layer = sequence of pre-norm residual sub-blocks
# ---------------------------------------------------------------------------

def _take(tree, i):
    return jax.tree_util.tree_map(lambda x: x[i], tree)


def _at_spec_precision(fn):
    """Trace ``fn`` under the spec's default matmul precision."""
    @functools.wraps(fn)
    def run(self, *args, **kwargs):
        if self.spec.matmul_precision is None:
            return fn(self, *args, **kwargs)
        with jax.default_matmul_precision(self.spec.matmul_precision):
            return fn(self, *args, **kwargs)
    return run


class LM:
    def __init__(self, spec: ModelSpec):
        self.spec = spec
        self.segments = build_segments(spec.layers)
        self.enc_segments = build_segments(spec.encoder_layers, prefix="enc")
        hybrid = [seg.name for seg in self.segments if seg.spec.hybrid]
        assert not hybrid or spec.shared, "hybrid layers need ModelSpec.shared"
        # the shared block of each hybrid segment: its invocation index mod n
        self.block = {name: f"shared_{i % spec.shared.n}" for i, name in enumerate(hybrid)}
        # a cache no leaf of which reads the params, as one program per
        # (batch, max_seq, dtype): eagerly it was an op or two per leaf
        self._empty_cache = jax.jit(
            lambda batch, max_seq, dtype: self._build_cache(None, batch, max_seq, None, dtype),
            static_argnums=(0, 1, 2))

    def _norm(self, params, x):
        eps = {} if self.spec.norm_eps is None else {"eps": self.spec.norm_eps}
        return NORM_APPLY[self.spec.norm](params, x, **eps)

    # -- init ---------------------------------------------------------------

    def _layer_init(self, layer: LayerSpec, key, dtype):
        params = {}
        keys = jax.random.split(key, len(layer.subs) + 2 * layer.hybrid)
        for i, (sub, k) in enumerate(zip(layer.subs, keys)):
            params[f"sub_{i}"] = {
                "norm": NORM_INIT[self.spec.norm](sub.cfg.d_model, dtype),
                "inner": _sub_init(sub, k, dtype),
            }
        if layer.hybrid:
            shared, d = self.spec.shared, self.spec.d_model
            params["adapter"] = mlp_mod.adapter_init(
                shared.layer.subs[1].cfg, shared.adapter_rank, keys[-2], dtype)
            params["linear"] = P(init.scaled_normal(keys[-1], (d, d), dtype), (None, "embed"))
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
        for seg, k in zip(self.segments, keys[kidx : kidx + len(self.segments)]):
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
        if spec.shared:
            for b, k in enumerate(jax.random.split(keys[-1], spec.shared.n)):
                params[f"shared_{b}"] = self._layer_init(spec.shared.layer, k, dtype)
        return params

    # -- one layer ------------------------------------------------------------

    def _layer(self, layer: LayerSpec, params, h, cache, call, emb=None, block=None):
        """One layer's pre-norm residual sub-blocks, ``h + f(norm(h))``.
        ``cache`` maps each sub-block's key to its :class:`attn.LayerCache`
        (empty without a cache); ``call(sub, inner params, x, that view or
        None)`` runs one and returns ``(y, the stack with its new
        cache)``.  A hybrid layer first runs shared block ``block``
        (:meth:`_shared`), whose output joins the first sub-block's input.
        Returns (h, the new stacks under the keys of ``cache``)."""
        new = {}

        def run(key, sub, inner, x):
            y, new[key] = call(sub, inner, x, cache.get(key))
            return y

        lift = None
        if layer.hybrid:
            lift = self._shared(block, params, h, emb, run)
        for i, sub in enumerate(layer.subs):
            sp = params[f"sub_{i}"]
            with jax.named_scope(sub.kind):
                x = self._norm(sp["norm"], h if lift is None else h + lift)
                h = h + run(f"sub_{i}", sub, sp["inner"], x)
            lift = None
        return h, {key: new[key] for key in cache}

    def _shared(self, block, params, h, emb, run):
        """A hybrid layer's shared block on ``[h; emb]``, with the layer's
        own adapter and ``linear``: ``linear(mlp(norm(attention(norm([h;
        emb])))))``, no residual inside; its attention runs as
        ``run("shared", ...)``."""
        attn_sub, mlp_sub = self.spec.shared.layer.subs
        a, m = block["sub_0"], block["sub_1"]
        with jax.named_scope("attention"):
            x = self._norm(a["norm"], jnp.concatenate([h, emb.astype(h.dtype)], axis=-1))
            t = run("shared", attn_sub, a["inner"], x)
        with jax.named_scope("mlp"):
            t = mlp_mod.mlp_apply(m["inner"], mlp_sub.cfg, self._norm(m["norm"], t),
                                  adapter=params["adapter"])
            return linear.dense(t, params["linear"])

    def _walk(self, segments, params, cache, h, call, emb=None, stream=False):
        """Every segment's layers over ``h``, each segment one
        ``lax.scan`` (of one layer, too) over (layer index, its params)
        that carries ``h`` and the segment's stacked cache: ``cache`` is
        None for ``apply`` and ``encode``, whose scans carry none and
        whose layer body is rematerialized as the spec says.  Each layer
        gets an :class:`attn.LayerCache` view of the stack per sub-block,
        and ``call`` (:meth:`_layer`) writes what it changes (K/V rows, a
        recurrent state) into the stack, which a program that takes the
        cache donated updates in place.  With ``stream`` (decode) the
        scan closes over the stacks :func:`linear.streams` accepts and
        hands each layer a :class:`linear.LayerWeight`, and so are the
        shared blocks' weights, as stacks of one.  ``scan_layers`` False
        unrolls the scans.  Returns (h, new cache)."""
        new_cache: Dict[str, Any] = {}
        for seg in segments:
            block = params.get(self.block.get(seg.name))
            rest, streamed = params[seg.name], {}
            if stream:
                # streamed weights are closed over, not sliced as xs
                rest, streamed = _split_streamed(seg.spec, rest)
                if block is not None:
                    # an unstacked weight is a stack of one layer
                    b_rest, b_streamed = _split_streamed(
                        self.spec.shared.layer, jax.tree_util.tree_map(lambda x: x[None], block))
                    block = _with_layer(_take(b_rest, 0), b_streamed, jnp.int32(0))

            def body(carry, inp, _seg=seg, _block=block, _streamed=streamed):
                h, stack = carry
                layer, lp = inp
                view = {key: attn.LayerCache(c, layer) for key, c in stack.items()}
                return self._layer(_seg.spec, _with_layer(lp, _streamed, layer), h, view,
                                   call, emb, _block), None

            if cache is None and self.spec.remat:
                policy = None
                if self.spec.remat_policy == "dots":
                    policy = jax.checkpoint_policies.dots_with_no_batch_dims_saveable
                body = jax.checkpoint(body, prevent_cse=False, policy=policy)
            (h, new_cache[seg.name]), _ = jax.lax.scan(
                body, (h, {} if cache is None else cache[seg.name]),
                (jnp.arange(seg.count, dtype=jnp.int32), rest),
                unroll=not self.spec.scan_layers)
            h = constrain(h, ("batch", None, None))
        return h, new_cache

    def _apply_call(self, positions, enc_out):
        def call(sub, inner, x, _):
            return _sub_apply(sub, inner, x, positions=positions, enc_out=enc_out), None
        return call

    # -- forward ------------------------------------------------------------

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
            h = self._norm(params["final_norm"], h)
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
        call = self._apply_call(jnp.arange(h.shape[1])[None], None)
        h, _ = self._walk(self.enc_segments, params, None, h, call)
        return self._norm(params["enc_final_norm"], h)

    def _backbone(self, params, tokens, prefix_embeds, enc_out, positions):
        h = emb = self._embed(params, tokens, prefix_embeds)
        if positions is None:
            positions = jnp.arange(tokens.shape[1])[None]
        if self.spec.positional == "learned":
            h = h + params["pos_embed"][: h.shape[1]][None].astype(h.dtype)
        h = constrain(h, ("batch", None, None))
        h, _ = self._walk(self.segments, params, None, h,
                          self._apply_call(positions, enc_out), emb)
        return h

    @_at_spec_precision
    def hidden(self, params, tokens, *, prefix_embeds=None, enc_out=None, positions=None):
        """Full-sequence forward -> final normed hidden states (B, S, d).

        Used with :func:`repro.train.loss.chunked_cross_entropy` so the
        (B, S, vocab) logits never materialize at once.
        """
        h = self._backbone(params, tokens, prefix_embeds, enc_out, positions)
        return self._norm(params["final_norm"], h)

    def head_weight(self, params):
        """(weight, transposed): logits = h @ w or einsum('bsd,vd', h, w)."""
        if self.spec.tie_embeddings:
            return params["embed"], True
        return params["head"], False

    @_at_spec_precision
    def apply(self, params, tokens, *, prefix_embeds=None, enc_out=None, positions=None):
        """Full-sequence forward -> logits (B, S, vocab)."""
        h = self._backbone(params, tokens, prefix_embeds, enc_out, positions)
        return self._head(params, h)

    # -- decode -------------------------------------------------------------

    def _layer_cache(self, layer: LayerSpec, params_layer, batch, max_seq, enc_len, enc_out, dtype):
        cache = {}
        for i, sub in enumerate(layer.subs):
            c = _sub_cache_init(sub, batch, max_seq, enc_len, dtype)
            if sub.kind == "cross_attention" and enc_out is not None:
                c = attn.precompute_cross_kv(params_layer[f"sub_{i}"]["inner"], sub.cfg, enc_out, dtype)
            cache[f"sub_{i}"] = c
        if layer.hybrid:
            cache["shared"] = _sub_cache_init(
                self.spec.shared.layer.subs[0], batch, max_seq, enc_len, dtype)
        return cache

    def init_cache(self, params, batch, max_seq, *, enc_out=None, dtype=jnp.bfloat16):
        """Build the full decode cache pytree (segment-stacked).  Only
        cross-attention over ``enc_out`` reads ``params``; without it the
        cache comes from one compiled program."""
        if enc_out is None:
            return self._empty_cache(int(batch), int(max_seq), jnp.dtype(dtype))
        return self._build_cache(params, batch, max_seq, enc_out, dtype)

    def _build_cache(self, params, batch, max_seq, enc_out, dtype):
        enc_len = enc_out.shape[1] if enc_out is not None else 0
        cache: Dict[str, Any] = {}
        for seg in self.segments:
            if enc_out is not None and any(sub.kind == "cross_attention" for sub in seg.spec.subs):
                layer_caches = [self._layer_cache(
                    seg.spec, _take(params[seg.name], i), batch, max_seq, enc_len, enc_out, dtype)
                    for i in range(seg.count)]
                cache[seg.name] = jax.tree_util.tree_map(
                    lambda *xs: jnp.stack(xs), *layer_caches
                )
            else:
                c0 = self._layer_cache(seg.spec, None, batch, max_seq, enc_len, enc_out, dtype)
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
        same tuples serve every entry.
        """
        axes: Dict[str, Any] = {}
        for seg in self.segments:
            entry = {
                f"sub_{i}": dict(self._CACHE_AXES[sub.kind])
                for i, sub in enumerate(seg.spec.subs)
            }
            if seg.spec.hybrid:
                entry["shared"] = dict(self._CACHE_AXES["attention"])
            axes[seg.name] = entry
        return axes

    @_at_spec_precision
    def prefill(self, params, cache, tokens, pos_offset=0):
        """Batched prefill: the whole prompt in one full-sequence forward
        that also fills the decode caches.  tokens: (B, S) int32.

        Returns (logits (B, S, vocab), new_cache); decoding continues
        from ``pos = pos_offset + S`` with :meth:`decode`.  Replaces the
        token-by-token ``decode`` loop over the prompt (quadratic in
        prompt length, and meaningless to measure prefill latency on).
        """
        h = emb = self._embed(params, tokens, None)
        s = tokens.shape[1]
        if self.spec.positional == "learned":
            pe = jax.lax.dynamic_slice_in_dim(
                params["pos_embed"], pos_offset, s, axis=0)
            h = h + pe[None].astype(h.dtype)

        call = functools.partial(_sub_prefill, pos_offset=pos_offset)
        h, new_cache = self._walk(self.segments, params, cache, h, call, emb)
        return self._head(params, h), new_cache

    @_at_spec_precision
    def decode(self, params, cache, tokens, pos):
        """One-step decode.  tokens: (B, 1) int32; pos: an int32 vector
        (B,) of per-sequence positions (continuous batching: each serving
        slot decodes at its own depth), or a scalar that every sequence
        shares.

        Returns (logits (B, 1, vocab), new_cache).  Each segment's stacked
        cache is carried through its layer scan and updated in place: a
        layer writes one K/V row per sequence, or its recurrent state, and
        nothing else (:meth:`_walk`).  On a TPU ``ServingEngine`` donates
        the cache to its decode program, so the step writes into the
        buffer it was given; a caller that does not donate gets the same
        answers, with one copy of the cache at the program's boundary.
        """
        h = emb = self._embed(params, tokens, None)
        pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), tokens.shape[:1])
        if self.spec.positional == "learned":
            h = h + jnp.take(params["pos_embed"], pos, axis=0)[:, None].astype(h.dtype)

        call = functools.partial(_sub_decode, pos=pos)
        h, new_cache = self._walk(self.segments, params, cache, h, call, emb, stream=True)
        return self._head(params, h), new_cache
