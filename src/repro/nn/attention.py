"""Grouped-query attention with qk-norm, RoPE, sliding windows and KV caches.

Three entry points:
  * ``attention_init``    -- parameters
  * ``attention_apply``   -- full-sequence (training / prefill / encoder /
                             cross-attention) attention
  * ``attention_decode``  -- single-token decode against one layer of
                             a stacked, preallocated KV cache
                             (:class:`LayerCache`): writes one K/V row
                             per sequence

The sequence-mixing math is grouped (no materialized KV repetition): q is
reshaped to (batch, seq, kv_heads, group, d_head) so the einsum contracts
directly against the grouped KV, which keeps HLO FLOPs/bytes at the GQA
level rather than the MHA level.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.distributed.api import current_mesh
from repro.kernels.schedule import LANES
from repro.nn import initializers as init
from repro.nn.linear import bf16_passes, dense
from repro.nn.rope import apply_rope
from repro.nn.types import P

NEG_INF = -1e30


@dataclasses.dataclass(frozen=True)
class AttentionConfig:
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_head: Optional[int] = None
    use_bias: bool = False
    qk_norm: bool = False
    rope: bool = True
    rope_theta: float = 10000.0
    causal: bool = True
    window: Optional[int] = None  # sliding-window size (None = full)
    impl: str = "xla"  # "xla" | "xla_chunked" | "pallas"
    softmax_scale: Optional[float] = None
    d_out: Optional[int] = None  # output width, where not d_model (zamba2)
    # cost-variant accounting: unroll the chunked-attention KV scan so
    # HloCostAnalysis sees every chunk (see launch/dryrun.py)
    scan_unroll: bool = False
    kv_chunk: int = 1024  # xla_chunked block size (bigger = fewer carry rewrites)
    # context-parallel q + replicated kv in full-seq attention (see
    # _project_qkv docstring); enabled by the "seq_shard" dry-run variant
    seq_shard: bool = False

    @property
    def head_dim(self) -> int:
        return self.d_head if self.d_head is not None else self.d_model // self.n_heads

    @property
    def out_dim(self) -> int:
        return self.d_out if self.d_out is not None else self.d_model

    @property
    def group(self) -> int:
        assert self.n_heads % self.n_kv_heads == 0
        return self.n_heads // self.n_kv_heads

    @property
    def scale(self) -> float:
        return (
            self.softmax_scale
            if self.softmax_scale is not None
            else self.head_dim ** -0.5
        )



# the weights multiplied by ``nn.linear.dense``, which the decode of a
# layer scan may hand in a layer at a time (``nn/linear.py``)
PROJECTIONS = ("wq", "wk", "wv", "wo")


def attention_init(cfg: AttentionConfig, key, dtype=jnp.float32):
    dh = cfg.head_dim
    kq, kk, kv, ko = jax.random.split(key, 4)
    params = {
        "wq": P(init.scaled_normal(kq, (cfg.d_model, cfg.n_heads * dh), dtype), ("embed", "heads")),
        "wk": P(init.scaled_normal(kk, (cfg.d_model, cfg.n_kv_heads * dh), dtype), ("embed", "kv_heads")),
        "wv": P(init.scaled_normal(kv, (cfg.d_model, cfg.n_kv_heads * dh), dtype), ("embed", "kv_heads")),
        "wo": P(init.scaled_normal(ko, (cfg.n_heads * dh, cfg.out_dim), dtype, fan_in=cfg.n_heads * dh), ("heads", "embed")),
    }
    if cfg.use_bias:
        params["bq"] = P(jnp.zeros((cfg.n_heads * dh,), dtype), ("heads",))
        params["bk"] = P(jnp.zeros((cfg.n_kv_heads * dh,), dtype), ("kv_heads",))
        params["bv"] = P(jnp.zeros((cfg.n_kv_heads * dh,), dtype), ("kv_heads",))
    if cfg.qk_norm:
        params["q_norm"] = P(jnp.ones((dh,), dtype), (None,))
        params["k_norm"] = P(jnp.ones((dh,), dtype), (None,))
    return params


def _headwise_rmsnorm(x, scale, eps=1e-6):
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    return (xf * (var + eps) ** -0.5 * scale.astype(jnp.float32)).astype(x.dtype)


def _project_qkv(params, cfg: AttentionConfig, x, kv_x, positions, kv_positions,
                 constrain_full_seq: bool = False):
    """Shared projection path. Returns q:(B,S,H,Dh), k/v:(B,T,K,Dh).

    constrain_full_seq (full-sequence attention only): pins q to
    sequence-sharded ("act_seq" -> model axis) and k/v to replicated
    heads.  Without this, GSPMD can slide the fused-head-projection
    sharding onto the head_dim when n_heads doesn't divide the model axis
    (e.g. 56 heads on 16 chips) and then all-reduces the full O(S^2)
    score tensors — observed 896 GiB ARs on arctic-480b/prefill_32k.
    """
    from repro.distributed.api import constrain

    b, s, _ = x.shape
    dh = cfg.head_dim
    q = dense(x, params["wq"])
    k = dense(kv_x, params["wk"])
    v = dense(kv_x, params["wv"])
    if cfg.use_bias:
        q, k, v = q + params["bq"], k + params["bk"], v + params["bv"]
    t = kv_x.shape[1]
    q = q.reshape(b, s, cfg.n_heads, dh)
    k = k.reshape(b, t, cfg.n_kv_heads, dh)
    v = v.reshape(b, t, cfg.n_kv_heads, dh)
    if constrain_full_seq:
        q = constrain(q, ("batch", "act_seq", None, None))
        k = constrain(k, ("batch", None, None, None))
        v = constrain(v, ("batch", None, None, None))
    if cfg.qk_norm:
        q = _headwise_rmsnorm(q, params["q_norm"])
        k = _headwise_rmsnorm(k, params["k_norm"])
    if cfg.rope:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, kv_positions, cfg.rope_theta)
    return q, k, v


def chunked_attention(q, k, v, scale, *, causal=True, window=None, kv_chunk=1024,
                      q_offset=0, unroll=False):
    """Flash-style attention in pure JAX: lax.scan over KV chunks with an
    online softmax — O(S * kv_chunk) score memory instead of O(S^2), and
    GSPMD-shardable (used by the dry-run's optimized configs, where the
    Pallas kernel cannot lower on the CPU host platform).

    q: (B,S,H,Dh); k/v: (B,T,K,Dh).  Returns (B,S,H,Dh).
    """
    b, s, h, dh = q.shape
    t, kheads = k.shape[1], k.shape[2]
    g = h // kheads
    nchunks = t // kv_chunk
    assert t % kv_chunk == 0, (t, kv_chunk)
    qg = q.reshape(b, s, kheads, g, dh)
    q_pos = (jnp.arange(s) + q_offset)[:, None]

    kc = k.reshape(b, nchunks, kv_chunk, kheads, dh).transpose(1, 0, 2, 3, 4)
    vc = v.reshape(b, nchunks, kv_chunk, kheads, dh).transpose(1, 0, 2, 3, 4)

    def body(carry, inp):
        m_prev, l_prev, acc = carry
        idx, k_blk, v_blk = inp
        scores = jnp.einsum("bskgd,btkd->bkgst", qg, k_blk).astype(jnp.float32) * scale
        k_pos = idx * kv_chunk + jnp.arange(kv_chunk)[None, :]
        mask = jnp.ones((s, kv_chunk), bool)
        if causal:
            mask &= k_pos <= q_pos
        if window is not None:
            mask &= k_pos > q_pos - window
        scores = jnp.where(mask[None, None, None], scores, NEG_INF)
        m_cur = jnp.max(scores, axis=-1)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(scores - m_new[..., None])
        p = jnp.where(mask[None, None, None], p, 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_prev + jnp.sum(p, axis=-1)
        acc = acc * alpha[..., None] + jnp.einsum(
            "bkgst,btkd->bkgsd", p.astype(v_blk.dtype), v_blk
        ).astype(jnp.float32)
        return (m_new, l_new, acc), None

    m0 = jnp.full((b, kheads, g, s), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, kheads, g, s), jnp.float32)
    acc0 = jnp.zeros((b, kheads, g, s, dh), jnp.float32)
    # unroll=True is used by the dry-run cost variant: HloCostAnalysis
    # counts while bodies once, so the KV loop must be visible.
    (m_f, l_f, acc), _ = jax.lax.scan(body, (m0, l0, acc0),
                                      (jnp.arange(nchunks), kc, vc),
                                      unroll=nchunks if unroll else 1)
    out = acc / jnp.maximum(l_f, 1e-30)[..., None]
    return out.transpose(0, 3, 1, 2, 4).reshape(b, s, h, dh).astype(q.dtype)


def grouped_attention(q, k, v, mask, scale):
    """Core GQA soft-attention.

    q: (B,S,H,Dh), k/v: (B,T,K,Dh), mask: broadcastable to (B,K,G,S,T).
    Returns (B,S,H,Dh).
    """
    b, s, h, dh = q.shape
    kheads = k.shape[2]
    g = h // kheads
    qg = q.reshape(b, s, kheads, g, dh)
    scores = jnp.einsum("bskgd,btkd->bkgst", qg, k).astype(jnp.float32) * scale
    scores = jnp.where(mask, scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    out = jnp.einsum("bkgst,btkd->bskgd", probs, v)
    return out.reshape(b, s, h, dh)


def make_mask(s, t, causal, window, q_offset=0):
    """(1,1,1,S,T) boolean attention mask."""
    qi = jnp.arange(s)[:, None] + q_offset
    kj = jnp.arange(t)[None, :]
    mask = jnp.ones((s, t), bool)
    if causal:
        mask &= kj <= qi
    if window is not None:
        mask &= kj > qi - window
    return mask[None, None, None]


def attention_apply(
    params,
    cfg: AttentionConfig,
    x,
    positions=None,
    kv_x=None,
    kv_positions=None,
    mask=None,
):
    """Full-sequence attention.  ``kv_x`` enables cross-attention."""
    b, s, _ = x.shape
    cross = kv_x is not None
    if kv_x is None:
        kv_x = x
    if positions is None:
        positions = jnp.arange(s)[None]
    if kv_positions is None:
        kv_positions = jnp.arange(kv_x.shape[1])[None]
    q, k, v = _project_qkv(params, cfg, x, kv_x, positions, kv_positions,
                           constrain_full_seq=cfg.seq_shard and not cross)
    if mask is None:
        causal = cfg.causal and not cross
        mask = make_mask(s, kv_x.shape[1], causal, None if cross else cfg.window)
    if cfg.impl == "pallas" and not cross:
        from repro.kernels import ops as kops

        out = kops.flash_attention(
            q, k, v, causal=cfg.causal, window=cfg.window, scale=cfg.scale
        )
    elif cfg.impl == "xla_chunked" and not cross:
        kv_chunk = min(cfg.kv_chunk, kv_x.shape[1])
        while kv_x.shape[1] % kv_chunk:
            kv_chunk //= 2
        out = chunked_attention(
            q, k, v, cfg.scale, causal=cfg.causal, window=cfg.window,
            kv_chunk=max(kv_chunk, 1), unroll=cfg.scan_unroll,
        )
    else:
        out = grouped_attention(q, k, v, mask, cfg.scale)
    out = out.reshape(b, s, cfg.n_heads * cfg.head_dim)
    return jnp.einsum("bsh,hd->bsd", out, params["wo"])


class LayerCache(NamedTuple):
    """Layer ``layer`` of a segment's stacked cache, not sliced: ``stack``
    is a dict of ``(L, ...)`` leaves.  A layer scan carries the stack and
    hands each layer this view, so a layer writes only what it changes
    into the stack, which a step that takes it donated updates in place."""

    stack: Dict[str, Any]
    layer: jax.Array  # int32 scalar

    def read(self):
        """The layer's own cache, sliced out of the stack."""
        return {name: jax.lax.dynamic_index_in_dim(leaf, self.layer, keepdims=False)
                for name, leaf in self.stack.items()}

    def write(self, cache):
        """The stack with the layer's cache replaced by ``cache``."""
        return {name: jax.lax.dynamic_update_index_in_dim(leaf, cache[name], self.layer, 0)
                for name, leaf in self.stack.items()}

    def update(self, fn):
        """``fn`` on the layer's own cache, which returns (y, a new
        cache): returns (y, the stack with that cache written back, or
        as it was where ``fn`` returned the cache it was given)."""
        own = self.read()
        y, new = fn(own)
        return y, self.stack if new is own else self.write(new)


def init_kv_cache(cfg: AttentionConfig, batch, max_seq, dtype=jnp.bfloat16):
    shape = (batch, max_seq, cfg.n_kv_heads, cfg.head_dim)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def precompute_cross_kv(params, cfg: AttentionConfig, enc_out, dtype=jnp.bfloat16):
    """Project encoder output once; reused for every decode step."""
    b, t, _ = enc_out.shape
    k = jnp.einsum("bsd,dh->bsh", enc_out, params["wk"])
    v = jnp.einsum("bsd,dh->bsh", enc_out, params["wv"])
    if cfg.use_bias:
        k, v = k + params["bk"], v + params["bv"]
    dh = cfg.head_dim
    return {
        "k": k.reshape(b, t, cfg.n_kv_heads, dh).astype(dtype),
        "v": v.reshape(b, t, cfg.n_kv_heads, dh).astype(dtype),
    }


def cross_attention_cached(params, cfg: AttentionConfig, x, cache):
    """Decode-time cross-attention against a precomputed cross-KV cache."""
    b, s, _ = x.shape
    dh = cfg.head_dim
    q = jnp.einsum("bsd,dh->bsh", x, params["wq"])
    if cfg.use_bias:
        q = q + params["bq"]
    q = q.reshape(b, s, cfg.n_heads, dh)
    if cfg.qk_norm:
        q = _headwise_rmsnorm(q, params["q_norm"])
    t = cache["k"].shape[1]
    mask = jnp.ones((1, 1, 1, s, t), bool)
    out = grouped_attention(q, cache["k"].astype(q.dtype), cache["v"].astype(q.dtype), mask, cfg.scale)
    out = out.reshape(b, s, cfg.n_heads * dh)
    return jnp.einsum("bsh,hd->bsd", out, params["wo"])


def attention_prefill(params, cfg: AttentionConfig, x, cache: LayerCache, pos_offset=0):
    """Batched prefill: full-sequence attention through the same kernel
    dispatch as :func:`attention_apply` (pallas flash / xla_chunked /
    grouped), writing the prompt's K/V rows straight into layer
    ``cache.layer`` of the stacked cache in one shot instead of
    token-by-token.  x: (B,S,d_model); the prompt occupies cache
    positions ``[pos_offset, pos_offset+S)``.

    Returns (y (B,S,d_model), the stack) — bitwise the same cache a
    ``attention_decode`` loop over the prompt would produce, at
    full-sequence kernel cost (see tests/test_serving.py).
    """
    b, s, _ = x.shape
    positions = (pos_offset + jnp.arange(s))[None]
    q, k, v = _project_qkv(params, cfg, x, x, positions, positions)
    # the prompt's rows as the cache holds them, and decode reads them
    rows = {n: new.astype(cache.stack[n].dtype) for n, new in (("k", k), ("v", v))}
    zero = jnp.int32(0)
    start = (cache.layer, zero, jnp.int32(pos_offset), zero, zero)
    stack = {n: jax.lax.dynamic_update_slice(cache.stack[n], rows[n][None], start)
             for n in rows}
    if cfg.impl == "pallas" and pos_offset == 0:
        from repro.kernels import ops as kops

        out = kops.flash_attention(
            q, k, v, causal=cfg.causal, window=cfg.window, scale=cfg.scale)
    elif cfg.impl == "xla_chunked" and pos_offset == 0:
        kv_chunk = min(cfg.kv_chunk, s)
        while s % kv_chunk:
            kv_chunk //= 2
        out = chunked_attention(
            q, k, v, cfg.scale, causal=cfg.causal, window=cfg.window,
            kv_chunk=max(kv_chunk, 1), unroll=cfg.scan_unroll)
    else:
        t = pos_offset + s
        mask = make_mask(s, t, cfg.causal, cfg.window, q_offset=pos_offset)
        if pos_offset:
            # chunked prompt ingestion attends against the cache prefix,
            # which the flash/chunked paths don't slice yet
            rows = {n: jax.lax.dynamic_slice(
                        stack[n], (cache.layer, zero, zero, zero, zero),
                        (1, b, t) + stack[n].shape[3:])[0] for n in rows}
        out = grouped_attention(q, rows["k"].astype(q.dtype),
                                rows["v"].astype(q.dtype), mask, cfg.scale)
    out = out.reshape(b, s, cfg.n_heads * cfg.head_dim)
    y = jnp.einsum("bsh,hd->bsd", out, params["wo"])
    return y, stack


def _write_rows(leaf, new, pos):
    """``leaf`` (B,T,K,Dh) with row ``b`` of ``new`` (B,1,K,Dh) written at
    position ``pos[b]`` of sequence ``b``, each row a slice."""
    zero = jnp.int32(0)
    new = new.astype(leaf.dtype)
    for i in range(new.shape[0]):
        leaf = jax.lax.dynamic_update_slice(leaf, new[i][None], (jnp.int32(i), pos[i], zero, zero))
    return leaf


def _attend_rows(q, kv, pos, cfg: AttentionConfig):
    """q (B,1,H,Dh) over the positions ``<= pos`` (within the sliding
    window when configured) of one layer's K/V (B,T,K,Dh): every
    position is read and the rest masked off."""
    positions = pos[:, None]
    kj = jnp.arange(kv["k"].shape[1])
    valid = kj[None, :] <= positions
    if cfg.window is not None:
        valid &= kj[None, :] > positions - cfg.window
    mask = valid[:, None, None, None, :]  # (B,1,1,1,T)
    return grouped_attention(q, kv["k"].astype(q.dtype), kv["v"].astype(q.dtype), mask,
                             cfg.scale)


def _attend_stack(q, cache: LayerCache, pos, cfg: AttentionConfig):
    """q (B,1,H,Dh) over layer ``cache.layer`` of a stacked K/V cache
    whose heads are lane-aligned.  On a TPU the decode attention kernel
    reads each sequence's K/V only up to its position, in blocks, from
    the stack in place.  XLA slices the whole layer out and masks it
    elsewhere, and wherever the kernel would change the answer or its
    partitioning: a sliding window (the kernel starts every sequence at
    position 0), a precision other than one bf16 pass (the kernel's), or
    a mesh that shards the cache (a Pallas call does not partition)."""
    def xla(q, stack, layer, pos):
        return _attend_rows(q, LayerCache(stack, layer).read(), pos, cfg)

    if cfg.window is not None or bf16_passes() != 1 or current_mesh() is not None:
        return xla(q, cache.stack, cache.layer, pos)

    # a fresh closure on purpose, as in ``nn.linear.dense``: the recorder
    # sees the kernel call on every trace
    def kernel(q, stack, layer, pos):
        from repro.kernels import ops as kops

        out = kops.decode_attention(q[:, 0], stack["k"], stack["v"], layer, pos + 1,
                                    scale=cfg.scale, platform="tpu")
        return out[:, None].astype(q.dtype)

    return jax.lax.platform_dependent(q, cache.stack, cache.layer, pos, tpu=kernel,
                                      default=xla)


def attention_decode(params, cfg: AttentionConfig, x, cache: LayerCache, pos):
    """One-token decode.  x: (B,1,d_model); pos: an int32 vector (B,) of
    *per-sequence* positions (continuous batching: each serving slot
    decodes at its own depth).

    ``cache`` is one layer of the segment's stacked ``{"k", "v"}`` of
    (L,B,T,K,Dh).  Writes one K/V row per sequence at its position,
    attends to positions ``<= pos`` (within the sliding window when
    configured) in the updated cache, and returns (y, the stack).  A TPU
    lays out a cache whose heads are lane-aligned with the head dim
    minor: the rows are scattered straight into layer ``cache.layer`` of
    the stack, and attention reads the stack after them
    (:func:`_attend_stack`: on a TPU, each sequence's valid blocks
    only).  One whose heads are not (zamba2's 160) it lays out with the
    positions minor, and in a layer loop XLA would copy such a stack
    whole to scatter into it: the rows go into the layer's own cache as
    slices (:func:`_write_rows`), which goes back into the stack
    (:meth:`LayerCache.update`; the slice of a stack of one layer is the
    stack).  On a TPU the serving engine donates the cache to its decode
    program, so the writes are in place.
    """
    b = x.shape[0]
    positions = pos[:, None]
    q, k_new, v_new = _project_qkv(params, cfg, x, x, positions, positions)
    new = {"k": k_new, "v": v_new}

    def project(out):
        return dense(out.reshape(b, 1, cfg.n_heads * cfg.head_dim), params["wo"])

    if cfg.head_dim % LANES == 0:
        rows = (cache.layer, jnp.arange(b), pos)
        stack = {n: cache.stack[n].at[rows].set(new[n][:, 0].astype(cache.stack[n].dtype))
                 for n in new}
        return project(_attend_stack(q, LayerCache(stack, cache.layer), pos, cfg)), stack

    def write(own):
        kv = {n: _write_rows(own[n], new[n], pos) for n in new}
        return project(_attend_rows(q, kv, pos, cfg)), kv

    return cache.update(write)
