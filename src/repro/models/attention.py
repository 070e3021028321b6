"""Attention: GQA (full / sliding-window) and MLA (DeepSeek-V2), with KV caches.

Cache layout is unified: every cache carries ``pos_ids`` — the absolute
position stored in each slot (-1 = empty).  Full-causal caches have
``cache_len = max_seq``; sliding-window caches are ring buffers of
``cache_len = window`` (write slot = pos % window), which is what makes
``long_500k`` decode O(window) in memory for attention archs.  A decoder's
layer scan hands attention the stack of every layer's cache and the layer's
index: only the new positions are written, in place, and attention reads the
layer's slab with them in it.

MLA caches the *latent* (c_kv, k_rope) — the paper-faithful DeepSeek-V2
design.  One query position against a cache (decode) runs the weight-absorbed
form, which never re-expands K/V over the cache length (arXiv:2405.04434
§2.1.2); prefill and training run the expanded form.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro import telemetry
from repro.configs.base import AttentionConfig
from repro.models import common
from repro.sharding import logical

NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------


def init(key: jax.Array, d_model: int, cfg: AttentionConfig, dtype) -> dict:
    if cfg.kind == "mla":
        return _mla_init(key, d_model, cfg, dtype)
    return _gqa_init(key, d_model, cfg, dtype)


def _gqa_init(key, d_model, cfg: AttentionConfig, dtype) -> dict:
    kq, kk, kv, ko, kb = jax.random.split(key, 5)
    h, kh, dh = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    p = {
        "w_q": common.dense_init(kq, d_model, (h, dh), dtype),
        "w_k": common.dense_init(kk, d_model, (kh, dh), dtype),
        "w_v": common.dense_init(kv, d_model, (kh, dh), dtype),
        "w_o": common.dense_init(ko, h * dh, d_model, dtype),
    }
    if cfg.qkv_bias:
        del kb
        p["b_q"] = jnp.zeros((h, dh), dtype)
        p["b_k"] = jnp.zeros((kh, dh), dtype)
        p["b_v"] = jnp.zeros((kh, dh), dtype)
    return p


def _mla_init(key, d_model, cfg: AttentionConfig, dtype) -> dict:
    ks = jax.random.split(key, 8)
    h = cfg.num_heads
    nope, rope, vd, lora = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim, cfg.kv_lora_rank
    p = {
        "w_dkv": common.dense_init(ks[0], d_model, lora + rope, dtype),
        "kv_norm": common.rmsnorm_init(lora, dtype),
        "w_uk": common.dense_init(ks[1], lora, (h, nope), dtype),
        "w_uv": common.dense_init(ks[2], lora, (h, vd), dtype),
        "w_o": common.dense_init(ks[3], h * vd, d_model, dtype),
    }
    if cfg.q_lora_rank:
        p["w_dq"] = common.dense_init(ks[4], d_model, cfg.q_lora_rank, dtype)
        p["q_norm"] = common.rmsnorm_init(cfg.q_lora_rank, dtype)
        p["w_uq"] = common.dense_init(ks[5], cfg.q_lora_rank, (h, nope + rope), dtype)
    else:
        p["w_q"] = common.dense_init(ks[5], d_model, (h, nope + rope), dtype)
    return p


# ---------------------------------------------------------------------------
# Caches
# ---------------------------------------------------------------------------


def init_cache(cfg: AttentionConfig, batch: int, max_seq: int, dtype) -> dict:
    """Decode cache; ring buffer of size window when sliding-window.

    cache_quant="int8" stores K/V as int8 with a per-(slot, head) absmax
    scale — halves cache bytes (the decode memory-term floor) at <1e-2
    logit error (tests/test_perf_variants.py)."""
    cache_len = min(max_seq, cfg.sliding_window) if cfg.sliding_window else max_seq
    pos_ids = jnp.full((batch, cache_len), -1, jnp.int32)
    if cfg.kind == "mla":
        return {
            "c_kv": jnp.zeros((batch, cache_len, cfg.kv_lora_rank), dtype),
            "k_rope": jnp.zeros((batch, cache_len, cfg.qk_rope_dim), dtype),
            "pos_ids": pos_ids,
        }
    kv_shape = (batch, cache_len, cfg.num_kv_heads, cfg.head_dim)
    if cfg.cache_quant == "int8":
        return {
            "k": jnp.zeros(kv_shape, jnp.int8),
            "v": jnp.zeros(kv_shape, jnp.int8),
            "k_scale": jnp.zeros(kv_shape[:3], jnp.float16),
            "v_scale": jnp.zeros(kv_shape[:3], jnp.float16),
            "pos_ids": pos_ids,
        }
    return {
        "k": jnp.zeros(kv_shape, dtype),
        "v": jnp.zeros(kv_shape, dtype),
        "pos_ids": pos_ids,
    }


def _quantize_kv(x: jax.Array) -> tuple[jax.Array, jax.Array]:
    """x: (B, T, Kh, D) -> (int8 values, f16 per-(token, head) scales)."""
    absmax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1)
    scale = jnp.maximum(absmax / 127.0, 1e-8)
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale[..., None]), -127, 127)
    return q.astype(jnp.int8), scale.astype(jnp.float16)


def _dequantize_kv(q: jax.Array, scale: jax.Array) -> jax.Array:
    return q.astype(jnp.float32) * scale.astype(jnp.float32)[..., None]


def cache_bytes(cfg: AttentionConfig, batch: int, max_seq: int, bytes_per_el: int = 2) -> int:
    cache_len = min(max_seq, cfg.sliding_window) if cfg.sliding_window else max_seq
    if cfg.kind == "mla":
        return batch * cache_len * (cfg.kv_lora_rank + cfg.qk_rope_dim) * bytes_per_el
    return batch * cache_len * 2 * cfg.num_kv_heads * cfg.head_dim * bytes_per_el


def _write_slots(cache_len: int, positions: jax.Array) -> jax.Array:
    """Ring-buffer slot for each absolute position (identity if cache covers seq)."""
    return positions % cache_len


def _scatter_cache(buf: jax.Array, slots: jax.Array, values: jax.Array, layer=None) -> jax.Array:
    """buf: (B, C, ...), or with ``layer`` every layer's stack (L, B, C, ...),
    written in place at [layer, row, slot]; slots: (B, T); values: (B, T, ...)."""
    bidx = jnp.arange(slots.shape[0])[:, None]
    index = (bidx, slots) if layer is None else (layer, bidx, slots)
    return buf.at[index].set(values.astype(buf.dtype))


def _write_cache(cache: dict, layer, positions: jax.Array, entries: dict) -> tuple[dict, dict]:
    """Write ``entries`` (each (B, T, ...)) and ``positions`` into their ring
    slots.  Returns (the cache, this layer's view of it).  With ``layer`` the
    cache is the stack of every layer's and only the new positions are
    written; the view is that layer's slab, the new positions in it."""
    slots = _write_slots(cache["pos_ids"].shape[-1], positions)
    entries = dict(entries, pos_ids=positions)
    cache = {name: _scatter_cache(buf, slots, entries[name], layer) for name, buf in cache.items()}
    if layer is None:
        return cache, cache
    view = {name: jax.lax.dynamic_index_in_dim(buf, layer, keepdims=False)
            for name, buf in cache.items()}
    return cache, view


# ---------------------------------------------------------------------------
# Core attend
# ---------------------------------------------------------------------------


def _attend(q, k, v, mask, scale):
    """q: (B,T,Kh,G,dh) grouped query; k/v: (B,C,Kh,dh); mask: (B,1,1,T,C)."""
    scores = jnp.einsum("btkgd,bckd->bkgtc", q.astype(jnp.float32), k.astype(jnp.float32))
    scores = scores * scale + jnp.where(mask, 0.0, NEG_INF)  # mask: (B,1,1,T,C)
    probs = jax.nn.softmax(scores, axis=-1)
    ctx = jnp.einsum("bkgtc,bckd->btkgd", probs, v.astype(jnp.float32))
    return ctx


def _make_mask(q_pos: jax.Array, kv_pos: jax.Array, window: Optional[int]) -> jax.Array:
    """(B, T, C) bool: causal, slot-valid, and optionally windowed."""
    m = (kv_pos[:, None, :] >= 0) & (kv_pos[:, None, :] <= q_pos[:, :, None])
    if window:
        m &= (q_pos[:, :, None] - kv_pos[:, None, :]) < window
    return m


def gqa_apply(
    params: dict,
    cfg: AttentionConfig,
    x: jax.Array,
    positions: jax.Array,
    *,
    cache: Optional[dict] = None,
    layer: Optional[jax.Array] = None,
    causal: bool = True,
) -> tuple[jax.Array, Optional[dict]]:
    """x: (B, T, D); positions: (B, T) absolute. Returns (out, new_cache).
    With ``layer``, ``cache`` is every layer's stack (L, B, C, ...) and this
    layer's new positions are written into it at [layer, row, slot]."""
    b, t, _ = x.shape
    h, kh, dh = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    g = h // kh

    q = jnp.einsum("btd,dhx->bthx", x, params["w_q"])
    k = jnp.einsum("btd,dkx->btkx", x, params["w_k"])
    v = jnp.einsum("btd,dkx->btkx", x, params["w_v"])
    if "b_q" in params:
        q, k, v = q + params["b_q"], k + params["b_k"], v + params["b_v"]
    q = logical.shard(q, "batch", "seq", "heads", "head_dim")
    k = logical.shard(k, "batch", "seq", "kv_heads", "head_dim")
    v = logical.shard(v, "batch", "seq", "kv_heads", "head_dim")
    q = common.apply_rope(q, positions, cfg.rope_theta, cfg.rope_scaling)
    k = common.apply_rope(k, positions, cfg.rope_theta, cfg.rope_scaling)

    if cache is not None:
        if "k_scale" in cache:  # int8-quantized cache
            (kq, ks), (vq, vs) = _quantize_kv(k), _quantize_kv(v)
            cache, view = _write_cache(
                cache, layer, positions, {"k": kq, "v": vq, "k_scale": ks, "v_scale": vs}
            )
            kk = _dequantize_kv(view["k"], view["k_scale"])
            vv = _dequantize_kv(view["v"], view["v_scale"])
        else:
            cache, view = _write_cache(cache, layer, positions, {"k": k, "v": v})
            kk, vv = view["k"], view["v"]
        kv_pos = view["pos_ids"]
    else:
        kk, vv, kv_pos = k, v, positions

    if causal:
        mask = _make_mask(positions, kv_pos, cfg.sliding_window)
    else:
        mask = (kv_pos[:, None, :] >= 0) & jnp.ones((b, t, 1), bool)
    qg = q.reshape(b, t, kh, g, dh)
    ctx = _attend(qg, kk, vv, mask[:, None, None], dh**-0.5)
    ctx = ctx.reshape(b, t, h * dh).astype(x.dtype)
    out = jnp.einsum("bte,ed->btd", ctx, params["w_o"])
    return logical.shard(out, "batch", "residual_seq", "embed"), cache


def cross_attention_apply(
    params: dict,
    cfg: AttentionConfig,
    x: jax.Array,
    enc_kv: tuple[jax.Array, jax.Array],
) -> jax.Array:
    """Decoder cross-attention against precomputed encoder K/V (no RoPE)."""
    b, t, _ = x.shape
    h, kh, dh = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    q = jnp.einsum("btd,dhx->bthx", x, params["w_q"])
    k, v = enc_kv
    qg = q.reshape(b, t, kh, h // kh, dh)
    mask = jnp.ones((b, 1, 1, t, k.shape[1]), bool)
    ctx = _attend(qg, k, v, mask, dh**-0.5).reshape(b, t, h * dh).astype(x.dtype)
    return jnp.einsum("bte,ed->btd", ctx, params["w_o"])


def encoder_kv(
    params: dict, cfg: AttentionConfig, enc_out: jax.Array
) -> tuple[jax.Array, jax.Array]:
    k = jnp.einsum("btd,dkx->btkx", enc_out, params["w_k"])
    v = jnp.einsum("btd,dkx->btkx", enc_out, params["w_v"])
    return k, v


# ---------------------------------------------------------------------------
# MLA (multi-head latent attention)
# ---------------------------------------------------------------------------


def _mla_q(params, cfg: AttentionConfig, x, positions, eps):
    if cfg.q_lora_rank:
        cq = jnp.einsum("btd,dr->btr", x, params["w_dq"])
        cq = common.rmsnorm(params["q_norm"], cq, eps)
        q = jnp.einsum("btr,rhx->bthx", cq, params["w_uq"])
    else:
        q = jnp.einsum("btd,dhx->bthx", x, params["w_q"])
    q_nope = q[..., : cfg.qk_nope_dim]
    q_rope = common.apply_rope(
        q[..., cfg.qk_nope_dim :], positions, cfg.rope_theta, cfg.rope_scaling
    )
    return q_nope, q_rope


def mla_softmax_scale(cfg: AttentionConfig) -> float:
    """(qk_nope + qk_rope)^-0.5, times YaRN's mscale(factor, mscale_all_dim)
    squared where the rope scaling states ``mscale_all_dim``."""
    scale = (cfg.qk_nope_dim + cfg.qk_rope_dim) ** -0.5
    rs = cfg.rope_scaling
    if rs is not None and rs.mscale_all_dim:
        scale *= common.yarn_mscale(rs.factor, rs.mscale_all_dim) ** 2
    return scale


MLA_QUERY_BLOCK = 128  # queries per block of the expanded form's scores


def mla_context_expanded(params, q_nope, q_rope, c_all, krope_all, bias, scale):
    """Per-head K and V rebuilt from the latent at every cached position:
    (B,T,H,nope), (B,T,H,rope) queries against (B,C,lora), (B,C,rope) ->
    context (B,T,H,v) f32.  ``bias``: (B,1,T,C) additive mask.  Queries run
    in blocks of at most ``MLA_QUERY_BLOCK`` (the last zero-padded), so the
    f32 scores held at once are (B,H,block,C), not (B,H,T,C)."""
    k_nope = jnp.einsum("bcr,rhn->bchn", c_all, params["w_uk"]).astype(jnp.float32)
    vv = jnp.einsum("bcr,rhv->bchv", c_all, params["w_uv"]).astype(jnp.float32)
    krope_all = krope_all.astype(jnp.float32)

    def block(args):
        qn, qr, bs = args
        scores = jnp.einsum("bthn,bchn->bhtc", qn.astype(jnp.float32), k_nope)
        scores += jnp.einsum("bthp,bcp->bhtc", qr.astype(jnp.float32), krope_all)
        probs = jax.nn.softmax(scores * scale + bs, axis=-1)
        return jnp.einsum("bhtc,bchv->bthv", probs, vv)

    t = q_nope.shape[1]
    n = -(-t // MLA_QUERY_BLOCK)
    if n == 1:
        return block((q_nope, q_rope, bias))
    tb = -(-t // n)
    pad = n * tb - t

    def blocks(x, axis):
        x = jnp.pad(x, [(0, pad if i == axis else 0) for i in range(x.ndim)])
        x = x.reshape(x.shape[:axis] + (n, tb) + x.shape[axis + 1 :])
        return jnp.moveaxis(x, axis, 0)

    ctx = jax.lax.map(block, (blocks(q_nope, 1), blocks(q_rope, 1), blocks(bias, 2)))
    ctx = jnp.moveaxis(ctx, 0, 1)  # (B, n, tb, H, v)
    return ctx.reshape((ctx.shape[0], n * tb) + ctx.shape[3:])[:, :t]


def mla_context_absorbed(params, q_nope, q_rope, c_all, krope_all, bias, scale):
    """The same context with W_uk folded into the query and W_uv applied
    after the latent context: scores and context live in the latent space,
    so nothing is expanded over the cache."""
    q_lat = jnp.einsum(
        "bthn,rhn->bthr", q_nope.astype(jnp.float32), params["w_uk"].astype(jnp.float32)
    )
    scores = jnp.einsum("bthr,bcr->bhtc", q_lat, c_all.astype(jnp.float32))
    scores += jnp.einsum(
        "bthp,bcp->bhtc", q_rope.astype(jnp.float32), krope_all.astype(jnp.float32)
    )
    probs = jax.nn.softmax(scores * scale + bias, axis=-1)
    ctx_lat = jnp.einsum("bhtc,bcr->bthr", probs, c_all.astype(jnp.float32))
    return jnp.einsum("bthr,rhv->bthv", ctx_lat, params["w_uv"].astype(jnp.float32))


@telemetry.scoped("repro.mla")
def mla_apply(
    params: dict,
    cfg: AttentionConfig,
    x: jax.Array,
    positions: jax.Array,
    *,
    cache: Optional[dict] = None,
    layer: Optional[jax.Array] = None,
    eps: float = 1e-5,
) -> tuple[jax.Array, Optional[dict]]:
    """``eps``: the model's RMSNorm epsilon, for the latent norms; ``layer``
    as in ``gqa_apply``.  One query position against a cache runs
    ``mla_context_absorbed``; more positions, or no cache,
    ``mla_context_expanded``."""
    b, t, _ = x.shape
    h = cfg.num_heads

    q_nope, q_rope = _mla_q(params, cfg, x, positions, eps)

    dkv = jnp.einsum("btd,dr->btr", x, params["w_dkv"])
    c_kv = common.rmsnorm(params["kv_norm"], dkv[..., : cfg.kv_lora_rank], eps)
    k_rope = common.apply_rope(
        dkv[..., cfg.kv_lora_rank :], positions, cfg.rope_theta, cfg.rope_scaling
    )
    c_kv = logical.shard(c_kv, "batch", "seq", "kv_lora")

    if cache is not None:
        cache, view = _write_cache(cache, layer, positions, {"c_kv": c_kv, "k_rope": k_rope})
        c_all, krope_all, kv_pos = view["c_kv"], view["k_rope"], view["pos_ids"]
    else:
        c_all, krope_all, kv_pos = c_kv, k_rope, positions

    mask = _make_mask(positions, kv_pos, cfg.sliding_window)[:, None]  # (B,1,T,C)
    bias = jnp.where(mask, 0.0, NEG_INF)
    context = mla_context_absorbed if cache is not None and t == 1 else mla_context_expanded
    ctx = context(params, q_nope, q_rope, c_all, krope_all, bias, mla_softmax_scale(cfg))

    ctx = ctx.reshape(b, t, h * cfg.v_head_dim).astype(x.dtype)
    out = jnp.einsum("bte,ed->btd", ctx, params["w_o"])
    return logical.shard(out, "batch", "residual_seq", "embed"), cache


def apply(params, cfg: AttentionConfig, x, positions, *, cache=None, layer=None, causal=True,
          eps=1e-5):
    """``eps`` is the model's RMSNorm epsilon (MLA's latent norms use it);
    ``layer`` indexes a stacked ``cache`` (``gqa_apply``)."""
    if cfg.kind == "mla":
        return mla_apply(params, cfg, x, positions, cache=cache, layer=layer, eps=eps)
    return gqa_apply(params, cfg, x, positions, cache=cache, layer=layer, causal=causal)
