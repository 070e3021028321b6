"""Model stacks: dense/MoE decoders, RWKV6, Zamba2 hybrid, enc-dec, VLM.

All stacks use *layer-stacked* parameters (leading L axis, built with
vmap(init)) applied under ``lax.scan`` — HLO size is independent of depth,
which is what keeps 94-layer dry-run lowering tractable.  ``cfg.remat``
wraps the scanned block in ``jax.checkpoint``.

Per-family batch/IO contracts (see data/pipeline.py and launch/dryrun.py):
  dense/moe/rwkv6/hybrid : batch = {tokens (B,S), labels (B,S)}
  vlm                    : + patches (B, Np, frontend_dim); text len = S - Np
  encdec                 : frames (B, S_enc, frontend_dim), tokens/labels (B, S_dec)
"""
from __future__ import annotations

import math
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro import telemetry
from repro.configs.base import ModelConfig
from repro.models import attention, common, moe, ssm
from repro.sharding import logical

PyTree = Any


def compute_dtype(cfg: ModelConfig):
    return jnp.dtype(cfg.dtype)


# ===========================================================================
# Decoder block (dense MLP or MoE)
# ===========================================================================


def _block_init(key, cfg: ModelConfig, *, use_moe: bool, dense_ff: Optional[int] = None) -> dict:
    dtype = compute_dtype(cfg)
    k1, k2 = jax.random.split(key)
    p = {
        "ln1": common.rmsnorm_init(cfg.d_model, dtype),
        "attn": attention.init(k1, cfg.d_model, cfg.attention, dtype),
        "ln2": common.rmsnorm_init(cfg.d_model, dtype),
    }
    if use_moe:
        p["moe"] = moe.init(k2, cfg.d_model, cfg.moe, dtype)
    else:
        p["mlp"] = common.mlp_init(k2, cfg.d_model, dense_ff or cfg.d_ff, dtype)
    return p


def _block_apply(p, cfg: ModelConfig, x, positions, cache, layer=None):
    """Returns (x, new_cache, aux).  ``layer`` indexes a stacked ``cache``
    (``attention.gqa_apply``).  With a cache (prefill, decode) the expert
    layer drops no token; without one (training) it runs the capacity
    dispatch that expert-parallel meshes partition."""
    h, cache = attention.apply(
        p["attn"], cfg.attention, common.rmsnorm(p["ln1"], x, cfg.norm_eps), positions,
        cache=cache, layer=layer, eps=cfg.norm_eps,
    )
    x = x + h
    h2 = common.rmsnorm(p["ln2"], x, cfg.norm_eps)
    if "moe" in p:
        dispatch = moe.apply if cache is not None else moe.apply_capacity
        h2, aux = dispatch(p["moe"], cfg.moe, h2, act=cfg.act)
    else:
        h2, aux = common.mlp_apply(p["mlp"], h2, act=cfg.act), jnp.zeros((), jnp.float32)
    return x + h2, cache, aux


def _stacked_init(key, n, init_one):
    return jax.vmap(init_one)(jax.random.split(key, max(n, 1)))


def _scan_blocks(block_fn, x, stacked_params, stacked_cache, remat: bool):
    """scan over layers: ``block_fn(p, x, cache, layer) -> (x, cache, aux)``.

    The stacked cache (every leaf (L, B, C, ...); None in training and in the
    encoder) rides in the carry with the layer index, and each block writes
    only its new positions into it, in place: nothing of the cache leaves the
    scan as ``ys``.  So under the fleet's vmap the group axis stays in front
    of the layer axis, as in the decode scan's carry, and a decode step
    neither transposes the cache nor rewrites a layer's slab."""
    fn = jax.checkpoint(block_fn) if remat else block_fn

    def body(carry, p):
        x, aux, cache, layer = carry
        x, cache, a = fn(p, x, cache, layer)
        return (x, aux + a, cache, layer + 1), None

    carry = (x, jnp.zeros((), jnp.float32), stacked_cache, jnp.zeros((), jnp.int32))
    (x, aux, cache, _), _ = jax.lax.scan(body, carry, stacked_params)
    return x, cache, aux


# ===========================================================================
# Decoder-only model (dense / moe / vlm share this)
# ===========================================================================


def decoder_init(key: jax.Array, cfg: ModelConfig) -> dict:
    dtype = compute_dtype(cfg)
    ks = jax.random.split(key, 6)
    p: dict = {"embed": common.embed_init(ks[0], cfg.vocab_size, cfg.d_model, dtype)}
    n_dense_first = cfg.moe.first_dense_layers if cfg.moe else 0
    n_main = cfg.num_layers - n_dense_first
    if n_dense_first:
        p["first_layers"] = _stacked_init(
            ks[1],
            n_dense_first,
            lambda k: _block_init(k, cfg, use_moe=False, dense_ff=cfg.moe.dense_ff or cfg.d_ff),
        )
    p["layers"] = _stacked_init(
        ks[2], n_main, lambda k: _block_init(k, cfg, use_moe=cfg.moe is not None)
    )
    p["final_norm"] = common.rmsnorm_init(cfg.d_model, dtype)
    if not cfg.tie_embeddings:
        p["lm_head"] = common.dense_init(ks[3], cfg.d_model, cfg.vocab_size, dtype)
    if cfg.family == "vlm":
        p["projector"] = common.dense_init(ks[4], cfg.frontend_dim, cfg.d_model, dtype)
    return p


def _decoder_embed(params, cfg: ModelConfig, tokens, patches=None):
    dtype = compute_dtype(cfg)
    x = common.embed_lookup(params["embed"], tokens, dtype)
    if patches is not None:
        px = jnp.einsum("bpf,fd->bpd", patches.astype(dtype), params["projector"])
        x = jnp.concatenate([px, x], axis=1)  # image patches are a prefix
    return x


def _cache_write_bytes(caches, t: int) -> int:
    """Bytes a pass of ``t`` positions writes into the stacked caches: every
    leaf is (L, B, C, ...), and each of its L x B rows takes t entries."""
    return sum(
        leaf.shape[0] * leaf.shape[1] * t * math.prod(leaf.shape[3:]) * leaf.dtype.itemsize
        for leaf in jax.tree.leaves(caches)
    )


def _decoder_trunk(params, cfg: ModelConfig, x, positions, caches):
    """caches: {"first": ..., "main": ...} stacked, or None (training).  Each
    trace with caches adds the bytes it writes into them to the counter
    ``decode.cache_write_bytes``."""
    if caches is not None:
        telemetry.count("decode.cache_write_bytes", _cache_write_bytes(caches, x.shape[1]))
    aux = jnp.zeros((), jnp.float32)
    new_caches: dict | None = {} if caches is not None else None
    block = lambda p, h, c, layer: _block_apply(p, cfg, h, positions, c, layer)
    for name, layers in (("first", "first_layers"), ("main", "layers")):
        if layers not in params:
            continue
        c = None if caches is None else caches[name]
        x, nc, a = _scan_blocks(block, x, params[layers], c, cfg.remat)
        aux = aux + a
        if new_caches is not None:
            new_caches[name] = nc
    x = common.rmsnorm(params["final_norm"], x, cfg.norm_eps)
    return x, new_caches, aux


def decoder_logits(params, cfg: ModelConfig, x):
    head = params.get("lm_head", params["embed"])
    return common.unembed(head, x, transpose="lm_head" not in params)


def decoder_loss_fn(params, cfg: ModelConfig, batch) -> jax.Array:
    tokens, labels = batch["tokens"], batch["labels"]
    patches = batch.get("patches")
    x = _decoder_embed(params, cfg, tokens, patches)
    b, s, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))
    x = logical.shard(x, "batch", "residual_seq", "embed")
    x, _, aux = _decoder_trunk(params, cfg, x, positions, None)
    if patches is not None:
        x = x[:, patches.shape[1] :]  # loss over text positions only
    logits = decoder_logits(params, cfg, x)
    loss = common.cross_entropy_loss(logits, labels)
    if cfg.moe is not None:
        loss = loss + cfg.moe.router_aux_coef * aux / cfg.num_layers
    return loss


def decoder_init_cache(cfg: ModelConfig, batch: int, max_seq: int) -> dict:
    dtype = compute_dtype(cfg)
    n_first = cfg.moe.first_dense_layers if cfg.moe else 0
    n_main = cfg.num_layers - n_first

    def stack(n):
        return jax.vmap(lambda _i: attention.init_cache(cfg.attention, batch, max_seq, dtype))(
            jnp.arange(n)
        )

    caches = {"main": stack(n_main)}
    if n_first:
        caches["first"] = stack(n_first)
    return caches


def decoder_prefill(params, cfg: ModelConfig, batch, caches):
    tokens = batch["tokens"]
    patches = batch.get("patches")
    x = _decoder_embed(params, cfg, tokens, patches)
    b, s, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))
    x, caches, _ = _decoder_trunk(params, cfg, x, positions, caches)
    logits = decoder_logits(params, cfg, x[:, -1:])
    return logits, caches


def decoder_decode_step(params, cfg: ModelConfig, token, pos, caches):
    """token: (B,) int32; pos: (B,) absolute position of this token."""
    x = _decoder_embed(params, cfg, token[:, None])
    positions = pos[:, None]
    x, caches, _ = _decoder_trunk(params, cfg, x, positions, caches)
    logits = decoder_logits(params, cfg, x)
    return logits, caches


# ===========================================================================
# RWKV6 stack
# ===========================================================================


def rwkv6_init_model(key: jax.Array, cfg: ModelConfig) -> dict:
    dtype = compute_dtype(cfg)
    ks = jax.random.split(key, 4)
    p = {
        "embed": common.embed_init(ks[0], cfg.vocab_size, cfg.d_model, dtype),
        "ln0": common.layernorm_init(cfg.d_model, dtype),
        "layers": _stacked_init(
            ks[1],
            cfg.num_layers,
            lambda k: ssm.rwkv6_init(k, cfg.d_model, cfg.d_ff, cfg.ssm, dtype),
        ),
        "final_norm": common.layernorm_init(cfg.d_model, dtype),
    }
    if not cfg.tie_embeddings:
        p["lm_head"] = common.dense_init(ks[2], cfg.d_model, cfg.vocab_size, dtype)
    return p


def rwkv6_init_state(cfg: ModelConfig, batch: int) -> dict:
    dtype = compute_dtype(cfg)
    one = lambda _i: ssm.rwkv6_state(cfg.d_model, cfg.ssm, batch, dtype)
    return jax.vmap(one)(jnp.arange(cfg.num_layers))


def _rwkv6_trunk(params, cfg: ModelConfig, x, states, *, chunked: bool):
    block = lambda p, h, s: ssm.rwkv6_block_apply(p, cfg.ssm, h, s, chunked=chunked)
    fn = jax.checkpoint(block) if cfg.remat else block

    def body(h, layer):
        p, s = layer
        h, s2 = fn(p, h, s)
        return h, s2

    x, new_states = jax.lax.scan(body, x, (params["layers"], states))
    return common.layernorm(params["final_norm"], x, cfg.norm_eps), new_states


def rwkv6_loss_fn(params, cfg: ModelConfig, batch) -> jax.Array:
    tokens, labels = batch["tokens"], batch["labels"]
    dtype = compute_dtype(cfg)
    x = common.embed_lookup(params["embed"], tokens, dtype)
    x = common.layernorm(params["ln0"], x, cfg.norm_eps)
    states = rwkv6_init_state(cfg, tokens.shape[0])
    x, _ = _rwkv6_trunk(params, cfg, x, states, chunked=True)
    logits = decoder_logits(params, cfg, x)
    return common.cross_entropy_loss(logits, labels)


def rwkv6_features(params, cfg: ModelConfig, tokens, *, chunked: bool = True) -> jax.Array:
    """Trunk hidden states (B, S, D) for sequence-level heads (no unembed).

    The full-sequence forward of ``rwkv6_loss_fn`` stopped before the logits:
    embed, ln0, the layer scan from a zero recurrent state, final norm.  Used
    by ``models.registry.build_sequence_classifier`` (e.g. the P2P
    ``rwkv6_seqmnist`` task reads position -1 as the RNN's summary state).

    ``chunked=False`` runs the token-sequential RNN recurrence instead of the
    chunked parallel scan: same math, but O(B * D) live state instead of the
    chunked form's O(B * heads * chunk^2) attention-shaped intermediates — on
    CPU, for short-sequence classification, it is both smaller and faster.
    """
    x = common.embed_lookup(params["embed"], tokens, compute_dtype(cfg))
    x = common.layernorm(params["ln0"], x, cfg.norm_eps)
    states = rwkv6_init_state(cfg, tokens.shape[0])
    x, _ = _rwkv6_trunk(params, cfg, x, states, chunked=chunked)
    return x


def rwkv6_prefill(params, cfg: ModelConfig, batch, states):
    tokens = batch["tokens"]
    x = common.embed_lookup(params["embed"], tokens, compute_dtype(cfg))
    x = common.layernorm(params["ln0"], x, cfg.norm_eps)
    x, states = _rwkv6_trunk(params, cfg, x, states, chunked=True)
    return decoder_logits(params, cfg, x[:, -1:]), states


def rwkv6_decode_step(params, cfg: ModelConfig, token, pos, states):
    del pos  # recurrent: position-free
    x = common.embed_lookup(params["embed"], token[:, None], compute_dtype(cfg))
    x = common.layernorm(params["ln0"], x, cfg.norm_eps)
    x, states = _rwkv6_trunk(params, cfg, x, states, chunked=False)
    return decoder_logits(params, cfg, x), states


# ===========================================================================
# Zamba2-style hybrid: Mamba2 backbone + weight-shared attention block
# ===========================================================================


def hybrid_init(key: jax.Array, cfg: ModelConfig) -> dict:
    dtype = compute_dtype(cfg)
    ks = jax.random.split(key, 6)

    def mamba_layer(k):
        return {
            "ln": common.rmsnorm_init(cfg.d_model, dtype),
            "mamba": ssm.mamba2_init(k, cfg.d_model, cfg.ssm, dtype),
        }

    p = {
        "embed": common.embed_init(ks[0], cfg.vocab_size, cfg.d_model, dtype),
        "layers": _stacked_init(ks[1], cfg.num_layers, mamba_layer),
        "final_norm": common.rmsnorm_init(cfg.d_model, dtype),
    }
    if cfg.shared_block_period:
        kc, kb = jax.random.split(ks[2])
        p["shared_proj"] = common.dense_init(kc, 2 * cfg.d_model, cfg.d_model, dtype)
        p["shared_block"] = _block_init(kb, cfg, use_moe=False)
    if not cfg.tie_embeddings:
        p["lm_head"] = common.dense_init(ks[3], cfg.d_model, cfg.vocab_size, dtype)
    return p


def hybrid_num_shared_applications(cfg: ModelConfig) -> int:
    return cfg.num_layers // cfg.shared_block_period if cfg.shared_block_period else 0


def hybrid_init_cache(cfg: ModelConfig, batch: int, max_seq: int) -> dict:
    dtype = compute_dtype(cfg)
    n_apps = hybrid_num_shared_applications(cfg)
    cache = {
        "mamba": jax.vmap(lambda _i: ssm.mamba2_state(cfg.d_model, cfg.ssm, batch, dtype))(
            jnp.arange(cfg.num_layers)
        ),
        "attn": jax.vmap(lambda _i: attention.init_cache(cfg.attention, batch, max_seq, dtype))(
            jnp.arange(max(n_apps, 1))
        ),
    }
    return cache


def _hybrid_trunk(params, cfg: ModelConfig, x, positions, cache, *, chunked: bool):
    period = cfg.shared_block_period
    n_apps = hybrid_num_shared_applications(cfg)
    x0 = x  # original embedding, concatenated into every shared-block input
    mamba_fn = ssm.mamba2_apply_chunked if chunked else ssm.mamba2_apply_scan

    def mamba_block(p, h, s):
        o, s2 = mamba_fn(p["mamba"], cfg.ssm, common.rmsnorm(p["ln"], h, cfg.norm_eps), s)
        return h + o, s2

    mamba_block = jax.checkpoint(mamba_block) if cfg.remat else mamba_block

    def shared_apply(h, attn_cache):
        inp = jnp.einsum("bsd,dp->bsp", jnp.concatenate([h, x0], axis=-1), params["shared_proj"])
        out, attn_cache, _ = _block_apply(params["shared_block"], cfg, inp, positions, attn_cache)
        return h + out, attn_cache

    # group the stacked mamba layers: (n_apps|1 groups, period, ...)
    groups = n_apps if period else 1
    per = cfg.num_layers // groups
    grouped = jax.tree.map(lambda t: t.reshape((groups, per) + t.shape[1:]), params["layers"])
    grouped_state = jax.tree.map(
        lambda t: t.reshape((groups, per) + t.shape[1:]), cache["mamba"]
    )

    def group_body(carry, layer):
        h, _ = carry
        gp, gs, attn_cache = layer

        def inner(h2, lp_ls):
            lp, ls = lp_ls
            h2, s2 = mamba_block(lp, h2, ls)
            return h2, s2

        h, new_s = jax.lax.scan(inner, h, (gp, gs))
        if period:
            h, attn_cache = shared_apply(h, attn_cache)
        return (h, jnp.zeros((), jnp.float32)), (new_s, attn_cache)

    (x, _), (new_mamba, new_attn) = jax.lax.scan(
        group_body, (x, jnp.zeros((), jnp.float32)), (grouped, grouped_state, cache["attn"])
    )
    new_mamba = jax.tree.map(lambda t: t.reshape((cfg.num_layers,) + t.shape[2:]), new_mamba)
    x = common.rmsnorm(params["final_norm"], x, cfg.norm_eps)
    return x, {"mamba": new_mamba, "attn": new_attn}


def hybrid_loss_fn(params, cfg: ModelConfig, batch) -> jax.Array:
    tokens, labels = batch["tokens"], batch["labels"]
    b, s = tokens.shape
    dtype = compute_dtype(cfg)
    x = common.embed_lookup(params["embed"], tokens, dtype)
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))
    states = jax.vmap(lambda _i: ssm.mamba2_state(cfg.d_model, cfg.ssm, b, dtype))(
        jnp.arange(cfg.num_layers)
    )
    x, _ = _hybrid_trunk_nocache(params, cfg, x, positions, states)
    logits = decoder_logits(params, cfg, x)
    return common.cross_entropy_loss(logits, labels)


def _hybrid_trunk_nocache(params, cfg: ModelConfig, x, positions, mamba_states):
    """Training/prefill-without-cache variant (attention cache = None)."""
    period = cfg.shared_block_period
    n_apps = hybrid_num_shared_applications(cfg)
    x0 = x

    def mamba_block(p, h, s):
        o, s2 = ssm.mamba2_apply_chunked(
            p["mamba"], cfg.ssm, common.rmsnorm(p["ln"], h, cfg.norm_eps), s
        )
        return h + o, s2

    mamba_block = jax.checkpoint(mamba_block) if cfg.remat else mamba_block

    groups = n_apps if period else 1
    per = cfg.num_layers // groups
    grouped = jax.tree.map(lambda t: t.reshape((groups, per) + t.shape[1:]), params["layers"])
    grouped_state = jax.tree.map(
        lambda t: t.reshape((groups, per) + t.shape[1:]), mamba_states
    )

    def group_body(h, layer):
        gp, gs = layer

        def inner(h2, lp_ls):
            lp, ls = lp_ls
            h2, s2 = mamba_block(lp, h2, ls)
            return h2, s2

        h, new_s = jax.lax.scan(inner, h, (gp, gs))
        if period:
            inp = jnp.einsum(
                "bsd,dp->bsp", jnp.concatenate([h, x0], axis=-1), params["shared_proj"]
            )
            out, _, _ = _block_apply(params["shared_block"], cfg, inp, positions, None)
            h = h + out
        return h, new_s

    x, new_states = jax.lax.scan(group_body, x, (grouped, grouped_state))
    new_states = jax.tree.map(lambda t: t.reshape((cfg.num_layers,) + t.shape[2:]), new_states)
    x = common.rmsnorm(params["final_norm"], x, cfg.norm_eps)
    return x, new_states


def hybrid_prefill(params, cfg: ModelConfig, batch, cache):
    tokens = batch["tokens"]
    b, s = tokens.shape
    x = common.embed_lookup(params["embed"], tokens, compute_dtype(cfg))
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))
    x, cache = _hybrid_trunk(params, cfg, x, positions, cache, chunked=True)
    return decoder_logits(params, cfg, x[:, -1:]), cache


def hybrid_decode_step(params, cfg: ModelConfig, token, pos, cache):
    x = common.embed_lookup(params["embed"], token[:, None], compute_dtype(cfg))
    x, cache = _hybrid_trunk(params, cfg, x, pos[:, None], cache, chunked=False)
    return decoder_logits(params, cfg, x), cache


# ===========================================================================
# Encoder-decoder (seamless-m4t backbone; audio frontend stubbed)
# ===========================================================================


def encdec_init(key: jax.Array, cfg: ModelConfig) -> dict:
    dtype = compute_dtype(cfg)
    ks = jax.random.split(key, 8)

    def enc_layer(k):
        return _block_init(k, cfg, use_moe=False)

    def dec_layer(k):
        k1, k2 = jax.random.split(k)
        p = _block_init(k1, cfg, use_moe=False)
        p["ln_cross"] = common.rmsnorm_init(cfg.d_model, dtype)
        p["cross"] = attention.init(k2, cfg.d_model, cfg.attention, dtype)
        return p

    return {
        "frontend_proj": common.dense_init(ks[0], cfg.frontend_dim, cfg.d_model, dtype),
        "enc_layers": _stacked_init(ks[1], cfg.encoder_layers, enc_layer),
        "enc_norm": common.rmsnorm_init(cfg.d_model, dtype),
        "embed": common.embed_init(ks[2], cfg.vocab_size, cfg.d_model, dtype),
        "dec_layers": _stacked_init(ks[3], cfg.num_layers, dec_layer),
        "final_norm": common.rmsnorm_init(cfg.d_model, dtype),
    }


def encdec_encode(params, cfg: ModelConfig, frames):
    dtype = compute_dtype(cfg)
    x = jnp.einsum("bsf,fd->bsd", frames.astype(dtype), params["frontend_proj"])
    b, s, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))

    def block(p, h, cache, layer):
        del cache, layer
        h2, _ = attention.apply(
            p["attn"], cfg.attention, common.rmsnorm(p["ln1"], h, cfg.norm_eps), positions,
            causal=False,
        )
        h = h + h2
        h = h + common.mlp_apply(p["mlp"], common.rmsnorm(p["ln2"], h, cfg.norm_eps), act=cfg.act)
        return h, None, jnp.zeros((), jnp.float32)

    x, _, _ = _scan_blocks(block, x, params["enc_layers"], None, cfg.remat)
    return common.rmsnorm(params["enc_norm"], x, cfg.norm_eps)


def _encdec_dec_block(p, cfg: ModelConfig, x, positions, cache, enc_kv):
    h, cache = attention.apply(
        p["attn"], cfg.attention, common.rmsnorm(p["ln1"], x, cfg.norm_eps), positions, cache=cache
    )
    x = x + h
    h = attention.cross_attention_apply(
        p["cross"], cfg.attention, common.rmsnorm(p["ln_cross"], x, cfg.norm_eps), enc_kv
    )
    x = x + h
    x = x + common.mlp_apply(p["mlp"], common.rmsnorm(p["ln2"], x, cfg.norm_eps), act=cfg.act)
    return x, cache


def encdec_cross_kv(params, cfg: ModelConfig, enc_out):
    """Precompute per-decoder-layer cross-attention K/V from encoder output."""

    def one(p):
        return attention.encoder_kv(p["cross"], cfg.attention, enc_out)

    return jax.vmap(one, in_axes=(0,))(params["dec_layers"])


def _encdec_dec_trunk(params, cfg: ModelConfig, x, positions, caches, cross_kv):
    def body(h, layer):
        p, c, kv = layer  # c is None without caches (training)
        return _encdec_dec_block(p, cfg, h, positions, c, kv)

    body_fn = jax.checkpoint(body) if cfg.remat else body
    x, new_caches = jax.lax.scan(body_fn, x, (params["dec_layers"], caches, cross_kv))
    return common.rmsnorm(params["final_norm"], x, cfg.norm_eps), new_caches


def encdec_loss_fn(params, cfg: ModelConfig, batch) -> jax.Array:
    frames, tokens, labels = batch["frames"], batch["tokens"], batch["labels"]
    enc_out = encdec_encode(params, cfg, frames)
    cross_kv = encdec_cross_kv(params, cfg, enc_out)
    x = common.embed_lookup(params["embed"], tokens, compute_dtype(cfg))
    b, s, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))
    x, _ = _encdec_dec_trunk(params, cfg, x, positions, None, cross_kv)
    logits = decoder_logits(params, cfg, x)
    return common.cross_entropy_loss(logits, labels)


def encdec_init_cache(cfg: ModelConfig, batch: int, max_seq: int, enc_len: int) -> dict:
    dtype = compute_dtype(cfg)
    a = cfg.attention
    self_caches = jax.vmap(lambda _i: attention.init_cache(a, batch, max_seq, dtype))(
        jnp.arange(cfg.num_layers)
    )
    kv_shape = (cfg.num_layers, batch, enc_len, a.num_kv_heads, a.head_dim)
    return {
        "self": self_caches,
        "cross_k": jnp.zeros(kv_shape, dtype),
        "cross_v": jnp.zeros(kv_shape, dtype),
    }


def encdec_prefill(params, cfg: ModelConfig, batch, caches):
    """Encode audio + run the decoder prompt; fills self- and cross-caches."""
    enc_out = encdec_encode(params, cfg, batch["frames"])
    cross_k, cross_v = encdec_cross_kv(params, cfg, enc_out)
    tokens = batch["tokens"]
    x = common.embed_lookup(params["embed"], tokens, compute_dtype(cfg))
    b, s, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))
    x, self_c = _encdec_dec_trunk(params, cfg, x, positions, caches["self"], (cross_k, cross_v))
    logits = decoder_logits(params, cfg, x[:, -1:])
    return logits, {"self": self_c, "cross_k": cross_k, "cross_v": cross_v}


def encdec_decode_step(params, cfg: ModelConfig, token, pos, caches):
    x = common.embed_lookup(params["embed"], token[:, None], compute_dtype(cfg))
    positions = pos[:, None]
    x, self_c = _encdec_dec_trunk(
        params, cfg, x, positions, caches["self"], (caches["cross_k"], caches["cross_v"])
    )
    logits = decoder_logits(params, cfg, x)
    return logits, {"self": self_c, "cross_k": caches["cross_k"], "cross_v": caches["cross_v"]}
