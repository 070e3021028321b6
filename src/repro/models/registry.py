"""Build a uniform Model interface from a ModelConfig.

Every family exposes:
    init(rng) -> params
    loss_fn(params, batch) -> scalar            (train step substrate)
    init_cache(batch, seq_len) -> cache         (decode substrate)
    prefill(params, batch, cache) -> (logits, cache)
    decode_step(params, token, pos, cache) -> (logits, cache)
    make_batch(rng, batch, seq) -> batch pytree (synthetic, family-correct)
    batch_specs(batch, seq) -> ShapeDtypeStruct pytree (dry-run stand-ins)
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp

from repro import telemetry
from repro.configs.base import ModelConfig
from repro.models import common, moe
from repro.models import transformer as tf

PyTree = Any


@dataclasses.dataclass(frozen=True)
class Model:
    cfg: ModelConfig
    init: Callable[[jax.Array], PyTree]
    loss_fn: Callable[[PyTree, PyTree], jax.Array]
    init_cache: Callable[[int, int], PyTree]
    prefill: Callable[[PyTree, PyTree, PyTree], tuple]
    decode_step: Callable[[PyTree, jax.Array, jax.Array, PyTree], tuple]
    make_batch: Callable[[jax.Array, int, int], PyTree]
    batch_specs: Callable[[int, int], PyTree]


def _token_batch(rng, cfg, b, s):
    k1, k2 = jax.random.split(rng)
    return {
        "tokens": jax.random.randint(k1, (b, s), 0, cfg.vocab_size, jnp.int32),
        "labels": jax.random.randint(k2, (b, s), 0, cfg.vocab_size, jnp.int32),
    }


def _token_specs(cfg, b, s):
    t = jax.ShapeDtypeStruct((b, s), jnp.int32)
    return {"tokens": t, "labels": t}


def split_vlm_seq(cfg: ModelConfig, s: int) -> tuple[int, int]:
    np_ = min(cfg.num_prefix_embeddings, max(s - 1, 1))
    return np_, s - np_


def split_encdec_seq(s: int) -> tuple[int, int]:
    enc = max(s // 4, 1)
    return enc, max(s - enc, 1)


def build_sequence_classifier(cfg: ModelConfig, num_classes: int):
    """(init, apply, loss) for sequence classification on a registry family.

    ``apply(params, tokens (B, S) int32) -> (B, num_classes) f32 logits``:
    the family's trunk run over the token sequence, the final position's
    hidden state (the RNN summary for recurrent families) through one linear
    head.  ``loss(params, (tokens, labels (B,) int32))`` is mean cross
    entropy — the ``(params, batch) -> scalar`` shape ``core.p2p`` trains.

    Currently rwkv6-only: recurrent families have a natural "state after the
    whole sequence" readout; attention families would need pooling choices
    this signature does not yet take.
    """
    if cfg.family != "rwkv6":
        raise ValueError(
            f"build_sequence_classifier supports family 'rwkv6', got "
            f"{cfg.family!r}"
        )
    dtype = tf.compute_dtype(cfg)

    def init(key: jax.Array) -> PyTree:
        k_trunk, k_head = jax.random.split(key)
        params = tf.rwkv6_init_model(k_trunk, cfg)
        params["cls_head"] = {
            "w": common.dense_init(k_head, cfg.d_model, num_classes, dtype),
            "b": jnp.zeros((num_classes,), dtype),
        }
        return params

    def apply(params: PyTree, tokens: jax.Array) -> jax.Array:
        # RNN mode (chunked=False): token-sequential recurrence — for short
        # classification sequences it beats the chunked scan on CPU time AND
        # peak memory (no (B, heads, chunk, chunk) intermediates)
        h = tf.rwkv6_features(params, cfg, tokens, chunked=False)[:, -1]  # (B, D)
        head = params["cls_head"]
        return (h.astype(jnp.float32) @ head["w"].astype(jnp.float32)
                + head["b"].astype(jnp.float32))

    def loss(params: PyTree, batch) -> jax.Array:
        tokens, labels = batch
        return common.cross_entropy_loss(apply(params, tokens), labels)

    return init, apply, loss


def build_model(cfg: ModelConfig) -> Model:
    """The family's functions for ``cfg``.  An expert model adds the bytes of
    the routed experts it holds, over its layers, to the counter
    ``moe.held_expert_bytes``."""
    fam = cfg.family
    if cfg.moe is not None:
        layers = cfg.num_layers - cfg.moe.first_dense_layers
        telemetry.count("moe.held_expert_bytes", layers * moe.held_expert_bytes(
            cfg.moe, cfg.d_model, tf.compute_dtype(cfg)))

    if fam in ("dense", "moe", "vlm"):
        init = lambda k: tf.decoder_init(k, cfg)
        loss = lambda p, b: tf.decoder_loss_fn(p, cfg, b)
        init_cache = lambda b, s: tf.decoder_init_cache(cfg, b, s)
        prefill = lambda p, batch, c: tf.decoder_prefill(p, cfg, batch, c)
        decode = lambda p, t, pos, c: tf.decoder_decode_step(p, cfg, t, pos, c)

        if fam == "vlm":

            def make_batch(rng, b, s):
                np_, st = split_vlm_seq(cfg, s)
                k1, k2 = jax.random.split(rng)
                out = _token_batch(k1, cfg, b, st)
                out["patches"] = jax.random.normal(k2, (b, np_, cfg.frontend_dim), jnp.float32)
                return out

            def batch_specs(b, s):
                np_, st = split_vlm_seq(cfg, s)
                out = _token_specs(cfg, b, st)
                out["patches"] = jax.ShapeDtypeStruct((b, np_, cfg.frontend_dim), jnp.float32)
                return out

        else:
            make_batch = lambda rng, b, s: _token_batch(rng, cfg, b, s)
            batch_specs = lambda b, s: _token_specs(cfg, b, s)

    elif fam == "rwkv6":
        init = lambda k: tf.rwkv6_init_model(k, cfg)
        loss = lambda p, b: tf.rwkv6_loss_fn(p, cfg, b)
        init_cache = lambda b, s: tf.rwkv6_init_state(cfg, b)
        prefill = lambda p, batch, c: tf.rwkv6_prefill(p, cfg, batch, c)
        decode = lambda p, t, pos, c: tf.rwkv6_decode_step(p, cfg, t, pos, c)
        make_batch = lambda rng, b, s: _token_batch(rng, cfg, b, s)
        batch_specs = lambda b, s: _token_specs(cfg, b, s)

    elif fam == "hybrid":
        init = lambda k: tf.hybrid_init(k, cfg)
        loss = lambda p, b: tf.hybrid_loss_fn(p, cfg, b)
        init_cache = lambda b, s: tf.hybrid_init_cache(cfg, b, s)
        prefill = lambda p, batch, c: tf.hybrid_prefill(p, cfg, batch, c)
        decode = lambda p, t, pos, c: tf.hybrid_decode_step(p, cfg, t, pos, c)
        make_batch = lambda rng, b, s: _token_batch(rng, cfg, b, s)
        batch_specs = lambda b, s: _token_specs(cfg, b, s)

    elif fam == "encdec":
        init = lambda k: tf.encdec_init(k, cfg)
        loss = lambda p, b: tf.encdec_loss_fn(p, cfg, b)

        def init_cache(b, s):
            enc, dec = split_encdec_seq(s)
            return tf.encdec_init_cache(cfg, b, dec, enc)

        prefill = lambda p, batch, c: tf.encdec_prefill(p, cfg, batch, c)
        decode = lambda p, t, pos, c: tf.encdec_decode_step(p, cfg, t, pos, c)

        def make_batch(rng, b, s):
            enc, dec = split_encdec_seq(s)
            k1, k2 = jax.random.split(rng)
            out = _token_batch(k1, cfg, b, dec)
            out["frames"] = jax.random.normal(k2, (b, enc, cfg.frontend_dim), jnp.float32)
            return out

        def batch_specs(b, s):
            enc, dec = split_encdec_seq(s)
            out = _token_specs(cfg, b, dec)
            out["frames"] = jax.ShapeDtypeStruct((b, enc, cfg.frontend_dim), jnp.float32)
            return out

    else:
        raise ValueError(f"unknown family {fam!r}")

    return Model(
        cfg=cfg,
        init=init,
        loss_fn=loss,
        init_cache=init_cache,
        prefill=prefill,
        decode_step=decode,
        make_batch=make_batch,
        batch_specs=batch_specs,
    )
