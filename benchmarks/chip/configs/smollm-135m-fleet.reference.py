"""Plain reference of SmolLM-135M (a Llama decoder, hf:HuggingFaceTB/SmolLM-135M).

Imports nothing of the program.  Full causal forward pass over a whole
sequence in float32, no cache, one layer after another:

    x = embed[tokens]
    per layer:  h = rmsnorm(x) * ln1;  q, k, v = h Wq, h Wk, h Wv (GQA: each
                key/value head serves num_heads / num_kv_heads query heads)
                rotary embedding on q and k (theta 10000; the two halves of
                each head rotate together, as Llama's rotate_half)
                x += softmax(q k^T / sqrt(head_dim), causal) v  Wo
                h = rmsnorm(x) * ln2;  x += (silu(h Wgate) * (h Wup)) Wdown
    logits = (rmsnorm(x) * final_norm) embed^T       (tied embeddings)

The weights are the benchmark's own (made from the seed in the program's
parameter layout, bfloat16), read here as float32.  ``matmul`` picks how
the weight products are computed: ``"f32"`` (the reference) or ``"fp8"``,
both operands rounded to float8 e4m3 first, or ``"int8"``, weights rounded
to int8 with one scale per output channel (the controls).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST


def _matmul(a, w, mode: str):
    """a (..., n) times w (n, m) in the named arithmetic."""
    if mode == "fp8":
        a = a.astype(jnp.float8_e4m3fn).astype(jnp.float32)
        w = w.astype(jnp.float8_e4m3fn).astype(jnp.float32)
    elif mode == "int8":
        scale = jnp.max(jnp.abs(w), axis=0, keepdims=True) / 127.0
        w = jnp.round(w / jnp.maximum(scale, 1e-30)) * scale
    elif mode != "f32":
        raise ValueError(f"unknown matmul mode {mode!r}")
    return jnp.matmul(a, w, precision=HI)


def _rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _rope(x, theta):
    """x (S, heads, dh); position of row s is s."""
    s, _, dh = x.shape
    freqs = theta ** (-jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None, None] * freqs
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., : dh // 2], x[..., dh // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def make_forward(cfg: dict, mode: str = "f32"):
    """jit(weights of one model (float32), tokens (S,)) -> logits (S, V)."""
    d, nh, nkv = cfg["hidden_size"], cfg["num_attention_heads"], cfg["num_key_value_heads"]
    dh, eps, theta = cfg["head_dim"], cfg["rms_norm_eps"], cfg["rope_theta"]
    group = nh // nkv

    def layer(x, p):
        s = x.shape[0]
        h = _rmsnorm(x, p["ln1"]["scale"], eps)
        a = p["attn"]
        q = _matmul(h, a["w_q"].reshape(d, nh * dh), mode).reshape(s, nh, dh)
        k = _matmul(h, a["w_k"].reshape(d, nkv * dh), mode).reshape(s, nkv, dh)
        v = _matmul(h, a["w_v"].reshape(d, nkv * dh), mode).reshape(s, nkv, dh)
        q, k = _rope(q, theta), _rope(k, theta)
        k = jnp.repeat(k, group, axis=1)
        v = jnp.repeat(v, group, axis=1)
        scores = jnp.einsum("shd,thd->hst", q, k, precision=HI) * dh**-0.5
        causal = jnp.tril(jnp.ones((s, s), bool))
        scores = jnp.where(causal[None], scores, -jnp.inf)
        probs = jax.nn.softmax(scores, axis=-1)
        ctx = jnp.einsum("hst,thd->shd", probs, v, precision=HI).reshape(s, nh * dh)
        x = x + _matmul(ctx, a["w_o"], mode)
        h = _rmsnorm(x, p["ln2"]["scale"], eps)
        m = p["mlp"]
        up = _matmul(h, m["w_up"], mode)
        gate = _matmul(h, m["w_gate"], mode)
        return x + _matmul(jax.nn.silu(gate) * up, m["w_down"], mode), None

    def forward(w, tokens):
        x = w["embed"][tokens]
        x, _ = jax.lax.scan(layer, x, w["layers"])
        x = _rmsnorm(x, w["final_norm"]["scale"], eps)
        return _matmul(x, w["embed"].T, mode)

    return jax.jit(forward)
