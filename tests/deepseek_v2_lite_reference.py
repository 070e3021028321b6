"""Plain float32 reference of DeepSeek-V2-Lite for the CPU tests (a copy of
the benchmark's ``benchmarks/chip/configs/deepseek-v2-lite-fleet.reference.py``
without its control arithmetics; the tests import nothing from the
benchmark).  Imports nothing of the program.

Full causal forward pass over a whole sequence, no cache, no batching, every
product at ``Precision.HIGHEST``:

    x = embed[tokens]
    per layer:  h = rmsnorm(x) * ln1
                q = h Wq  (heads x (nope + rope));  [c, k_rope] = h Wdkv
                c = rmsnorm(c) * kv_norm;  k_nope = c Wuk;  v = c Wuv
                q_rope, k_rope rotated with YaRN's frequencies
                scores = (q_nope k_nope^T + q_rope k_rope^T) * s, causal,
                    s = (nope + rope)^-0.5 * mscale(factor, mscale_all_dim)^2
                x += softmax(scores) v Wo
                h = rmsnorm(x) * ln2
                dense layers: x += (silu(h Wgate) * (h Wup)) Wdown
                expert layers: x += expert_layer(h)
    logits = (rmsnorm(x) * final_norm) Whead         (untied head)

YaRN as DeepSeek-V2's ``DeepseekV2YarnRotaryEmbedding``.  Departure: the
rope part rotates its two contiguous halves together where the published
code interleaves pairs, a fixed permutation of the rope columns of Wq and
Wdkv (the same model on random weights).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST


def mm(a, w):
    return jnp.matmul(a, w, precision=HI)


def rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def yarn_mscale(s: float, m: float) -> float:
    return 1.0 if s <= 1 else 0.1 * m * math.log(s) + 1.0


def yarn_inv_freq(d: int, theta: float, rs: dict):
    """(d/2,) inverse frequencies and the cos/sin factor of YaRN."""
    def n(b):
        return d * math.log(rs["original_max_position_embeddings"] / (2 * math.pi * b)) / (
            2 * math.log(theta))

    low = max(math.floor(n(rs["beta_fast"])), 0)
    high = min(math.ceil(n(rs["beta_slow"])), d - 1)
    extra = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ramp = jnp.clip((jnp.arange(d // 2, dtype=jnp.float32) - low) / max(high - low, 1e-3), 0, 1)
    inv = extra / rs["factor"] * ramp + extra * (1 - ramp)
    factor = (yarn_mscale(rs["factor"], rs["mscale"])
              / yarn_mscale(rs["factor"], rs["mscale_all_dim"]))
    return inv, factor


def softmax_scale(cfg: dict) -> float:
    rs = cfg["rope_scaling"]
    s = (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]) ** -0.5
    return s * yarn_mscale(rs["factor"], rs["mscale_all_dim"]) ** 2


def rope(x, inv, factor):
    """x (S, ..., d); position of row s is s; the two halves rotate together."""
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * inv
    ang = ang.reshape(ang.shape[:1] + (1,) * (x.ndim - 2) + ang.shape[1:])
    cos, sin = jnp.cos(ang) * factor, jnp.sin(ang) * factor
    h = x.shape[-1] // 2
    x1, x2 = x[..., :h], x[..., h:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def swiglu(h, m):
    return mm(jax.nn.silu(mm(h, m["w_gate"])) * mm(h, m["w_up"]), m["w_down"])


def expert_layer(h, m, cfg: dict, *, offset: int = 0, shared: bool = True):
    """h (S, D): a softmax over all the router's outputs, the top
    ``num_experts_per_tok`` probabilities (renormalised only if
    ``norm_topk_prob``) times ``routed_scaling_factor`` weigh the experts
    m holds, which are experts ``offset`` onward; plus the shared experts."""
    probs = jax.nn.softmax(mm(h, m["router"]), axis=-1)
    top_p, top_e = jax.lax.top_k(probs, cfg["num_experts_per_tok"])
    gates = top_p / top_p.sum(-1, keepdims=True) if cfg["norm_topk_prob"] else top_p
    gates = gates * cfg["routed_scaling_factor"]
    out = swiglu(h, m["shared"]) if shared else jnp.zeros_like(h)
    for j in range(m["w_gate"].shape[0]):
        g = jnp.sum(jnp.where(top_e == offset + j, gates, 0.0), axis=-1)
        out = out + g[:, None] * swiglu(h, {n: m[n][j] for n in ("w_gate", "w_up", "w_down")})
    return out


def make_forward(cfg: dict, *, offset: int = 0):
    """jit(weights of one model (float32), tokens (S,)) -> logits (S, V)."""
    nh, lora = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    nope, rd, vd = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    eps = cfg["rms_norm_eps"]
    inv, factor = yarn_inv_freq(rd, cfg["rope_theta"], cfg["rope_scaling"])
    scale = softmax_scale(cfg)

    def attend(x, a):
        s, d = x.shape
        q = mm(x, a["w_q"].reshape(d, -1)).reshape(s, nh, nope + rd)
        dkv = mm(x, a["w_dkv"])
        c = rmsnorm(dkv[:, :lora], a["kv_norm"]["scale"], eps)
        k_nope = mm(c, a["w_uk"].reshape(lora, -1)).reshape(s, nh, nope)
        v = mm(c, a["w_uv"].reshape(lora, -1)).reshape(s, nh, vd)
        q_rope = rope(q[..., nope:], inv, factor)
        k_rope = rope(dkv[:, lora:], inv, factor)
        scores = (jnp.einsum("shn,thn->hst", q[..., :nope], k_nope, precision=HI)
                  + jnp.einsum("shp,tp->hst", q_rope, k_rope, precision=HI)) * scale
        causal = jnp.tril(jnp.ones((s, s), bool))
        probs = jax.nn.softmax(jnp.where(causal[None], scores, -jnp.inf), axis=-1)
        ctx = jnp.einsum("hst,thv->shv", probs, v, precision=HI).reshape(s, nh * vd)
        return mm(ctx, a["w_o"])

    def dense_layer(x, p):
        x = x + attend(rmsnorm(x, p["ln1"]["scale"], eps), p["attn"])
        return x + swiglu(rmsnorm(x, p["ln2"]["scale"], eps), p["mlp"]), None

    def moe_layer(x, p):
        x = x + attend(rmsnorm(x, p["ln1"]["scale"], eps), p["attn"])
        return x + expert_layer(rmsnorm(x, p["ln2"]["scale"], eps), p["moe"], cfg,
                                offset=offset), None

    def forward(w, tokens):
        x = w["embed"][tokens]
        x, _ = jax.lax.scan(dense_layer, x, w["first_layers"])
        x, _ = jax.lax.scan(moe_layer, x, w["layers"])
        return mm(rmsnorm(x, w["final_norm"]["scale"], eps), w["lm_head"])

    return jax.jit(forward)
