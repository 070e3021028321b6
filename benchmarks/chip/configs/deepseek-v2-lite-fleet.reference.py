"""Plain reference of DeepSeek-V2-Lite (hf:deepseek-ai/DeepSeek-V2-Lite,
arXiv:2405.04434), this chip's share of it.

Imports nothing of the program.  Full causal forward pass over a whole
sequence in float32, no cache, no batching, one layer after another:

    x = embed[tokens]
    per layer:  h = rmsnorm(x) * ln1
                q = h Wq  (heads x (nope + rope));  [c, k_rope] = h Wdkv
                c = rmsnorm(c) * kv_norm;  k_nope = c Wuk;  v = c Wuv
                q_rope, k_rope rotated with YaRN's frequencies (below)
                scores = (q_nope k_nope^T + q_rope k_rope^T) * s, causal,
                    s = (nope + rope)^-0.5 * mscale(factor, mscale_all_dim)^2
                x += softmax(scores) v Wo
                h = rmsnorm(x) * ln2
                layer 0:  x += (silu(h Wgate) * (h Wup)) Wdown
                after:    p = softmax(h Wrouter) over all routed experts;
                          the 6 largest p_e, not renormalised, times
                          routed_scaling_factor, weigh the held experts
                          among them: x += sum_e g_e expert_e(h) + shared(h)
    logits = (rmsnorm(x) * final_norm) Whead         (untied head)

YaRN (DeepSeek-V2's ``DeepseekV2YarnRotaryEmbedding``): inverse frequency i
of the rope part is theta^(-2i/d) below the ramp, divided by ``factor``
above it, and mixed linearly over the ramp [floor(n(beta_fast)),
ceil(n(beta_slow))], n(b) = d ln(original / (2 pi b)) / (2 ln theta); cos and
sin carry mscale(factor, mscale) / mscale(factor, mscale_all_dim), with
mscale(s, m) = 0.1 m ln s + 1.

This chip's share (the configuration's ``deployment``): the experts with
ids ``expert_offset`` onward, as many as ``n_routed_experts`` says, and the
vocabulary slice the weights hold; the router keeps all its outputs.  What
the experts held elsewhere would add is left out, as in the program.

Departures from the published code: the rope part rotates its two halves
together (rotate_half on contiguous halves), where the published code first
interleaves pairs; that is a fixed permutation of the rope columns of Wq and
Wdkv, so on random weights the two are the same model.  The weights are the
benchmark's own (made from the seed in the program's parameter layout,
bfloat16), read here as float32.  ``matmul`` picks how the weight products
are computed: ``"f32"`` (the reference) or ``"fp8"``, both operands rounded
to float8 e4m3 first, or ``"int8"``, weights rounded to int8 with one scale
per output channel (the controls).
"""
from __future__ import annotations

import math

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


def _rope(x, inv, factor):
    """x (S, ..., d); position of row s is s; the two halves rotate together."""
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * inv
    ang = ang.reshape(ang.shape[:1] + (1,) * (x.ndim - 2) + ang.shape[1:])
    cos, sin = jnp.cos(ang) * factor, jnp.sin(ang) * factor
    h = x.shape[-1] // 2
    x1, x2 = x[..., :h], x[..., h:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def make_forward(cfg: dict, mode: str = "f32", *, with_routes: bool = False):
    """jit(weights of one model (float32), tokens (S,)) -> logits (S, V), and
    with ``with_routes`` also each expert layer's top-k expert ids (L, S, k)."""
    nh, lora = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    nope, rope, vd = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    eps, k = cfg["rms_norm_eps"], cfg["num_experts_per_tok"]
    offset, held = cfg["deployment"]["expert_offset"], cfg["n_routed_experts"]
    inv, factor = yarn_inv_freq(rope, cfg["rope_theta"], cfg["rope_scaling"])
    scale = softmax_scale(cfg)

    def mm(a, w):
        return _matmul(a, w, mode)

    def swiglu(h, m):
        return mm(jax.nn.silu(mm(h, m["w_gate"])) * mm(h, m["w_up"]), m["w_down"])

    def attend(x, a):
        s, d = x.shape
        q = mm(x, a["w_q"].reshape(d, -1)).reshape(s, nh, nope + rope)
        dkv = mm(x, a["w_dkv"])
        c = _rmsnorm(dkv[:, :lora], a["kv_norm"]["scale"], eps)
        k_nope = mm(c, a["w_uk"].reshape(lora, -1)).reshape(s, nh, nope)
        v = mm(c, a["w_uv"].reshape(lora, -1)).reshape(s, nh, vd)
        q_rope = _rope(q[..., nope:], inv, factor)
        k_rope = _rope(dkv[:, lora:], inv, factor)
        scores = (jnp.einsum("shn,thn->hst", q[..., :nope], k_nope, precision=HI)
                  + jnp.einsum("shp,tp->hst", q_rope, k_rope, precision=HI)) * scale
        causal = jnp.tril(jnp.ones((s, s), bool))
        probs = jax.nn.softmax(jnp.where(causal[None], scores, -jnp.inf), axis=-1)
        ctx = jnp.einsum("hst,thv->shv", probs, v, precision=HI).reshape(s, nh * vd)
        return mm(ctx, a["w_o"])

    def experts(h, m):
        probs = jax.nn.softmax(mm(h, m["router"]), axis=-1)  # (S, all experts)
        top_p, top_e = jax.lax.top_k(probs, k)
        gates = top_p / top_p.sum(-1, keepdims=True) if cfg["norm_topk_prob"] else top_p
        gates = gates * cfg["routed_scaling_factor"]
        out = swiglu(h, m["shared"])
        for j in range(held):
            g = jnp.sum(jnp.where(top_e == offset + j, gates, 0.0), axis=-1)  # (S,)
            out = out + g[:, None] * swiglu(h, {n: m[n][j] for n in ("w_gate", "w_up", "w_down")})
        return out, top_e

    def dense_layer(x, p):
        x = x + attend(_rmsnorm(x, p["ln1"]["scale"], eps), p["attn"])
        return x + swiglu(_rmsnorm(x, p["ln2"]["scale"], eps), p["mlp"]), None

    def moe_layer(x, p):
        x = x + attend(_rmsnorm(x, p["ln1"]["scale"], eps), p["attn"])
        out, top_e = experts(_rmsnorm(x, p["ln2"]["scale"], eps), p["moe"])
        return x + out, top_e

    def forward(w, tokens):
        x = w["embed"][tokens]
        x, _ = jax.lax.scan(dense_layer, x, w["first_layers"])
        x, routes = jax.lax.scan(moe_layer, x, w["layers"])
        logits = mm(_rmsnorm(x, w["final_norm"]["scale"], eps), w["lm_head"])
        return (logits, routes) if with_routes else logits

    return jax.jit(forward)
