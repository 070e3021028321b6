"""Shared layer primitives: norms, projections, RoPE, activations, inits.

Functional style: every layer is an ``init(key, ...) -> params`` plus an
``apply(params, x, ...) -> y`` pair operating on plain dict pytrees.  Compute
dtype is configurable (bf16 on TPU, f32 on CPU smoke); norm/softmax accumulate
in f32 throughout.
"""
from __future__ import annotations

import math
from typing import Sequence

import jax
import jax.numpy as jnp

from repro.sharding import logical


def truncated_normal_init(key, shape, scale: float, dtype) -> jax.Array:
    return (jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32) * scale).astype(dtype)


def dense_init(
    key, in_dim: int, out_dims: Sequence[int] | int, dtype, *, scale: float | None = None
):
    """Fan-in scaled init for a dense kernel (in_dim, *out_dims)."""
    out_dims = (out_dims,) if isinstance(out_dims, int) else tuple(out_dims)
    scale = scale if scale is not None else in_dim**-0.5
    return truncated_normal_init(key, (in_dim, *out_dims), scale, dtype)


def embed_init(key, vocab: int, dim: int, dtype):
    # dim**-0.5 keeps tied-unembedding logits O(1) at init.
    return truncated_normal_init(key, (vocab, dim), dim**-0.5, dtype)


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------


def rmsnorm_init(dim: int, dtype) -> dict:
    return {"scale": jnp.ones((dim,), dtype)}


def rmsnorm(params: dict, x: jax.Array, eps: float = 1e-5) -> jax.Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    return (y * params["scale"].astype(jnp.float32)).astype(x.dtype)


def layernorm_init(dim: int, dtype) -> dict:
    return {"scale": jnp.ones((dim,), dtype), "bias": jnp.zeros((dim,), dtype)}


def layernorm(params: dict, x: jax.Array, eps: float = 1e-5) -> jax.Array:
    xf = x.astype(jnp.float32)
    mean = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    y = (xf - mean) * jax.lax.rsqrt(var + eps)
    y = y * params["scale"].astype(jnp.float32) + params["bias"].astype(jnp.float32)
    return y.astype(x.dtype)


# ---------------------------------------------------------------------------
# Rotary position embeddings
# ---------------------------------------------------------------------------


def yarn_mscale(factor: float, mscale: float = 1.0) -> float:
    """YaRN's attention temperature term: 0.1 * mscale * ln(factor) + 1."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_ramp_range(scaling, head_dim: int, theta: float) -> tuple[int, int]:
    """The frequency indices [low, high] over which YaRN's ramp runs: the
    indices that turn ``beta_fast`` and ``beta_slow`` times over the
    original context, floored and ceiled, clipped to [0, head_dim - 1]."""

    def index(rotations):
        turns = scaling.original_max_position / (rotations * 2 * math.pi)
        return head_dim * math.log(turns) / (2 * math.log(theta))

    low = math.floor(index(scaling.beta_fast))
    high = math.ceil(index(scaling.beta_slow))
    return max(low, 0), min(high, head_dim - 1)


def rope_frequencies(head_dim: int, theta: float = 10000.0, scaling=None) -> jax.Array:
    """(D/2,) inverse frequencies; YaRN-interpolated where ``scaling`` (a
    ``configs.base.RopeScaling``) is given."""
    freqs = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))
    if scaling is None:
        return freqs
    low, high = yarn_ramp_range(scaling, head_dim, theta)
    idx = jnp.arange(head_dim // 2, dtype=jnp.float32)
    ramp = jnp.clip((idx - low) / max(high - low, 1e-3), 0.0, 1.0)
    return freqs / scaling.factor * ramp + freqs * (1.0 - ramp)


def apply_rope(
    x: jax.Array, positions: jax.Array, theta: float = 10000.0, scaling=None
) -> jax.Array:
    """x: (..., S, H, D) or (..., S, D); positions: (..., S) int32.  The two
    halves of the last axis rotate together (rotate_half)."""
    d = x.shape[-1]
    freqs = rope_frequencies(d, theta, scaling)  # (D/2,)
    angles = positions.astype(jnp.float32)[..., None] * freqs  # (..., S, D/2)
    if x.ndim == angles.ndim + 1:  # head axis present
        angles = angles[..., None, :]
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    if scaling is not None:
        m = yarn_mscale(scaling.factor, scaling.mscale) / yarn_mscale(
            scaling.factor, scaling.mscale_all_dim
        )
        if m != 1.0:
            cos, sin = cos * m, sin * m
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Activations / MLP
# ---------------------------------------------------------------------------


def act_fn(name: str):
    return {
        "silu": jax.nn.silu,
        "gelu": jax.nn.gelu,
        "relu": jax.nn.relu,
        "relu2": lambda x: jnp.square(jax.nn.relu(x)),
    }[name]


def mlp_init(key, d_model: int, d_ff: int, dtype, *, gated: bool = True) -> dict:
    k1, k2, k3 = jax.random.split(key, 3)
    p = {
        "w_up": dense_init(k1, d_model, d_ff, dtype),
        "w_down": dense_init(k2, d_ff, d_model, dtype),
    }
    if gated:
        p["w_gate"] = dense_init(k3, d_model, d_ff, dtype)
    return p


def mlp_apply(params: dict, x: jax.Array, *, act: str = "silu") -> jax.Array:
    """SwiGLU when w_gate present; plain act-MLP otherwise. x: (B, S, D)."""
    up = jnp.einsum("bsd,df->bsf", x, params["w_up"])
    up = logical.shard(up, "batch", "seq", "mlp")
    if "w_gate" in params:
        gate = jnp.einsum("bsd,df->bsf", x, params["w_gate"])
        h = act_fn(act)(gate.astype(jnp.float32)).astype(x.dtype) * up
    else:
        h = act_fn(act)(up.astype(jnp.float32)).astype(x.dtype)
    out = jnp.einsum("bsf,fd->bsd", h, params["w_down"])
    # residual-stream boundary: under sequence parallelism this reshards the
    # seq dim over `model` (XLA inserts reduce-scatter here instead of a
    # full all-reduce)
    return logical.shard(out, "batch", "residual_seq", "embed")


# ---------------------------------------------------------------------------
# Embedding / unembedding
# ---------------------------------------------------------------------------


def embed_lookup(table: jax.Array, tokens: jax.Array, compute_dtype) -> jax.Array:
    out = jnp.take(table, tokens, axis=0).astype(compute_dtype)
    return logical.shard(out, "batch", "residual_seq", "embed")


def unembed(table_or_head: jax.Array, x: jax.Array, *, transpose: bool) -> jax.Array:
    """Logits in f32. transpose=True when sharing the embedding table (V, D)."""
    if transpose:
        logits = jnp.einsum("bsd,vd->bsv", x.astype(jnp.float32), table_or_head.astype(jnp.float32))
    else:
        logits = jnp.einsum("bsd,dv->bsv", x.astype(jnp.float32), table_or_head.astype(jnp.float32))
    return logical.shard(logits, "batch", "seq", "vocab")


def cross_entropy_loss(logits: jax.Array, labels: jax.Array, *, ignore_id: int = -100) -> jax.Array:
    """Mean token cross entropy, f32, with ignore mask."""
    logits = logits.astype(jnp.float32)
    mask = (labels != ignore_id).astype(jnp.float32)
    safe = jnp.where(labels == ignore_id, 0, labels)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, safe[..., None], axis=-1)[..., 0]
    nll = (logz - gold) * mask
    return jnp.sum(nll) / jnp.maximum(jnp.sum(mask), 1.0)
