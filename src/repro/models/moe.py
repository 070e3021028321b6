"""Mixture-of-Experts: a dropless dispatch for serving, a grouped-capacity
dispatch for training, both over this device's share of the experts.

Routing (``route``): a softmax over all ``num_experts`` router outputs in
f32, greedy top-k, the top-k gates renormalised only where
``norm_topk_prob`` says so, then multiplied by ``routed_scaling_factor``.
The device holds experts [expert_offset, expert_offset + held) (expert
parallelism, ``MoEConfig.experts_held``) and computes only their part of the
result; assignments to experts held elsewhere contribute nothing here.  On
one device the layer runs without an exchange.  Shared experts run on every
token.

``apply`` (prefill and decode) drops no token at any load.  Above
``DENSE_DISPATCH_MAX_TOKENS`` tokens the (token, choice) assignments are
sorted by held expert and each expert's rows go through one grouped product
(``jax.lax.ragged_dot``); assignments to experts held elsewhere sort last and
fall outside every group.  The TPU's grouped product takes no batch axis, so
the fleet runs an expert model's request groups one after another
(``launch/serve.py``).  At or below it (decode, short prompts) every held
expert runs on every token, weighted by the token's gate for it, zero where the token did
not pick it.

``apply_capacity`` (training, ``decoder_loss_fn``): within each of G routing
groups an assignment takes slot (expert, running count) up to a capacity C,
and overflow is dropped (GShard/Switch).  Expert compute is three grouped
einsums over (G, E, C, D) whose expert axis shards over the mesh ``model``
axis, which the dry run's expert-parallel training lowers; the combine
scatter-add leaves a partial sum per model shard that XLA all-reduces.

FLOPs are O(N * top_k * D * F) over the held experts in the grouped
product, O(N * held * D * F) at or below ``DENSE_DISPATCH_MAX_TOKENS``, plus
capacity padding in training.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro import telemetry
from repro.configs.base import MoEConfig
from repro.models import common
from repro.sharding import logical


def init(key: jax.Array, d_model: int, cfg: MoEConfig, dtype) -> dict:
    """Router over all ``num_experts``; expert kernels for the held ones."""
    kr, kg, ku, kd, ks = jax.random.split(key, 5)
    e, f = cfg.held, cfg.expert_ff
    p = {
        "router": common.dense_init(kr, d_model, cfg.num_experts, jnp.float32),
        "w_gate": common.truncated_normal_init(kg, (e, d_model, f), d_model**-0.5, dtype),
        "w_up": common.truncated_normal_init(ku, (e, d_model, f), d_model**-0.5, dtype),
        "w_down": common.truncated_normal_init(kd, (e, f, d_model), f**-0.5, dtype),
    }
    if cfg.num_shared:
        p["shared"] = common.mlp_init(ks, d_model, cfg.num_shared * f, dtype, gated=True)
    return p


def held_expert_bytes(cfg: MoEConfig, d_model: int, dtype) -> int:
    """Bytes of one layer's held routed-expert kernels (gate, up, down)."""
    return 3 * cfg.held * d_model * cfg.expert_ff * jnp.dtype(dtype).itemsize


def route(params: dict, cfg: MoEConfig, x: jax.Array):
    """x (..., D) -> (probs (..., E) f32, gates (..., k) f32, experts (..., k))."""
    logits = jnp.einsum("...d,de->...e", x.astype(jnp.float32), params["router"])
    probs = jax.nn.softmax(logits, axis=-1)
    gates, experts = jax.lax.top_k(probs, cfg.top_k)
    if cfg.norm_topk_prob:
        gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    if cfg.routed_scaling_factor != 1.0:
        gates = gates * cfg.routed_scaling_factor
    return probs, gates, experts


def held_index(cfg: MoEConfig, experts: jax.Array) -> jax.Array:
    """Each assignment's index among the held experts; ``cfg.held`` where the
    expert is held elsewhere."""
    local = experts - cfg.expert_offset
    return jnp.where((local >= 0) & (local < cfg.held), local, cfg.held)


def _aux_loss(probs: jax.Array, experts: jax.Array, cfg: MoEConfig) -> jax.Array:
    """Switch Transformer load-balance loss E * sum_e f_e P_e / k over the
    token axis before the last (all experts, before any capacity)."""
    e, k = cfg.num_experts, cfg.top_k
    onehot = jax.nn.one_hot(experts, e, dtype=jnp.float32)  # (..., N, K, E)
    f_e = onehot.sum(axis=(-3, -2)) / experts.shape[-2]
    p_e = probs.mean(axis=-2)
    return e * jnp.mean(jnp.sum(f_e / k * p_e, axis=-1))


# ---------------------------------------------------------------------------
# Dropless dispatch (serving)
# ---------------------------------------------------------------------------


# At or below this many tokens a forward pass runs every held expert on every
# token.  An expert's product over n tokens does n FLOPs per byte of its bf16
# weights, and a v5e does about 240 bf16 FLOPs in the time it reads a byte
# (197 TFLOP/s, 819 GB/s), so up to 240 tokens the dense pass takes no longer
# than reading the held experts, which the grouped product pays as well once
# most are hit; on the TPU the grouped product, a custom call, also takes each
# layer's experts copied out of the layer stack first (27 % of a
# DeepSeek-V2-Lite fleet call's device time on a v5e).  In an expert-parallel
# deployment every chip's requests reach a model's held experts: on 8 chips
# serving 8 groups of 8 requests each, 64 tokens a decode step on average and
# 188 for the most popular of 8 models under a power law of exponent 1.
DENSE_DISPATCH_MAX_TOKENS = 240


def _held_experts_dense(act, x, local, gates, w_gate, w_up, w_down):
    """(x (N, D), local (N, K), gates (N, K) f32, w_gate, w_up, w_down) ->
    (N, D) f32: the held experts' gated sum per token, every held expert run
    on every token (``local`` is ``held`` for an expert held elsewhere)."""
    weights = jnp.einsum("nk,nke->ne", gates, jax.nn.one_hot(local, w_gate.shape[0]))
    h_gate = jnp.einsum("nd,edf->enf", x, w_gate)
    h_up = jnp.einsum("nd,edf->enf", x, w_up)
    h = common.act_fn(act)(h_gate.astype(jnp.float32)).astype(x.dtype) * h_up
    y = jnp.einsum("enf,efd->end", h, w_down)
    return jnp.einsum("end,ne->nd", y.astype(jnp.float32), weights)


def _held_experts_grouped(act, x, local, gates, w_gate, w_up, w_down):
    """The same sum, each held expert's rows through one grouped product."""
    n, k = local.shape
    e = w_gate.shape[0]
    flat = local.reshape(n * k)
    order = jnp.argsort(flat, stable=True)  # held experts' rows first, by expert
    sizes = jnp.sum(jax.nn.one_hot(flat, e, dtype=jnp.int32), axis=0)
    xs = jnp.take(x, order // k, axis=0)
    h_gate = jax.lax.ragged_dot(xs, w_gate, sizes)
    h_up = jax.lax.ragged_dot(xs, w_up, sizes)
    h = common.act_fn(act)(h_gate.astype(jnp.float32)).astype(x.dtype) * h_up
    ys = jax.lax.ragged_dot(h, w_down, sizes)  # rows past the held ones: none
    y = jnp.take(ys, jnp.argsort(order), axis=0).reshape(n, k, -1)
    y = jnp.where((local < e)[..., None], y.astype(jnp.float32), 0.0)
    return jnp.einsum("nk,nkd->nd", gates, y)


@telemetry.scoped("repro.moe")
def apply(
    params: dict, cfg: MoEConfig, x: jax.Array, *, act: str = "silu"
) -> tuple[jax.Array, jax.Array]:
    """Dropless: x (B, S, D) -> (out (B, S, D), load-balance aux loss f32)."""
    b, s, d = x.shape
    xf = x.reshape(b * s, d)
    probs, gates, experts = route(params, cfg, xf)
    local = held_index(cfg, experts)
    kernels = (params["w_gate"], params["w_up"], params["w_down"])
    if b * s <= DENSE_DISPATCH_MAX_TOKENS:
        routed = _held_experts_dense(act, xf, local, gates, *kernels)
    else:
        routed = _held_experts_grouped(act, xf, local, gates, *kernels)
    out = routed.astype(x.dtype).reshape(b, s, d)
    if "shared" in params:
        out = out + common.mlp_apply(params["shared"], x, act=act)
    return out, _aux_loss(probs, experts, cfg)


# ---------------------------------------------------------------------------
# Grouped-capacity dispatch (training)
# ---------------------------------------------------------------------------


def _num_groups(cfg: MoEConfig, n_tokens: int) -> int:
    g = max(1, cfg.router_groups)
    return math.gcd(g, n_tokens)


def capacity(cfg: MoEConfig, tokens_per_group: int) -> int:
    c = math.ceil(tokens_per_group * cfg.top_k * cfg.capacity_factor / cfg.num_experts)
    return max(8, -(-c // 8) * 8)  # round up to a multiple of 8


@telemetry.scoped("repro.moe")
def apply_capacity(
    params: dict, cfg: MoEConfig, x: jax.Array, *, act: str = "silu"
) -> tuple[jax.Array, jax.Array]:
    """x: (B, S, D). Returns (out (B,S,D), load-balance aux loss scalar f32)."""
    b, s, d = x.shape
    n = b * s
    g = _num_groups(cfg, n)
    ng = n // g
    e, k = cfg.held, cfg.top_k
    c = capacity(cfg, ng)

    xg = x.reshape(g, ng, d)
    xg = logical.shard(xg, "expert_group", None, "embed")

    # --- routing (f32) ------------------------------------------------------
    probs, gate_vals, expert_idx = route(params, cfg, xg)  # (G, Ng, E), (G, Ng, K)
    aux = _aux_loss(probs, expert_idx, cfg)

    # --- slot assignment ----------------------------------------------------
    # Flatten (token, k-choice) assignments in token order; position within
    # each held expert = exclusive running count; position >= C, or an
    # expert held elsewhere, drops the assignment.
    flat_e = held_index(cfg, expert_idx).reshape(g, ng * k)  # (G, A); e = elsewhere
    flat_gate = gate_vals.reshape(g, ng * k)
    flat_tok = jnp.broadcast_to(jnp.arange(ng)[:, None], (ng, k)).reshape(ng * k)
    flat_tok = jnp.broadcast_to(flat_tok, (g, ng * k))

    assign_oh = jax.nn.one_hot(flat_e, e, dtype=jnp.int32)  # (G, A, E); zero if elsewhere
    pos_in_e = jnp.cumsum(assign_oh, axis=1) - assign_oh  # exclusive cumsum
    pos = jnp.take_along_axis(pos_in_e, jnp.minimum(flat_e, e - 1)[..., None], axis=-1)[..., 0]
    keep = (flat_e < e) & (pos < c)
    dest = jnp.where(keep, flat_e * c + pos, e * c)  # overflow slot = e*c

    # All slot bookkeeping is vmapped over G so the group axis stays a true
    # scatter/gather *batch* dim — GSPMD partitions batch dims over `data`;
    # an explicit 2-D index formulation defeats that and replicates every
    # group on every chip (measured: 16x combine payload for deepseek-v2).
    def build_slots(dest_g, tok_g, gate_g):
        st = jnp.full((e * c + 1,), ng, jnp.int32).at[dest_g].set(tok_g)
        sg = jnp.zeros((e * c + 1,), jnp.float32).at[dest_g].set(gate_g)
        return st[:-1], sg[:-1]  # drop the overflow slot

    slot_tok, slot_gate = jax.vmap(build_slots)(dest, flat_tok, flat_gate)

    # --- gather -> expert compute -> combine --------------------------------
    x_pad = jnp.concatenate([xg, jnp.zeros((g, 1, d), xg.dtype)], axis=1)
    xe = jax.vmap(lambda xp, st: xp[st])(x_pad, slot_tok)  # (G, E*C, D)
    xe = xe.reshape(g, e, c, d)
    xe = logical.shard(xe, "expert_group", "experts", None, None)

    h_gate = jnp.einsum("gecd,edf->gecf", xe, params["w_gate"])
    h_up = jnp.einsum("gecd,edf->gecf", xe, params["w_up"])
    h = common.act_fn(act)(h_gate.astype(jnp.float32)).astype(x.dtype) * h_up
    ye = jnp.einsum("gecf,efd->gecd", h, params["w_down"])
    ye = logical.shard(ye, "expert_group", "experts", None, None)

    y_flat = ye.reshape(g, e * c, d) * slot_gate[..., None].astype(ye.dtype)

    # combine in the model dtype: each token receives at most top_k + shared
    # partial outputs, so bf16 accumulation is safe — and it halves the
    # expert-parallel psum payload (a measured 2x on the collective term).
    def combine(yt, st):
        return jnp.zeros((ng + 1, d), x.dtype).at[st].add(yt)

    out = jax.vmap(combine)(y_flat.astype(x.dtype), slot_tok)
    out = out[:, :ng]
    out = logical.shard(out, "expert_group", None, "embed")

    if "shared" in params:
        out = out + common.mlp_apply(params["shared"], xg, act=act).reshape(g, ng, d)

    return out.reshape(b, s, d), aux


def apply_dense_reference(
    params: dict, cfg: MoEConfig, x: jax.Array, *, act: str = "silu"
) -> tuple[jax.Array, jax.Array]:
    """O(N·E) oracle: every held expert computed on every token, masked by
    its top-k gate, nothing dropped.  Used only in tests."""
    b, s, d = x.shape
    xf = x.reshape(-1, d)
    probs, gate_vals, expert_idx = route(params, cfg, xf)
    nidx = jnp.arange(xf.shape[0])[:, None]
    dense_gates = jnp.zeros_like(probs).at[nidx, expert_idx].set(gate_vals)  # (N, E)
    dense_gates = dense_gates[:, cfg.expert_offset : cfg.expert_offset + cfg.held]

    h_gate = jnp.einsum("nd,edf->enf", xf, params["w_gate"])
    h_up = jnp.einsum("nd,edf->enf", xf, params["w_up"])
    h = common.act_fn(act)(h_gate.astype(jnp.float32)).astype(x.dtype) * h_up
    ye = jnp.einsum("enf,efd->end", h, params["w_down"])  # (E, N, D)
    out = jnp.einsum("end,ne->nd", ye.astype(jnp.float32), dense_gates)

    out = out.astype(x.dtype)
    if "shared" in params:
        out = out + common.mlp_apply(params["shared"], x, act=act).reshape(-1, d)
    return out.reshape(b, s, d), _aux_loss(probs, expert_idx, cfg)
