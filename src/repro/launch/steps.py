"""jit-able step functions: local train step, consensus step, serve steps.

The paper's round structure at production scale:
    for r in rounds:
        for t in range(T):  train_step        (intra-peer only: FSDP/TP colls)
        consensus_step                        (inter-peer: the `pod` axis)

``train_step`` is the P2PL learning phase (Eq. 3): grad + optimizer update +
eta_d * d affinity bias.  ``consensus_step`` is Eq. 4 plus the affinity d/b
updates — at zero extra communication, since d is computed from the very
parameters the mixing step already gathers (verified by the dry-run byte
parity check in EXPERIMENTS.md).

Multi-pod variants wrap the single-peer step in
``jax.vmap(..., spmd_axis_name="pod")`` over peer-stacked trees.
"""
from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import consensus as consensus_lib
from repro.models.registry import Model
from repro.optim import Optimizer

PyTree = Any


def make_train_step(model: Model, opt: Optimizer, *, eta_d: float = 0.0) -> Callable:
    """(params, opt_state, d_bias, batch, step) -> (params, opt_state, loss)."""

    def train_step(params, opt_state, d_bias, batch, step):
        loss, grads = jax.value_and_grad(model.loss_fn)(params, batch)
        params, opt_state = opt.update(grads, opt_state, params, step)
        if eta_d:
            params = jax.tree.map(
                lambda w, d: (w.astype(jnp.float32) + eta_d * d.astype(jnp.float32)).astype(
                    w.dtype
                ),
                params,
                d_bias,
            )
        return params, opt_state, loss

    return train_step


def make_consensus_step(
    w_mat: np.ndarray,
    beta_mat: np.ndarray,
    *,
    local_steps: int,
    use_affinity: bool,
) -> Callable:
    """Stacked-peer gossip: (stacked_params, d_bias) -> (mixed_params, new_d).

    Operates on trees whose leaves carry a leading K (peer) axis, sharded over
    the `pod` mesh axis at production scale.  The mixing einsum lowers to an
    all-gather/all-reduce across `pod` only.
    """
    w = jnp.asarray(w_mat, jnp.float32)
    beta = jnp.asarray(beta_mat, jnp.float32)

    def consensus_step(stacked_params, d_bias):
        if use_affinity:
            nbr_avg = consensus_lib.mix_stacked(beta, stacked_params)
            d_bias = jax.tree.map(
                lambda avg, p: (avg.astype(jnp.float32) - p.astype(jnp.float32))
                / local_steps,
                nbr_avg,
                stacked_params,
            )
        mixed = consensus_lib.mix_stacked(w, stacked_params)
        return mixed, d_bias

    return consensus_step


def make_consensus_step_psum(
    num_peers: int,
    *,
    self_weight: float,
    peer_weight: float,
    local_steps: int,
    use_affinity: bool,
) -> Callable:
    """Optimized gossip for uniform complete graphs (the pod-level topology).

    out_k = a*x_k + b*sum_{j!=k} x_j = (a-b)*x_k + b*S,   S = sum_k x_k
    d_k   = (S - x_k)/(K-1 ) - x_k, scaled by 1/T          (uniform beta)

    Both outputs derive from ONE peer-axis reduction S: XLA lowers the
    jnp.sum over the stacked axis into a single all-reduce of the *local
    shard* across the pod axis — vs. the general einsum form, which the
    partitioner resolves by fully rematerializing (replicating) the stacked
    parameters on every chip (measured: ~113 GiB/chip for rwkv6-7b).  This
    also makes the paper's zero-extra-communication claim structural: the
    affinity d costs zero additional collective ops, not just zero bytes.
    """

    def consensus_step(stacked_params, d_bias):
        def mix_leaf(x):
            xf = x.astype(jnp.float32)
            s = jnp.sum(xf, axis=0, keepdims=True)  # one all-reduce over pod
            mixed = (self_weight - peer_weight) * xf + peer_weight * s
            return mixed.astype(x.dtype), s

        mixed_and_s = jax.tree.map(mix_leaf, stacked_params)
        mixed = jax.tree.map(lambda t: t[0], mixed_and_s,
                             is_leaf=lambda t: isinstance(t, tuple))
        if use_affinity:
            def d_leaf(pair, x):
                _, s = pair
                xf = x.astype(jnp.float32)
                nbr_avg = (s - xf) / max(num_peers - 1, 1)
                return ((nbr_avg - xf) / local_steps).astype(x.dtype)

            d_bias = jax.tree.map(
                d_leaf, mixed_and_s, stacked_params,
                is_leaf=lambda t: isinstance(t, tuple),
            )
        return mixed, d_bias

    return consensus_step


def make_multipod_train_step(model: Model, opt: Optimizer, *, eta_d: float = 0.0) -> Callable:
    """vmap the single-peer train step over the leading peer axis; inner
    sharding constraints are lifted onto the `pod` mesh axis via
    spmd_axis_name (each peer's compute stays inside its pod)."""
    step = make_train_step(model, opt, eta_d=eta_d)
    return jax.vmap(step, in_axes=(0, 0, 0, 0, None), spmd_axis_name="pod")


def make_prefill_step(model: Model) -> Callable:
    def prefill_step(params, batch, cache):
        logits, cache = model.prefill(params, batch, cache)
        next_tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
        return next_tok, cache

    return prefill_step


def make_serve_step(model: Model) -> Callable:
    """One decode step: greedy-sample the next token, update the cache."""

    def serve_step(params, cache, token, pos):
        logits, cache = model.decode_step(params, token, pos, cache)
        next_tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
        return next_tok, pos + 1, cache

    return serve_step


def make_multipod_serve_step(model: Model) -> Callable:
    step = make_serve_step(model)
    return jax.vmap(step, in_axes=(0, 0, 0, 0), spmd_axis_name="pod")


def prompt_dec_len(batch: PyTree) -> int:
    """Decoder-side length of a prompt batch: the position decode resumes at.

    vlm prefix embeddings (``patches``) occupy decoder cache slots ahead of
    the text tokens, so they advance the decode position; encoder inputs
    (encdec ``frames``) live in a separate cross-attention cache and do NOT.
    """
    n = batch["tokens"].shape[1]
    if "patches" in batch:
        n += batch["patches"].shape[1]
    return n


def make_decode_scan(model: Model, num_steps: int) -> Callable:
    """(params, cache, token, pos) -> (tokens (B, num_steps), cache).

    The per-token python decode loop collapsed into ONE ``lax.scan`` over
    generation steps — one dispatch and one compile for the whole generation
    instead of one per token (the same scan pattern that fused the round
    loop in ``core/p2p.py:make_scan_driver``).  ``num_steps`` is static: one
    compile per generation length.  ``num_steps == 0`` is rejected — callers
    take the empty-decode path structurally (see ``make_generate_fn``).
    """
    if num_steps < 1:
        raise ValueError(
            f"make_decode_scan needs num_steps >= 1, got {num_steps}; a "
            "zero-step decode is the explicit empty-decode case — skip the "
            "scan entirely (make_generate_fn does this structurally)"
        )
    step = make_serve_step(model)

    def decode_scan(params, cache, token, pos):
        def body(carry, _):
            tok, p, c = carry
            tok, p, c = step(params, c, tok, p)
            return (tok, p, c), tok

        (_, _, cache), toks = jax.lax.scan(
            body, (token, pos, cache), None, length=num_steps
        )
        return jnp.moveaxis(toks, 0, 1), cache  # (steps, B) -> (B, steps)

    return decode_scan


def make_generate_fn(model: Model, gen_tokens: int) -> Callable:
    """(params, batch, cache) -> (tokens (B, gen_tokens), cache).

    Prefill + scanned greedy decode as one traceable function: the prefill
    argmax is the first generated token, the remaining ``gen_tokens - 1``
    come from ``make_decode_scan``.  ``gen_tokens == 1`` skips the scan
    STRUCTURALLY (prefill only — the explicit empty decode).  Returning the
    final cache lets callers jit with ``donate_argnums`` on the cache slot:
    the input buffers are reused in place for the output cache.  Prefill runs
    under the named scope ``repro.prefill``, decode under ``repro.decode``.
    """
    if gen_tokens < 1:
        raise ValueError(f"need gen_tokens >= 1, got {gen_tokens}")
    prefill = make_prefill_step(model)
    decode = make_decode_scan(model, gen_tokens - 1) if gen_tokens > 1 else None

    def generate(params, batch, cache):
        with jax.named_scope("repro.prefill"):
            tok, cache = prefill(params, batch, cache)
        if decode is None:
            return tok[:, None], cache
        with jax.named_scope("repro.decode"):
            pos = jnp.full(tok.shape, prompt_dec_len(batch), jnp.int32)
            toks, cache = decode(params, cache, tok, pos)
            return jnp.concatenate([tok[:, None], toks], axis=1), cache

    return generate
