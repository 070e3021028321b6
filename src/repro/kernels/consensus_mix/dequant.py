"""Pallas TPU kernel: fused dequantize-and-mix for int8-compressed gossip.

The compressed-gossip runtime (``repro.compression``, ``compressor="qint8"``)
moves consensus traffic as int8 difference payloads plus one fp32 scale per
sender; each receiver keeps a dense fp32 public estimate per neighbor and the
mix consumes ``est + q * scale`` (the advanced estimate).  The obvious
consumption order — materialize each advanced fp32 neighbor copy, then run
the fused mix — doubles the HBM traffic: write D fp32 tensors, read them
back.  This kernel fuses the advance INTO the mix: the int8 tiles and the
fp32 estimate tiles stream straight to VMEM and the per-sender scale is
folded into the mixing weights on the host side of the call,

    mixed = w_self * x + sum_d w_nbr[d] * est[d]
                       + sum_d (w_nbr[d] * scale[d]) * q[d]
    d     = (sum_d beta[d] * est[d]
             + sum_d (beta[d] * scale[d]) * q[d] - x_hat_self) / T

so no advanced neighbor copy ever exists — the weighted accumulation runs
directly on the compressed representation (the in-register int8 -> f32 cast
is free next to the memory saved).  ``x_hat_self`` is the peer's OWN public
estimate: the affinity d of the compressed runtime operates on estimate
differences (see the CHOCO wire of ``p2p.consensus_phase``), while the mix's self
term stays exact on the true ``x``.  The no-neighbor guard cannot read the
folded beta (scale = 0 would corrupt it), so the RAW beta sum rides in as a
separate flag.

Layout matches ``consensus_mix.py``: (rows, 128) lanes, the grid runs over
peers and tiles rows, one (D, BR, 128) int8 BlockSpec streams a peer's
payloads per tile.  Note the TPU int8 tile floor is (32, 128) vs fp32's
(8, 128); ``dequant_mix_rows`` pads the lane rows to blocks that are
multiples of 32 accordingly.  The dense oracle
is ``ref.dequant_mix_ref`` (advance-then-mix, f32): the kernel must stay
allclose to it in every cell.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.consensus_mix.consensus_mix import (
    DEFAULT_BLOCK_ROWS,
    LANE,
    lane_layout,
    smem_spec,
    sublane_multiple,
    to_lanes,
)
from repro.kernels.consensus_mix.ops import flatten_pytree, unflatten_pytree

PyTree = object


def _kernel(x_ref, self_est_ref, est_ref, q_ref, w_self_ref, w_nbr_ref,
            w_eff_ref, beta_ref, beta_eff_ref, has_nbrs_ref, inv_t_ref,
            mixed_ref, d_ref):
    k = pl.program_id(0)
    x = x_ref[...].astype(jnp.float32)  # (BR, 128)
    # a weighted sum over the static D; the (K, D) weights are SMEM scalars,
    # w_eff = w_nbr * scale and beta_eff = beta * scale fold the advance in
    mixed = w_self_ref[k] * x
    nbr_avg = jnp.zeros_like(x)
    for j in range(q_ref.shape[0]):
        est = est_ref[j].astype(jnp.float32)
        q = q_ref[j].astype(jnp.float32)  # int8, cast in-register
        mixed = mixed + w_nbr_ref[k, j] * est + w_eff_ref[k, j] * q
        nbr_avg = nbr_avg + beta_ref[k, j] * est + beta_eff_ref[k, j] * q
    mixed_ref[...] = mixed.astype(mixed_ref.dtype)
    # the guard flag is the RAW beta sum (beta_eff would read 0 whenever a
    # sender's payload scale is 0, e.g. an all-zero difference)
    self_est = self_est_ref[...].astype(jnp.float32)
    d = jnp.where(
        has_nbrs_ref[k] > 0.0, (nbr_avg - self_est) * inv_t_ref[0], jnp.zeros_like(x)
    )
    d_ref[...] = d.astype(d_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def dequant_mix_2d(
    x: jax.Array,  # (K, R, 128) f32 — each peer's own TRUE lanes
    self_est: jax.Array,  # (K, R, 128) f32 — each peer's own public estimate
    nbrs_est: jax.Array,  # (K, D, R, 128) f32 — neighbor public estimates
    nbrs_q: jax.Array,  # (K, D, R, 128) int8 — neighbor difference payloads
    w_self: jax.Array,  # (K,)
    w_nbr: jax.Array,  # (K, D)
    w_eff: jax.Array,  # (K, D) w_nbr * scale
    beta: jax.Array,  # (K, D)
    beta_eff: jax.Array,  # (K, D) beta * scale
    has_nbrs: jax.Array,  # (K,) raw sum(beta), the no-neighbor guard
    inv_t: jax.Array,  # scalar: 1 / local_steps
    *,
    block_rows: int = DEFAULT_BLOCK_ROWS,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Every peer's fused dequantize-and-mix in one pallas_call, grid
    (K, row blocks)."""
    from repro.kernels import lowering

    interpret = lowering.resolve_interpret(interpret)
    k, r, lane = x.shape
    d = nbrs_q.shape[1]
    assert lane == LANE and nbrs_q.shape == (k, d, r, LANE)
    assert nbrs_est.shape == (k, d, r, LANE) and self_est.shape == (k, r, LANE)
    assert nbrs_q.dtype == jnp.int8
    br = min(block_rows, r)
    assert r % br == 0, f"rows {r} not divisible by block {br}"

    tile = pl.BlockSpec((None, br, LANE), lambda p, i: (p, i, 0))
    slab = pl.BlockSpec((None, d, br, LANE), lambda p, i: (p, 0, i, 0))
    out_shape = (
        jax.ShapeDtypeStruct((k, r, LANE), x.dtype),
        jax.ShapeDtypeStruct((k, r, LANE), x.dtype),
    )
    f32 = functools.partial(jnp.asarray, dtype=jnp.float32)
    return pl.pallas_call(
        _kernel,
        grid=(k, r // br),
        in_specs=[tile, tile, slab, slab] + [smem_spec()] * 7,
        out_specs=[tile, tile],
        out_shape=out_shape,
        interpret=interpret,
    )(
        x, self_est, nbrs_est, nbrs_q, f32(w_self), f32(w_nbr), f32(w_eff),
        f32(beta), f32(beta_eff), f32(has_nbrs), f32(inv_t).reshape(1),
    )


def dequant_mix_rows(
    x: jax.Array,  # (K, N) f32 — each peer's own TRUE parameters
    self_est: jax.Array,  # (K, N) f32 — each peer's own public estimate
    nbrs_est: jax.Array,  # (K, D, N) f32 — gathered neighbor estimates
    nbrs_q: jax.Array,  # (K, D, N) int8 — gathered difference payloads
    nbr_scale: jax.Array,  # (K, D) fp32 payload scales
    w_self: jax.Array,  # (K,)
    w_nbr: jax.Array,  # (K, D)
    beta: jax.Array,  # (K, D)
    local_steps: int,
    *,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Fused dequantize-and-mix of K flattened rows: (mixed, d), both (K, N).

    Must stay allclose to ``ref.dequant_mix_ref`` (which materializes the
    advanced fp32 neighbors ``est + q * scale``); the kernel instead folds
    ``nbr_scale`` into the weights and accumulates straight from int8.
    """
    k, n = x.shape
    # x, self_est and the two outputs, plus D estimate and D int8 payload
    # rows, per lane row; the int8 payload sets the (32, 128) tile floor
    rows, br = lane_layout(
        n,
        multiple=sublane_multiple(x.dtype, nbrs_q.dtype),
        row_bytes=4 * 4 + nbrs_q.shape[1] * (4 + 1),
    )
    w_nbr = jnp.asarray(w_nbr, jnp.float32)
    beta = jnp.asarray(beta, jnp.float32)
    scale = jnp.asarray(nbr_scale, jnp.float32)
    mixed, d = dequant_mix_2d(
        *(to_lanes(a, rows) for a in (x, self_est, nbrs_est, nbrs_q)),
        w_self,
        w_nbr,
        w_nbr * scale,
        beta,
        beta * scale,
        jnp.sum(beta, axis=1),
        1.0 / local_steps,
        block_rows=br,
        interpret=interpret,
    )
    return mixed.reshape(k, -1)[:, :n], d.reshape(k, -1)[:, :n]


def quantize_int8(flat: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Symmetric int8 payload of a (K, N) f32 stack: (q int8, scale (K,)).

    The kernel path's whole-tree quantization (one scale per peer over the
    concatenated leaves) — the sender-side half of the fused consumer below.
    In the estimate-tracking protocol the input stack is the DIFFERENCE
    ``x - est``; the payload advances every copy of the sender's estimate.
    """
    f = flat.astype(jnp.float32)
    amax = jnp.max(jnp.abs(f), axis=1)  # (K,)
    scale = amax / 127.0
    safe = jnp.where(scale > 0.0, scale, 1.0)
    q = jnp.clip(jnp.round(f / safe[:, None]), -127.0, 127.0).astype(jnp.int8)
    return q, scale


@functools.partial(jax.jit, static_argnames=("local_steps", "interpret"))
def dequant_consensus_mix_stacked(
    stacked: PyTree,  # leaves (K, ...) — each peer's own TRUE parameters
    est: jax.Array,  # (K, N) f32 — flattened public-estimate stack
    q: jax.Array,  # (K, N) int8 — the senders' payloads (quantize_int8)
    scale: jax.Array,  # (K,) fp32 payload scales
    self_w: jax.Array,  # (K,)
    nbr_idx: jax.Array,  # (K, D) padded neighbor indices
    nbr_w: jax.Array,  # (K, D)
    beta: jax.Array,  # (K, D)
    local_steps: int,
    *,
    interpret: bool | None = None,
) -> tuple[PyTree, PyTree]:
    """One gossip step + affinity d where every NEIGHBOR view is its public
    estimate advanced by the int8 payload: the self term stays exact on the
    peer's own fp32 row, neighbor accumulation runs fused from the compressed
    representation.

    Returns (mixed_params, d_bias), like ``ops.consensus_mix_stacked``.
    ``est`` is the flattened (K, N) estimate stack BEFORE this step's
    advance; the caller advances its carried copy with ``est + q * scale``.
    """
    flat, _ = flatten_pytree(stacked)  # (K, N) f32
    # (K, D, N) gathers: the int8 payloads stay compressed in HBM
    mixed, d = dequant_mix_rows(
        flat, est, est[nbr_idx], q[nbr_idx], scale[nbr_idx], self_w, nbr_w, beta,
        local_steps, interpret=interpret,
    )
    return unflatten_pytree(stacked, mixed), unflatten_pytree(stacked, d)


@functools.partial(jax.jit, static_argnames=("local_steps", "interpret"))
def dequant_consensus_mix_schedule(
    stacked: PyTree,
    est: jax.Array,  # (K, N) f32
    q: jax.Array,  # (K, N) int8
    scale: jax.Array,  # (K,)
    self_w_s: jax.Array,  # (R, K)
    nbr_idx_s: jax.Array,  # (R, K, D)
    nbr_w_s: jax.Array,  # (R, K, D)
    beta_s: jax.Array,  # (R, K, D)
    round_idx: jax.Array,  # traced scalar
    local_steps: int,
    *,
    interpret: bool | None = None,
) -> tuple[PyTree, PyTree]:
    """Time-varying form: round ``round_idx % R`` of a stacked sparse schedule
    (``ops.sparse_from_schedule``) selected INSIDE the traced program — one
    compile serves every round, like ``ops.consensus_mix_schedule``."""
    idx = jax.lax.rem(round_idx, self_w_s.shape[0])
    return dequant_consensus_mix_stacked(
        stacked, est, q, scale,
        self_w_s[idx], nbr_idx_s[idx], nbr_w_s[idx], beta_s[idx],
        local_steps, interpret=interpret,
    )
