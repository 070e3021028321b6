"""Pallas TPU kernel: segment-sum consensus mix over an edge list.

The stacked kernel path (``ops.consensus_mix_stacked``) gathers each peer's
neighbor parameters OUTSIDE the kernel — ``flat[nbr_idx]`` materializes a
(K, D, N) array in HBM before a single tile is mixed.  At K = 4096 that
gather is the memory wall, and the dense alternative (a (K, K) einsum) is
the very array the sparse schedule exists to avoid.

This kernel moves the gather inside the pallas machinery: the padded
neighbor indices are scalar-prefetch operands, and the neighbor BlockSpec's
``index_map`` reads them — ``(idx_ref[k, d], r, 0)`` — so each grid step
DMAs exactly one neighbor's (block_rows, 128) tile straight to VMEM.  No
(K, K) matrix and no (K, D, N) gather ever exists; HBM traffic is the
edge list itself: sum_k (D+1) tiles read, 2 tiles written.

Grid: (K, row_blocks, D), neighbor slot innermost so the two outputs
accumulate in VMEM across the D steps of each (peer, row-block) pair:

    mixed[k] = self_w[k] * x[k] + sum_d nbr_w[k, d] * x[nbr_idx[k, d]]
    d[k]     = (sum_d beta[k, d] * x[nbr_idx[k, d]] - x[k]) / T

Padding slots follow the repo-wide convention (``graph.SparseSchedule``):
index = own row, weight = beta = 0.0 — a self-tile DMA whose contribution
is exactly +-0.0.  Like every degree-bounded path, the slot-ordered sum is
allclose to the dense einsum, not bit-identical (see core/p2p.py's
hierarchical "segment" mode for the same contract).
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
    sublane_multiple,
    to_lanes,
)


def _segment_kernel(
    num_slots: int,
    self_w_ref,  # SMEM (K,)
    idx_ref,  # SMEM (K, D)
    nbr_w_ref,  # SMEM (K, D)
    beta_ref,  # SMEM (K, D)
    has_nbrs_ref,  # SMEM (K,) 1.0 where the peer's beta row is not all zero
    inv_t_ref,  # SMEM (1,)
    x_self_ref,  # VMEM (1, BR, LANE) — peer k's own tile
    x_nbr_ref,  # VMEM (1, BR, LANE) — neighbor idx_ref[k, d]'s tile
    mixed_ref,  # VMEM (1, BR, LANE) accumulator
    d_ref,  # VMEM (1, BR, LANE) accumulator
):
    k = pl.program_id(0)
    d = pl.program_id(2)
    x = x_self_ref[0].astype(jnp.float32)
    xn = x_nbr_ref[0].astype(jnp.float32)

    @pl.when(d == 0)
    def _init():
        mixed_ref[0] = (self_w_ref[k] * x).astype(mixed_ref.dtype)
        d_ref[0] = jnp.zeros_like(x).astype(d_ref.dtype)

    mixed_ref[0] = (
        mixed_ref[0].astype(jnp.float32) + nbr_w_ref[k, d] * xn
    ).astype(mixed_ref.dtype)
    d_ref[0] = (d_ref[0].astype(jnp.float32) + beta_ref[k, d] * xn).astype(
        d_ref.dtype
    )

    @pl.when(d == num_slots - 1)
    def _finish():
        # all-zero beta row = isolated peer this round: d stays 0 instead of
        # decaying the peer toward the origin (dense-path semantics)
        acc = d_ref[0].astype(jnp.float32)
        out = jnp.where(
            has_nbrs_ref[k] > 0.0, (acc - x) * inv_t_ref[0], jnp.zeros_like(x)
        )
        d_ref[0] = out.astype(d_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def segment_mix_2d(
    x: jax.Array,  # (K, R, LANE) — every peer's lane-tiled parameters
    self_w: jax.Array,  # (K,)
    nbr_idx: jax.Array,  # (K, D) padded neighbor indices, int32
    nbr_w: jax.Array,  # (K, D)
    beta: jax.Array,  # (K, D)
    inv_t: jax.Array,  # scalar: 1 / local_steps
    *,
    block_rows: int = DEFAULT_BLOCK_ROWS,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    """All peers' fused segment mix in one pallas_call.

    Returns (mixed, d), both (K, R, LANE).  The neighbor gather happens via
    the scalar-prefetch ``index_map`` — ``x`` is read tile-by-tile, never
    gathered into a (K, D, ...) array.
    """
    from jax.experimental.pallas import tpu as pltpu

    from repro.kernels import lowering

    interpret = lowering.resolve_interpret(interpret)
    k, r, lane = x.shape
    d = nbr_idx.shape[1]
    assert lane == LANE and nbr_idx.shape == (k, d)
    br = min(block_rows, r)
    assert r % br == 0, f"rows {r} not divisible by block {br}"

    grid = (k, r // br, d)
    spec_self = pl.BlockSpec(
        (1, br, LANE), lambda pk, pr, pd, sw, idx, nw, bt, hn, it: (pk, pr, 0)
    )
    spec_nbr = pl.BlockSpec(
        (1, br, LANE),
        lambda pk, pr, pd, sw, idx, nw, bt, hn, it: (idx[pk, pd], pr, 0),
    )
    spec_out = pl.BlockSpec(
        (1, br, LANE), lambda pk, pr, pd, sw, idx, nw, bt, hn, it: (pk, pr, 0)
    )
    out_shape = (
        jax.ShapeDtypeStruct((k, r, LANE), x.dtype),
        jax.ShapeDtypeStruct((k, r, LANE), x.dtype),
    )
    return pl.pallas_call(
        functools.partial(_segment_kernel, d),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=6,
            grid=grid,
            in_specs=[spec_self, spec_nbr],
            out_specs=[spec_out, spec_out],
        ),
        out_shape=out_shape,
        interpret=interpret,
    )(
        self_w.astype(jnp.float32),
        nbr_idx.astype(jnp.int32),
        nbr_w.astype(jnp.float32),
        beta.astype(jnp.float32),
        (jnp.sum(beta, axis=1) > 0.0).astype(jnp.float32),
        jnp.asarray(inv_t, jnp.float32).reshape(1),
        x,
        x,
    )


def _segment_mix_flat(flat, self_w, nbr_idx, nbr_w, beta, local_steps, interpret):
    """(K, N) -> mixed, d (K, N) through ``segment_mix_2d``: lane rows padded
    to a whole number of blocks (multiples of the dtype's tile floor)."""
    k, n = flat.shape
    # the peer's tile, one neighbor tile and the two outputs per lane row
    rows, br = lane_layout(
        n, multiple=sublane_multiple(flat.dtype), row_bytes=4 * flat.dtype.itemsize
    )
    mixed, d = segment_mix_2d(
        to_lanes(flat, rows), self_w, nbr_idx, nbr_w, beta,
        jnp.asarray(1.0 / local_steps, jnp.float32),
        block_rows=br, interpret=interpret,
    )
    return mixed.reshape(k, -1)[:, :n], d.reshape(k, -1)[:, :n]


@functools.partial(jax.jit, static_argnames=("local_steps", "interpret"))
def segment_mix_stacked(
    stacked,  # pytree, leaves (K, ...)
    self_w: jax.Array,  # (K,)
    nbr_idx: jax.Array,  # (K, D)
    nbr_w: jax.Array,  # (K, D)
    beta: jax.Array,  # (K, D)
    local_steps: int,
    *,
    interpret: bool | None = None,
):
    """One gossip step + affinity d for all peers via the segment kernel.

    The degree-bounded analogue of ``ops.consensus_mix_stacked`` without its
    (K, D, N) pre-gather.  Returns (mixed_params, d_bias).
    """
    from repro.kernels.consensus_mix import ops

    flat, _ = ops.flatten_pytree(stacked)  # (K, N)
    mixed, d = _segment_mix_flat(
        flat, self_w, nbr_idx, nbr_w, beta, local_steps, interpret
    )
    return ops.unflatten_pytree(stacked, mixed), ops.unflatten_pytree(stacked, d)


@functools.partial(jax.jit, static_argnames=("local_steps", "interpret"))
def segment_mix_push_sum_stacked(
    stacked,  # pytree, leaves (K, ...) — the DE-BIASED parameters
    mass: jax.Array,  # (K,) push-sum mass y
    self_w: jax.Array,  # (K,) diagonal of the column-stochastic A
    nbr_idx: jax.Array,  # (K, D) padded in-neighbor indices
    nbr_w: jax.Array,  # (K, D) off-diagonal A weights
    beta: jax.Array,  # (K, D)
    local_steps: int,
    *,
    interpret: bool | None = None,
):
    """Push-sum through the SAME segment kernel via the mass-lane trick
    (``ops.consensus_mix_push_sum_stacked``, degree-bounded edition): the
    (K,) mass rides as one appended all-ones lane while the weights are
    pre-scaled by the sender's mass, so one fused pass yields the mixed
    numerators, the new mass, and the affinity d of the de-biased
    parameters.  Returns (mixed_params, d_bias, new_mass)."""
    from repro.kernels.consensus_mix import ops

    flat, _ = ops.flatten_pytree(stacked)  # (K, N)
    k = flat.shape[0]
    aug = jnp.concatenate(
        [flat.astype(jnp.float32), jnp.ones((k, 1), jnp.float32)], axis=1
    )
    massf = mass.astype(jnp.float32)
    self_w_y = self_w * massf
    nbr_w_y = nbr_w * massf[nbr_idx]  # (K, D) — edge-list sized, not (K, K)

    mixed, d = _segment_mix_flat(
        aug, self_w_y, nbr_idx, nbr_w_y, beta, local_steps, interpret
    )
    new_mass = mixed[:, -1]
    debiased = mixed[:, :-1] / new_mass[:, None]
    return (
        ops.unflatten_pytree(stacked, debiased),
        ops.unflatten_pytree(stacked, d[:, :-1]),
        new_mass,
    )


def segment_mix_schedule(
    stacked,
    round_idx: jax.Array,
    self_w_s: jax.Array,  # (R, K)
    nbr_idx_s: jax.Array,  # (R, K, D)
    nbr_w_s: jax.Array,  # (R, K, D)
    beta_s: jax.Array,  # (R, K, D)
    local_steps: int,
    *,
    interpret: bool | None = None,
):
    """Round ``round_idx % R`` of a stacked sparse schedule through the
    segment kernel (one compiled shape for the whole schedule)."""
    idx = jax.lax.rem(
        jnp.asarray(round_idx, jnp.int32), jnp.int32(self_w_s.shape[0])
    )
    return segment_mix_stacked(
        stacked, self_w_s[idx], nbr_idx_s[idx], nbr_w_s[idx], beta_s[idx],
        local_steps, interpret=interpret,
    )
