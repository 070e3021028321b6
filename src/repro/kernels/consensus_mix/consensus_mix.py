"""Pallas TPU kernel: fused gossip mixing + affinity-bias update.

The paper's hot op is memory-bound: each consensus step reads the peer's own
parameters plus D neighbor parameter sets and must produce both the mixed
parameters (Eq. 4) and the affinity bias d (Sec. IV-A).  Unfused, that is two
passes over the D+1 tensors (mix, then d) = 2(D+1) reads + 2 writes; fused it
is one pass = (D+1) reads + 2 writes, per tile, straight through VMEM.

Layout: parameters are flattened and reshaped to (R, 128) lanes; the grid
runs over peers and tiles R.  Each peer's neighbor tensors arrive as one
(D, R, 128) slab so a single BlockSpec streams all neighbors for the tile.
Mixing weights are tiny and live in SMEM whole.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

LANE = 128
DEFAULT_BLOCK_ROWS = 256  # 256 x 128 f32 = 128 KiB per operand tile
# bytes of VMEM the double-buffered blocks of one grid step may take: well
# inside the scoped VMEM limit of every TPU generation
VMEM_BLOCK_BUDGET = 8 * 1024 * 1024


def sublane_multiple(*dtypes) -> int:
    """Row multiple of the TPU tile floor for these operand dtypes: 8 rows
    for 32-bit, 16 for 16-bit, 32 for 8-bit (the (sublane, 128) tile)."""
    return max(32 // jnp.dtype(dt).itemsize for dt in dtypes)


def lane_layout(n: int, *, multiple: int, row_bytes: int) -> tuple[int, int]:
    """(padded_rows, block_rows) for N elements tiled as (rows, 128) lanes.

    ``block_rows`` is a multiple of ``multiple`` (never 1, 2 or 4 rows,
    which the TPU compiler refuses), at most ``DEFAULT_BLOCK_ROWS``, and
    small enough that double-buffered blocks of every operand — ``row_bytes``
    per lane row, summed over inputs and outputs — fit
    ``VMEM_BLOCK_BUDGET``.  Among such blocks it takes the one that pads
    least; ``padded_rows`` is a whole number of blocks.
    """
    rows = pl.cdiv(n, LANE)
    fit = VMEM_BLOCK_BUDGET // (2 * row_bytes * LANE) // multiple * multiple
    max_rows = max(multiple, min(DEFAULT_BLOCK_ROWS, fit))
    per_block = pl.cdiv(rows, pl.cdiv(rows, max_rows))
    block = pl.cdiv(per_block, multiple) * multiple
    return pl.cdiv(rows, block) * block, block


def to_lanes(x: jax.Array, rows: int) -> jax.Array:
    """(..., N) -> (..., rows, 128), zero-padded past N."""
    pad = rows * LANE - x.shape[-1]
    if pad:
        x = jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)])
    return x.reshape(x.shape[:-1] + (rows, LANE))


def smem_spec() -> pl.BlockSpec:
    """Whole small operand (mixing weights, scalars) in scalar memory."""
    from jax.experimental.pallas import tpu as pltpu

    return pl.BlockSpec(memory_space=pltpu.SMEM)


def _kernel(x_ref, nbrs_ref, w_self_ref, w_nbr_ref, beta_ref, inv_t_ref,
            mixed_ref, d_ref):
    k = pl.program_id(0)
    x = x_ref[...].astype(jnp.float32)  # (BR, 128)
    # One pass over the neighbor tensors computes both outputs: a weighted
    # sum over the static D, scalar weights read from SMEM.
    mixed = w_self_ref[k] * x
    nbr_avg = jnp.zeros_like(x)
    beta_sum = 0.0
    for j in range(nbrs_ref.shape[0]):
        nbr = nbrs_ref[j].astype(jnp.float32)
        mixed = mixed + w_nbr_ref[k, j] * nbr
        nbr_avg = nbr_avg + beta_ref[k, j] * nbr
        beta_sum = beta_sum + beta_ref[k, j]
    mixed_ref[...] = mixed.astype(mixed_ref.dtype)
    # All-zero beta row = no neighbors this round (e.g. churned-out peer in a
    # time-varying schedule): the affinity bias stays 0 instead of pulling
    # the peer toward the origin.
    d = jnp.where(beta_sum > 0.0, (nbr_avg - x) * inv_t_ref[0], jnp.zeros_like(x))
    d_ref[...] = d.astype(d_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def consensus_mix_2d(
    x: jax.Array,  # (K, R, 128)
    nbrs: jax.Array,  # (K, D, R, 128) each peer's gathered neighbors
    w_self: jax.Array,  # (K,)
    w_nbr: jax.Array,  # (K, D)
    beta: jax.Array,  # (K, D)
    inv_t: jax.Array,  # scalar: 1 / local_steps
    *,
    block_rows: int = DEFAULT_BLOCK_ROWS,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Every peer's fused mix in one pallas_call, grid (K, row blocks)."""
    from repro.kernels import lowering

    interpret = lowering.resolve_interpret(interpret)
    k, r, lane = x.shape
    d = nbrs.shape[1]
    assert lane == LANE and nbrs.shape == (k, d, r, LANE)
    br = min(block_rows, r)
    assert r % br == 0, f"rows {r} not divisible by block {br}"

    tile = pl.BlockSpec((None, br, LANE), lambda p, i: (p, i, 0))
    out_shape = (
        jax.ShapeDtypeStruct((k, r, LANE), x.dtype),
        jax.ShapeDtypeStruct((k, r, LANE), x.dtype),
    )
    return pl.pallas_call(
        _kernel,
        grid=(k, r // br),
        in_specs=[
            tile,
            pl.BlockSpec((None, d, br, LANE), lambda p, i: (p, 0, i, 0)),
        ]
        + [smem_spec()] * 4,
        out_specs=[tile, tile],
        out_shape=out_shape,
        interpret=interpret,
    )(
        x, nbrs, w_self.astype(jnp.float32), w_nbr.astype(jnp.float32),
        beta.astype(jnp.float32), jnp.asarray(inv_t, jnp.float32).reshape(1),
    )
