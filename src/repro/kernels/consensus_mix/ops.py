"""jit'd public API for the fused consensus kernel.

``consensus_mix_rows``    — operates on K flattened (N,) parameter rows.
``consensus_mix_stacked`` — drop-in accelerated form of one gossip step over a
stacked (K, ...) parameter pytree with a sparse (padded-neighbor) mixing
matrix; used by the P2P runtime when ``use_kernel=True``.
``consensus_mix_schedule``— time-varying form: selects round ``r % R`` of a
stacked (R, ...) sparse schedule (built by ``sparse_from_schedule``, padded to
the schedule-wide max degree) inside the traced program, so every round of a
churning topology reuses one compiled kernel.

``consensus_mix_push_sum_stacked`` / ``..._push_sum_schedule`` — the directed
push-sum protocol through the SAME kernel: the (K,) push-sum mass rides as one
appended all-ones lane of the flattened parameters while the sparse weights
are pre-scaled by the sender's mass, so a single fused pass yields the mixed
numerators, the new mass, AND the affinity d of the de-biased parameters.

``consensus_mix_dense`` / ``consensus_mix_push_sum_dense`` — the
*dense-dynamic* path for state-dependent (adaptive) topologies: the (K, K)
W/Beta are TRACED values computed inside the program each round
(``graph.adaptive_round_matrices``), so no host-built sparse structure
exists.  The candidate neighbor set is the static complete graph (every
``j != k``, a trace-time constant) and the per-candidate weights are gathered
dynamically from the dense matrices — unselected candidates carry weight 0
and contribute exactly +-0.0, so one kernel shape serves every matching the
selection can produce, preserving the one-compile property.

Every entry point takes ``interpret: bool | None = None`` and resolves the
default through ``repro.kernels.lowering`` — interpret mode on CPU (the only
mode Pallas can run there), compiled lowering on real accelerators, with the
``REPRO_PALLAS_INTERPRET`` environment variable overriding either direction.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import consensus as consensus_lib
from repro.kernels.consensus_mix.consensus_mix import (
    consensus_mix_2d,
    lane_layout,
    sublane_multiple,
    to_lanes,
)

PyTree = object


def consensus_mix_rows(
    x: jax.Array,  # (K, N)
    nbrs: jax.Array,  # (K, D, N) each peer's gathered neighbors
    w_self: jax.Array,  # (K,)
    w_nbr: jax.Array,  # (K, D)
    beta: jax.Array,  # (K, D)
    local_steps: int,
    *,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Fused mix + affinity d of K flattened rows: (mixed, d), both (K, N)."""
    # interpret=None resolves inside consensus_mix_2d (repro.kernels.lowering)
    k, n = x.shape
    # x and the two outputs, plus the D neighbor rows, per lane row
    rows, br = lane_layout(
        n,
        multiple=sublane_multiple(x.dtype, nbrs.dtype),
        row_bytes=3 * x.dtype.itemsize + nbrs.shape[1] * nbrs.dtype.itemsize,
    )
    mixed, d = consensus_mix_2d(
        to_lanes(x, rows),
        to_lanes(nbrs, rows),
        jnp.asarray(w_self, jnp.float32),
        jnp.asarray(w_nbr, jnp.float32),
        jnp.asarray(beta, jnp.float32),
        jnp.asarray(1.0 / local_steps, jnp.float32),
        block_rows=br,
        interpret=interpret,
    )
    return mixed.reshape(k, -1)[:, :n], d.reshape(k, -1)[:, :n]


def flatten_pytree(tree: PyTree) -> tuple[jax.Array, list]:
    leaves = jax.tree.leaves(tree)
    flat = jnp.concatenate([l.reshape(l.shape[0], -1) for l in leaves], axis=1)
    meta = [(l.shape, l.dtype) for l in leaves]
    return flat, meta


def unflatten_pytree(tree_like: PyTree, flat: jax.Array) -> PyTree:
    leaves, treedef = jax.tree.flatten(tree_like)
    out, off = [], 0
    for l in leaves:
        sz = int(np.prod(l.shape[1:])) if l.ndim > 1 else 1
        out.append(flat[:, off : off + sz].reshape(l.shape).astype(l.dtype))
        off += sz
    return jax.tree.unflatten(treedef, out)


@functools.partial(jax.jit, static_argnames=("local_steps", "interpret"))
def consensus_mix_stacked(
    stacked: PyTree,  # leaves (K, ...)
    self_w: jax.Array,  # (K,)
    nbr_idx: jax.Array,  # (K, D) padded neighbor indices
    nbr_w: jax.Array,  # (K, D)
    beta: jax.Array,  # (K, D)
    local_steps: int,
    *,
    interpret: bool | None = None,
) -> tuple[PyTree, PyTree]:
    """One gossip step + affinity d for all peers, via the fused kernel.

    Equivalent to consensus_lib.mix_sparse + the d update, but each neighbor
    tensor is read once.  Returns (mixed_params, d_bias).
    """
    flat, _ = flatten_pytree(stacked)  # (K, N)
    # (K, D, N) gather — stays in HBM, tiles stream to VMEM
    mixed, d = consensus_mix_rows(
        flat, flat[nbr_idx], self_w, nbr_w, beta, local_steps, interpret=interpret
    )
    return unflatten_pytree(stacked, mixed), unflatten_pytree(stacked, d)


@functools.partial(jax.jit, static_argnames=("local_steps", "interpret"))
def consensus_mix_push_sum_stacked(
    stacked: PyTree,  # leaves (K, ...) — the DE-BIASED parameters
    mass: jax.Array,  # (K,) push-sum mass y
    self_w: jax.Array,  # (K,) diagonal of the column-stochastic A
    nbr_idx: jax.Array,  # (K, D) padded in-neighbor indices
    nbr_w: jax.Array,  # (K, D) off-diagonal A weights
    beta: jax.Array,  # (K, D)
    local_steps: int,
    *,
    interpret: bool | None = None,
) -> tuple[PyTree, PyTree, jax.Array]:
    """One push-sum step + affinity d for all peers, via the fused kernel.

    The mass scalar is carried as an appended all-ones lane and the weights
    are scaled by the *sender's* mass, so the kernel's single pass computes

        [num_k | y_k'] = sum_j A[k, j] y_j [x_j | 1],   d from the raw x_j

    and the de-biased parameters are ``num / y'``.  Equivalent to
    ``protocols.PushSumProtocol.mix`` plus the d update.
    Returns (mixed_params, d_bias, new_mass).
    """
    flat, _ = flatten_pytree(stacked)  # (K, N)
    k = flat.shape[0]
    aug = jnp.concatenate(
        [flat.astype(jnp.float32), jnp.ones((k, 1), jnp.float32)], axis=1
    )
    massf = mass.astype(jnp.float32)
    self_w_y = self_w * massf
    nbr_w_y = nbr_w * massf[nbr_idx]

    mixed, d = consensus_mix_rows(
        aug, aug[nbr_idx], self_w_y, nbr_w_y, beta, local_steps, interpret=interpret
    )
    new_mass = mixed[:, -1]
    debiased = mixed[:, :-1] / new_mass[:, None]
    return (
        unflatten_pytree(stacked, debiased),
        unflatten_pytree(stacked, d[:, :-1]),
        new_mass,
    )


def _complete_candidates(k: int) -> jax.Array:
    """Static (K, K-1) candidate indices: every peer j != k, row-major.

    The dense-dynamic path's neighbor structure — a trace-time constant that
    admits EVERY possible edge; the traced weights decide which contribute.
    """
    if k < 2:
        raise ValueError("dense-dynamic consensus needs at least two peers")
    idx = np.arange(k)
    cand = np.stack([np.concatenate([idx[:i], idx[i + 1 :]]) for i in range(k)])
    return jnp.asarray(cand.astype(np.int32))


def _dense_operands(
    w_mat: jax.Array, beta_mat: jax.Array
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """(self_w, nbr_idx, nbr_w, beta) from TRACED dense (K, K) matrices.

    The traced analogue of ``sparse_from_matrices``: the candidate structure
    is the static complete graph, the weights are dynamic gathers from the
    dense matrices, so the stacked kernel entry points consume them unchanged.
    """
    k = w_mat.shape[0]
    nbr_idx = _complete_candidates(k)  # (K, K-1)
    rows = jnp.arange(k, dtype=jnp.int32)[:, None]
    self_w = jnp.diagonal(w_mat).astype(jnp.float32)
    nbr_w = w_mat[rows, nbr_idx].astype(jnp.float32)
    beta_p = beta_mat[rows, nbr_idx].astype(jnp.float32)
    return self_w, nbr_idx, nbr_w, beta_p


@functools.partial(jax.jit, static_argnames=("local_steps", "interpret"))
def consensus_mix_dense(
    stacked: PyTree,  # leaves (K, ...)
    w_mat: jax.Array,  # (K, K) TRACED row-stochastic mixing matrix
    beta_mat: jax.Array,  # (K, K) TRACED affinity matrix
    local_steps: int,
    *,
    interpret: bool | None = None,
) -> tuple[PyTree, PyTree]:
    """One gossip step + affinity d from DYNAMIC dense matrices, via the kernel.

    Unlike ``consensus_mix_stacked``/``_schedule`` (host-built sparse
    structure), ``w_mat``/``beta_mat`` may be values computed inside the
    traced program — e.g. an adaptive round's on-device
    ``graph.adaptive_round_matrices`` output.  The candidate set is the static
    complete graph; weights of unselected edges are zero.  Equivalent to
    ``consensus_lib.mix_stacked`` + the affinity-d update.
    Returns (mixed_params, d_bias).
    """
    self_w, nbr_idx, nbr_w, beta_p = _dense_operands(w_mat, beta_mat)
    return consensus_mix_stacked(
        stacked, self_w, nbr_idx, nbr_w, beta_p, local_steps, interpret=interpret
    )


@functools.partial(jax.jit, static_argnames=("local_steps", "interpret"))
def consensus_mix_push_sum_dense(
    stacked: PyTree,  # leaves (K, ...) — the DE-BIASED parameters
    mass: jax.Array,  # (K,) push-sum mass y
    w_mat: jax.Array,  # (K, K) TRACED column-stochastic push matrix
    beta_mat: jax.Array,  # (K, K) TRACED affinity matrix
    local_steps: int,
    *,
    interpret: bool | None = None,
) -> tuple[PyTree, PyTree, jax.Array]:
    """Dense-dynamic form of ``consensus_mix_push_sum_stacked``: one push-sum
    step + affinity d from TRACED dense matrices (adaptive directed rounds).
    Returns (mixed_params, d_bias, new_mass)."""
    self_w, nbr_idx, nbr_w, beta_p = _dense_operands(w_mat, beta_mat)
    return consensus_mix_push_sum_stacked(
        stacked, mass, self_w, nbr_idx, nbr_w, beta_p, local_steps,
        interpret=interpret,
    )


def sparse_from_matrices(w_mat: np.ndarray, beta_mat: np.ndarray, *, dmax: int | None = None):
    """Static (self_w, nbr_idx, nbr_w, beta_padded) from dense W and Beta.

    ``dmax`` pads the neighbor axis to a fixed width (weight-0 self-index
    padding) so rounds of differing degree share one kernel shape.  Padded
    slots read beta[i, i] = 0, so they contribute nothing to either output.
    """
    self_w, nbr_idx, nbr_w = consensus_lib.sparse_mixing(w_mat, dmax=dmax)
    k = nbr_idx.shape[0]
    beta_p = beta_mat[np.arange(k)[:, None], nbr_idx].astype(np.float32)
    return (
        jnp.asarray(self_w),
        jnp.asarray(nbr_idx),
        jnp.asarray(nbr_w),
        jnp.asarray(beta_p),
    )


def sparse_from_schedule(w_stack: np.ndarray, beta_stack: np.ndarray):
    """Stacked sparse form of a (R, K, K) W/Beta schedule.

    Returns (self_w (R, K), nbr_idx (R, K, D), nbr_w (R, K, D), beta (R, K, D))
    with D = the max degree across *all* rounds, so one kernel shape serves
    the whole schedule; callers select a round with ``arr[round_idx % R]``.
    """
    w_stack = np.asarray(w_stack)
    beta_stack = np.asarray(beta_stack)
    rounds = w_stack.shape[0]
    dmax = max(
        1, max(int(consensus_lib.mixing_degrees(w_stack[t]).max()) for t in range(rounds))
    )
    parts = [
        sparse_from_matrices(w_stack[t], beta_stack[t], dmax=dmax) for t in range(rounds)
    ]
    return tuple(jnp.stack([p[i] for p in parts]) for i in range(4))


def consensus_mix_schedule(
    stacked: PyTree,  # leaves (K, ...)
    round_idx: jax.Array,  # scalar int
    self_w_s: jax.Array,  # (R, K)
    nbr_idx_s: jax.Array,  # (R, K, D)
    nbr_w_s: jax.Array,  # (R, K, D)
    beta_s: jax.Array,  # (R, K, D)
    local_steps: int,
    *,
    interpret: bool | None = None,
) -> tuple[PyTree, PyTree]:
    """Schedule-aware gossip step: round ``round_idx`` of a time-varying graph.

    The round's sparse operands are dynamic slices of the stacked schedule,
    selected inside the traced program — no recompile, no host round-trip.
    """
    idx = jax.lax.rem(jnp.asarray(round_idx, jnp.int32), jnp.int32(self_w_s.shape[0]))
    return consensus_mix_stacked(
        stacked, self_w_s[idx], nbr_idx_s[idx], nbr_w_s[idx], beta_s[idx],
        local_steps, interpret=interpret,
    )


def consensus_mix_push_sum_schedule(
    stacked: PyTree,  # leaves (K, ...)
    mass: jax.Array,  # (K,)
    round_idx: jax.Array,  # scalar int
    self_w_s: jax.Array,  # (R, K)
    nbr_idx_s: jax.Array,  # (R, K, D)
    nbr_w_s: jax.Array,  # (R, K, D)
    beta_s: jax.Array,  # (R, K, D)
    local_steps: int,
    *,
    interpret: bool | None = None,
) -> tuple[PyTree, PyTree, jax.Array]:
    """Schedule-aware push-sum step: round ``round_idx`` of a (possibly
    directed) time-varying graph, selected inside the traced program."""
    idx = jax.lax.rem(jnp.asarray(round_idx, jnp.int32), jnp.int32(self_w_s.shape[0]))
    return consensus_mix_push_sum_stacked(
        stacked, mass, self_w_s[idx], nbr_idx_s[idx], nbr_w_s[idx], beta_s[idx],
        local_steps, interpret=interpret,
    )
