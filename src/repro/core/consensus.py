"""Distributed average-consensus (gossip) operators.

Three execution forms of the same mathematical op — out_k = sum_j W[k,j] w_j:

1. **Stacked einsum** (`mix_stacked`, `mix_leaf`): peer parameters are a
   pytree whose leaves carry a leading K axis. Used for CPU experiments (vmap
   runtime) and for the ``peer_axis="data"`` sharded mode, where the K axis is
   sharded over the mesh and XLA lowers the einsum into the appropriate
   collectives.
2. **Sparse gather** (`mix_sparse`): padded neighbor-index form; O(K * deg)
   instead of O(K^2). Feeds the Pallas `consensus_mix` kernel.
3. **Mesh gathers** (`gather_peer_leaf`, `ring_gather_slots`): inside
   ``shard_map``, the neighbour rows a peer (``peer_axis="pod"``, one
   ``ppermute`` per schedule lane) or a block of peers (hierarchical, a ring
   of ``ppermute``s) needs, which the stacked forms then mix (see the
   exchanges of ``repro.core.p2p``).

All operate on arbitrary pytrees and preserve leaf dtypes (mixing is computed
in float32 and cast back, matching how one would do it on TPU to avoid bf16
accumulation error across many neighbors).

These are the *primitive* mixing ops consumed by the consensus protocols in
``repro.core.protocols``: gossip's ``mix`` is exactly ``mix_stacked`` with a
row-stochastic W, and push-sum reuses the same einsum/gather forms with
column-stochastic weights re-scaled by the per-peer mass (the fused variant
lives in ``repro.kernels.consensus_mix.ops.consensus_mix_push_sum_stacked``).
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro import telemetry

PyTree = Any


def mix_leaf(w_mat: jax.Array, leaf: jax.Array) -> jax.Array:
    """einsum over the leading peer axis, f32 accumulation (one leaf of
    ``mix_stacked``; public so leaf-pipelined consumers can call it per leaf)."""
    out = jnp.einsum(
        "kj,j...->k...",
        w_mat.astype(jnp.float32),
        leaf.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST,
    )
    return out.astype(leaf.dtype)


def mix_stacked(w_mat: jax.Array, stacked: PyTree) -> PyTree:
    """Apply mixing matrix across the leading K axis of every leaf."""
    return jax.tree.map(lambda x: mix_leaf(w_mat, x), stacked)


# ---------------------------------------------------------------------------
# Sparse (padded-neighbor) form
# ---------------------------------------------------------------------------


def mixing_degrees(w_mat: np.ndarray) -> np.ndarray:
    """Per-peer neighbor count of a dense mixing matrix: off-diagonal nonzeros.

    The single definition of sparsity shared by ``sparse_mixing`` and the
    schedule-wide padding in ``consensus_mix.ops.sparse_from_schedule``.
    """
    off_diag = w_mat - np.diag(np.diag(w_mat))
    return (off_diag != 0).sum(axis=1)


def sparse_mixing(
    w_mat: np.ndarray, *, dmax: int | None = None
) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Convert a dense mixing matrix to padded (self_w, nbr_idx, nbr_w).

    nbr_idx: (K, Dmax) int32, padded with the peer's own index (weight 0).
    Returns numpy arrays — static per topology, closed over by jit.
    ``dmax`` overrides the padding width so every round of a time-varying
    schedule shares one shape (the max degree across the schedule).
    """
    k = w_mat.shape[0]
    off_diag = w_mat - np.diag(np.diag(w_mat))
    deg = mixing_degrees(w_mat)
    need = max(int(deg.max()), 1) if k else 1
    if dmax is None:
        dmax = need
    elif dmax < need:
        raise ValueError(f"dmax={dmax} below the actual max degree {need}")
    nbr_idx = np.tile(np.arange(k, dtype=np.int32)[:, None], (1, dmax))
    nbr_w = np.zeros((k, dmax), dtype=np.float32)
    for i in range(k):
        nbrs = np.nonzero(off_diag[i])[0]
        nbr_idx[i, : len(nbrs)] = nbrs
        nbr_w[i, : len(nbrs)] = off_diag[i, nbrs]
    self_w = np.diag(w_mat).astype(np.float32)
    return self_w, nbr_idx, nbr_w


def mix_sparse(
    self_w: jax.Array, nbr_idx: jax.Array, nbr_w: jax.Array, stacked: PyTree
) -> PyTree:
    """out_k = self_w[k] * x_k + sum_d nbr_w[k, d] * x[nbr_idx[k, d]]."""

    def leaf(x):
        xf = x.astype(jnp.float32)
        gathered = xf[nbr_idx]  # (K, Dmax, ...)
        bcast = nbr_w.reshape(nbr_w.shape + (1,) * (x.ndim - 1))
        sw = self_w.reshape((-1,) + (1,) * (x.ndim - 1))
        out = sw * xf + jnp.sum(bcast * gathered, axis=1)
        return out.astype(x.dtype)

    return jax.tree.map(leaf, stacked)


# ---------------------------------------------------------------------------
# Hierarchical (vmap-within-device x shard_map) forms
# ---------------------------------------------------------------------------


def ring_gather_slots(
    x_block: jax.Array,  # (p, ...) this device's contiguous block of rows
    nbr_idx: jax.Array,  # (p, D) int32 GLOBAL neighbor indices
    axis_name: str,
    num_devices: int,
) -> jax.Array:
    """Gather neighbor rows by global index across a block-sharded peer axis.

    Peers live block-major on the mesh: global row g sits on device g // p at
    local slot g % p.  The device's block streams around the ring — step s
    holds device (me + s)'s block after s ppermutes — and each step fills the
    slots whose owner just arrived, via a LOCAL take.  Returns (p, D, ...):
    per-device memory O(p * D * feat) and total traffic O(K * feat) per
    device, never a (K, ...) or (K, K) intermediate — the segment-mode
    communication primitive for fleets too large to all-gather.
    """
    p = x_block.shape[0]
    me = jax.lax.axis_index(axis_name)
    owner = nbr_idx // p  # (p, D) device holding each neighbor
    local = nbr_idx % p
    feat_dims = (1,) * (x_block.ndim - 1)
    perm = [(i, (i - 1) % num_devices) for i in range(num_devices)]
    visiting = x_block
    out = jnp.zeros(nbr_idx.shape + x_block.shape[1:], x_block.dtype)
    for s in range(num_devices):
        src = jax.lax.rem(me + s, num_devices)
        take = visiting[local]  # (p, D, ...)
        out = jnp.where((owner == src).reshape(owner.shape + feat_dims), take, out)
        if s + 1 < num_devices:
            visiting = jax.lax.ppermute(visiting, axis_name, perm=perm)
    return out


def mix_slots(
    self_w: jax.Array,  # (p,)
    nbr_w: jax.Array,  # (p, D)
    x_block: jax.Array,  # (p, ...)
    gathered: jax.Array,  # (p, D, ...) from ring_gather_slots
) -> jax.Array:
    """Segment-sum mix over gathered neighbor slots:
    out_i = self_w[i] * x_i + sum_d nbr_w[i, d] * gathered[i, d].
    f32 accumulation, cast back — the jnp twin of the Pallas segment kernel
    (kernels/consensus_mix/segment.py); O(p * D * feat), no (K, K)."""
    xf = x_block.astype(jnp.float32)
    gf = gathered.astype(jnp.float32)
    sw = self_w.reshape((-1,) + (1,) * (x_block.ndim - 1))
    bw = nbr_w.reshape(nbr_w.shape + (1,) * (x_block.ndim - 1))
    out = sw * xf + jnp.sum(bw * gf, axis=1)
    return out.astype(x_block.dtype)


def slot_sum(nbr_w: jax.Array, gathered: jax.Array) -> jax.Array:
    """Weighted slot reduction without the self term (affinity-beta form):
    out_i = sum_d nbr_w[i, d] * gathered[i, d], f32, cast back."""
    gf = gathered.astype(jnp.float32)
    bw = nbr_w.reshape(nbr_w.shape + (1,) * (gathered.ndim - 2))
    return jnp.sum(bw * gf, axis=1).astype(gathered.dtype)


# ---------------------------------------------------------------------------
# Mesh-collective forms (inside shard_map over the peer axis)
# ---------------------------------------------------------------------------


def gather_peer_leaf(v: jax.Array, axis_name: str, lanes, num_peers: int) -> jax.Array:
    """Rebuild the stacked (K, ...) peer array of one leaf inside a shard_map
    block: (1, ...) block -> (K, ...).

    ``v`` is this peer's (1, ...) slice of the stacked peer axis; ``lanes`` is
    a static ``graph.PermLane`` tuple (see ``edge_color_lanes``).  One
    ppermute per lane sends the block along that lane's edges — the
    schedule-aware sparse communication pattern.  Rows of peers this shard
    never hears from stay ZERO; consumers multiply them by mixing weights
    that are zero on exactly those rows, so the zeros never contribute (and
    the reconstructed einsum stays bit-identical to the dense stacked form).
    One leaf at a time, so the pod consensus phase can pipeline leaves —
    issuing leaf ``i+1``'s ppermutes while leaf ``i`` is still mixing (see
    ``repro.core.p2p._Lanes``).
    """
    my = jax.lax.axis_index(axis_name)
    full = jnp.zeros((num_peers,) + v.shape[1:], v.dtype)
    full = full.at[my].set(v[0])
    for lane in lanes:
        recv = jax.lax.ppermute(v, axis_name, perm=list(lane.perm))
        src = jnp.asarray(lane.src_for_dst, jnp.int32)[my]
        # sentinel src == num_peers marks "no payload this lane": dropped
        full = full.at[src].set(recv[0], mode="drop")
    return full


# ---------------------------------------------------------------------------
# Max-norm synchronization (P2PL initialization, Ref. [6])
# ---------------------------------------------------------------------------


def max_norm_sync(stacked: PyTree) -> PyTree:
    """All peers adopt, per leaf, the initialization with the largest L2 norm.

    P2PL replaces plain random init with a one-round synchronization where the
    highest-norm initialization wins (larger-norm inits preserve gradient
    diversity better after averaging).  Communication cost: one scalar norm
    exchange + one parameter broadcast — modeled here as an argmax-gather over
    the stacked peer axis.
    """

    def leaf(x):
        k = x.shape[0]
        norms = jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32).reshape(k, -1)), axis=1))
        winner = jnp.argmax(norms)
        return jnp.broadcast_to(x[winner], x.shape).astype(x.dtype)

    return jax.tree.map(leaf, stacked)


def consensus_error(stacked: PyTree) -> jax.Array:
    """Model drift metric: mean_k ||w_k - w_bar||_2 over all leaves (f32).

    Run eagerly; the span ``consensus.consensus_error`` times the dispatch
    of its ops."""
    with telemetry.span("consensus.consensus_error"):
        leaves = jax.tree.leaves(stacked)
        k = leaves[0].shape[0]
        sq = jnp.zeros((k,), jnp.float32)
        for x in leaves:
            xf = x.astype(jnp.float32).reshape(k, -1)
            mean = jnp.mean(xf, axis=0, keepdims=True)
            sq = sq + jnp.sum(jnp.square(xf - mean), axis=1)
        return jnp.mean(jnp.sqrt(sq))


def pairwise_drift(stacked: PyTree) -> jax.Array:
    """Max over peer pairs of ||w_i - w_j||_2 — the paper's drift/divergence.

    Run eagerly; the span ``consensus.pairwise_drift`` times the dispatch
    of its ops."""
    with telemetry.span("consensus.pairwise_drift"):
        leaves = jax.tree.leaves(stacked)
        k = leaves[0].shape[0]
        sq = jnp.zeros((k, k), jnp.float32)
        for x in leaves:
            xf = x.astype(jnp.float32).reshape(k, -1)
            # ||x_i - x_j||^2 = ||x_i||^2 + ||x_j||^2 - 2 x_i . x_j
            n2 = jnp.sum(xf * xf, axis=1)
            sq = sq + n2[:, None] + n2[None, :] - 2.0 * (xf @ xf.T)
        return jnp.sqrt(jnp.maximum(sq, 0.0)).max()
