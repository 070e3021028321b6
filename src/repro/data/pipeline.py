"""Peer-stacked batch pipeline for the stacked P2P runtime.

Produces per-round batches of shape (T, K, B, ...) — step-major, then peer —
matching ``repro.core.p2p.local_phase``.  Each peer cycles through its own
local dataset with per-peer reshuffling at epoch boundaries (mini-batch SGD
as in the paper: B=10, one epoch = n_k/B iterations).

The shards live on the device: the first ``round_batches`` call uploads all
K shards once, as one (N, ...) array of inputs and one (N,) array of labels.
Each call then draws its batches' global row indices on the host, one
(T, K, B) int32 array, sends only that, and gathers the batches on the
device in one jitted call.  The counter ``data.h2d_bytes`` holds what the
pipeline sends host-to-device: the shards once, then the index arrays.
"""
from __future__ import annotations

from typing import Iterator

import jax
import jax.numpy as jnp
import numpy as np

from repro import telemetry


@jax.jit
def _gather(x: jax.Array, y: jax.Array, idx: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Rows ``idx`` (any shape) of the resident shards: x (*idx, ...), y (*idx)."""
    return jnp.take(x, idx, axis=0, mode="clip"), jnp.take(y, idx, axis=0, mode="clip")


class PeerBatcher:
    """Cyclic per-peer mini-batch sampler over heterogeneous local datasets."""

    def __init__(
        self,
        parts: list[tuple[np.ndarray, np.ndarray]],
        batch_size: int,
        *,
        seed: int = 0,
        reshuffle: bool = True,
    ):
        self.parts = parts
        self.b = batch_size
        self.reshuffle = reshuffle
        self.sizes = [len(p[0]) for p in parts]
        self.offsets = np.cumsum([0] + self.sizes[:-1])
        self.rngs = [np.random.default_rng(seed + 7 * k) for k in range(len(parts))]
        self.orders = [rng.permutation(n) for rng, n in zip(self.rngs, self.sizes)]
        self.cursors = [0] * len(parts)
        self._shards = None  # (x (N, ...), y (N,)) on the device, from the first call

    @property
    def num_peers(self) -> int:
        return len(self.parts)

    def _next_indices(self, k: int, steps: int) -> np.ndarray:
        """Peer k's next ``steps`` batches of local row indices, (steps, B),
        drawn from the peer's own stream in the per-step order."""
        n, b, rng = self.sizes[k], self.b, self.rngs[k]
        if n < b:
            # sample with replacement when the local set is tiny, one draw a step
            return np.stack([rng.integers(0, n, size=b) for _ in range(steps)])
        out = []
        while steps:
            if self.cursors[k] + b > n:
                self.cursors[k] = 0
                if self.reshuffle:
                    self.orders[k] = rng.permutation(n)
            c = self.cursors[k]
            m = min(steps, (n - c) // b)
            out.append(self.orders[k][c : c + m * b].reshape(m, b))
            self.cursors[k] += m * b
            steps -= m
        return np.concatenate(out)

    def _device_shards(self) -> tuple[jax.Array, jax.Array]:
        if self._shards is None:
            x = np.concatenate([p[0] for p in self.parts])
            y = np.concatenate([p[1] for p in self.parts])
            self._shards = jax.device_put((x, y))
            telemetry.count("data.h2d_bytes", sum(a.nbytes for a in self._shards))
        return self._shards

    def round_batches(self, local_steps: int) -> tuple[jax.Array, jax.Array]:
        """Batches for one round, as device arrays not waited on: (x (T,K,B,F),
        y (T,K,B)); the span ``data.round_batches`` and the counters
        ``data.samples`` and ``data.h2d_bytes``."""
        with telemetry.span("data.round_batches"):
            x, y = self._device_shards()
            idx = np.empty((local_steps, self.num_peers, self.b), np.int32)
            for k in range(self.num_peers):
                idx[:, k] = self._next_indices(k, local_steps) + self.offsets[k]
            out = _gather(x, y, idx)
        telemetry.count("data.samples", local_steps * self.num_peers * self.b)
        telemetry.count("data.h2d_bytes", idx.nbytes)
        return out

    def rounds(self, num_rounds: int, local_steps: int) -> Iterator[tuple[jax.Array, jax.Array]]:
        for _ in range(num_rounds):
            yield self.round_batches(local_steps)


def images_to_tokens(
    x: np.ndarray,
    *,
    num_bins: int = 16,
    pool: int = 2,
    side: int = 28,
) -> np.ndarray:
    """Flat images (N, side*side) f32 -> pixel-stream tokens (N, L) int32.

    The sequential-MNIST transform: ``pool`` x ``pool`` average pooling
    (784 -> 196 positions at the default), then each pooled intensity is
    quantized into one of ``num_bins`` levels over a FIXED affine range — a
    dataset constant, not a per-batch statistic, so the same pixel always
    maps to the same token and train/eval tokenizations agree.  The range
    [-3, 4] covers ``synthetic.mnist_like``'s prototype * brightness + unit
    Gaussian noise; values outside clip into the edge bins.
    """
    if side % pool:
        raise ValueError(f"pool={pool} does not divide side={side}")
    n = x.shape[0]
    imgs = np.asarray(x, np.float32).reshape(n, side, side)
    if pool > 1:
        s = side // pool
        imgs = imgs.reshape(n, s, pool, s, pool).mean(axis=(2, 4))
    lo, hi = -3.0, 4.0
    u = np.clip((imgs - lo) / (hi - lo), 0.0, np.nextafter(1.0, 0.0))
    return np.floor(u * num_bins).astype(np.int32).reshape(n, -1)


class TokenSequenceBatcher:
    """``PeerBatcher`` for sequence models: image shards, token batches.

    Tokenizes each peer's shard ONCE up front (``images_to_tokens``), then
    delegates sampling to an inner ``PeerBatcher`` — identical cursor /
    reshuffle / seed behavior, so sequence tasks see the same epoch structure
    as the MLP, and the same device-resident shards and device gather.
    ``round_batches(T)`` returns ``(tokens (T, K, B, L) int32, labels
    (T, K, B) int32)`` as device arrays — the same two-leaf tuple contract, so
    the drivers' stacking and scan-chunk reshapes apply unchanged.
    """

    def __init__(
        self,
        parts: list[tuple[np.ndarray, np.ndarray]],
        batch_size: int,
        *,
        seed: int = 0,
        reshuffle: bool = True,
        num_bins: int = 16,
        pool: int = 2,
    ):
        tok_parts = [
            (images_to_tokens(px, num_bins=num_bins, pool=pool),
             np.asarray(py, np.int32))
            for px, py in parts
        ]
        self.inner = PeerBatcher(tok_parts, batch_size, seed=seed,
                                 reshuffle=reshuffle)

    @property
    def num_peers(self) -> int:
        return self.inner.num_peers

    def round_batches(self, local_steps: int) -> tuple[jax.Array, jax.Array]:
        return self.inner.round_batches(local_steps)

    def rounds(self, num_rounds: int, local_steps: int):
        return self.inner.rounds(num_rounds, local_steps)


def global_to_peer_batch(x: np.ndarray, num_peers: int) -> np.ndarray:
    """Split a global batch along axis 0 into a leading peer axis."""
    b = x.shape[0]
    assert b % num_peers == 0, f"global batch {b} not divisible by {num_peers} peers"
    return x.reshape(num_peers, b // num_peers, *x.shape[1:])
