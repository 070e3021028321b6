"""Peer-stacked batch pipeline for the stacked P2P runtime.

Produces per-round batches of shape (T, K, B, ...) — step-major, then peer —
matching ``repro.core.p2p.local_phase``.  Each peer cycles through its own
local dataset with per-peer reshuffling at epoch boundaries (mini-batch SGD
as in the paper: B=10, one epoch = n_k/B iterations).
"""
from __future__ import annotations

from typing import Iterator

import numpy as np

from repro import telemetry


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
        self.rngs = [np.random.default_rng(seed + 7 * k) for k in range(len(parts))]
        self.orders = [rng.permutation(len(p[0])) for rng, p in zip(self.rngs, parts)]
        self.cursors = [0] * len(parts)

    @property
    def num_peers(self) -> int:
        return len(self.parts)

    def _next_indices(self, k: int) -> np.ndarray:
        n = len(self.parts[k][0])
        if n < self.b:
            # sample with replacement when the local set is tiny
            return self.rngs[k].integers(0, n, size=self.b)
        if self.cursors[k] + self.b > n:
            self.cursors[k] = 0
            if self.reshuffle:
                self.orders[k] = self.rngs[k].permutation(n)
        sel = self.orders[k][self.cursors[k] : self.cursors[k] + self.b]
        self.cursors[k] += self.b
        return sel

    def round_batches(self, local_steps: int) -> tuple[np.ndarray, np.ndarray]:
        """Batches for one round: (x (T,K,B,F), y (T,K,B)); the span
        ``data.round_batches`` and the counter ``data.samples``."""
        with telemetry.span("data.round_batches"):
            xs, ys = [], []
            for _t in range(local_steps):
                bx, by = [], []
                for k in range(self.num_peers):
                    sel = self._next_indices(k)
                    bx.append(self.parts[k][0][sel])
                    by.append(self.parts[k][1][sel])
                xs.append(np.stack(bx))
                ys.append(np.stack(by))
            out = np.stack(xs), np.stack(ys)
        telemetry.count("data.samples", local_steps * self.num_peers * self.b)
        return out

    def rounds(self, num_rounds: int, local_steps: int) -> Iterator[tuple[np.ndarray, np.ndarray]]:
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
    as the MLP.  ``round_batches(T)`` returns ``(tokens (T, K, B, L) int32,
    labels (T, K, B) int32)`` — the same two-leaf tuple contract, so the
    drivers' stacking and scan-chunk reshapes apply unchanged.
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

    def round_batches(self, local_steps: int) -> tuple[np.ndarray, np.ndarray]:
        return self.inner.round_batches(local_steps)

    def rounds(self, num_rounds: int, local_steps: int):
        return self.inner.rounds(num_rounds, local_steps)


def global_to_peer_batch(x: np.ndarray, num_peers: int) -> np.ndarray:
    """Split a global batch along axis 0 into a leading peer axis."""
    b = x.shape[0]
    assert b % num_peers == 0, f"global batch {b} not divisible by {num_peers} peers"
    return x.reshape(num_peers, b // num_peers, *x.shape[1:])
