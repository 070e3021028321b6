"""Inputs of every cell, drawn from the seed: the yardstick's own generator.

Nothing here imports the program.  The training data set stands in for MNIST
(10 classes of 28x28 images: a fixed smooth prototype per class, brightness
jitter and unit Gaussian noise, as ``repro.data.synthetic.mnist_like`` builds
it, drawn in float32 for a shorter set-up) and is fixed by ``DATA_SEED``; the
run's ``--seed`` draws the partition across peers, the weights and the batch
order.  The serving traffic (prompts, the model each request group is routed
to) is drawn from the seed here too.
"""
from __future__ import annotations

import numpy as np

DATA_SEED = 1234


def _smooth_field(rng: np.random.Generator, size: int = 28, cutoff: int = 6) -> np.ndarray:
    spec = np.zeros((size, size), np.complex128)
    spec[:cutoff, :cutoff] = rng.normal(size=(cutoff, cutoff)) + 1j * rng.normal(
        size=(cutoff, cutoff)
    )
    img = np.fft.ifft2(spec).real
    return (img - img.min()) / (img.max() - img.min() + 1e-9)


def mnist_like(num_train: int, num_test: int, *, num_classes: int = 10, noise: float = 1.0):
    """(x_train (N, 784) f32, y_train (N,) i32, x_test, y_test), fixed by DATA_SEED."""
    rng = np.random.default_rng(DATA_SEED)
    protos = np.stack([_smooth_field(rng) for _ in range(num_classes)])
    protos = protos.reshape(num_classes, -1).astype(np.float32)

    def sample(n, rng):
        y = rng.integers(0, num_classes, size=n).astype(np.int32)
        bright = rng.uniform(0.7, 1.3, size=(n, 1)).astype(np.float32)
        x = protos[y] * bright
        x += noise * rng.standard_normal(x.shape, dtype=np.float32)
        return x, y

    x_tr, y_tr = sample(num_train, np.random.default_rng(DATA_SEED + 1))
    x_te, y_te = sample(num_test, np.random.default_rng(DATA_SEED + 2))
    return x_tr, y_tr, x_te, y_te


def iid_parts(x, y, num_peers: int, seed: int):
    """Shuffle and split into equal shards (the paper's IID setting)."""
    idx = np.random.default_rng(seed).permutation(len(x))
    return [(x[s], y[s]) for s in np.array_split(idx, num_peers)]


def class_parts(x, y, peer_classes, samples_per_class: int, seed: int):
    """Peer k holds ``samples_per_class`` samples of each class it is given
    (the paper's pathological non-IID setting), in a seeded order."""
    rng = np.random.default_rng(seed)
    parts = []
    for classes in peer_classes:
        idx = np.concatenate(
            [rng.permutation(np.nonzero(y == c)[0])[:samples_per_class] for c in classes]
        )
        idx = rng.permutation(idx)
        parts.append((x[idx], y[idx]))
    return parts


def ring_class_pairs(num_peers: int):
    """Peer k sees classes (2k mod 10, 2k+1 mod 10): neighbours on a ring
    hold different classes."""
    return [((2 * k) % 10, (2 * k + 1) % 10) for k in range(num_peers)]


def zipf_choice(rng: np.random.Generator, num_items: int, s: float, size) -> np.ndarray:
    """Draws over ``num_items`` items with P(i) proportional to 1 / (i+1)^s."""
    p = 1.0 / np.arange(1, num_items + 1) ** s
    return rng.choice(num_items, size=size, p=p / p.sum()).astype(np.int32)
