"""Communication compression for the consensus phase (Sparse-Push's headline).

A ``Compressor`` shrinks the per-edge consensus message: instead of gossiping
raw fp32 parameter leaves, each peer broadcasts a compressed payload that the
receivers apply to a persistent *public estimate* of the sender's parameters
(CHOCO-SGD 1902.00340, Sparse-Push 2102.05715).  Every node — the sender
included — carries the same estimate stack ``x̂`` (the
``P2PState.compression`` tree, one dense copy per peer, warm-started at the
common initialization — see ``Compressor.init_estimate``); each
consensus step the sender ships ``C(x - x̂)`` and everyone advances
``x̂ <- x̂ + D(C(x - x̂))``.  The un-shipped part ``x - x̂`` IS the
error-feedback residual: it stays in the next difference and is re-compressed
every step, so the estimate converges to the parameters and the long-run
signal is conserved.  Mixing then runs on the dense estimates — this is what
makes top-k viable: decompressing a sparse payload *directly* as the
neighbor value zeroes most coordinates and shrinks every mix toward the
origin, while applying it as a sparse *update* to a dense running estimate
loses only the (fed-back) compression error.

Three implementations, in one registry mirroring ``core/protocols.py``:

    none  — the identity: runtimes detect ``identity = True`` and take the
            EXACT pre-compression code path (fp32 bit-identical by
            construction, zero overhead, no estimate state).  Its
            ``compress`` still exists so bytes accounting can price the
            uncompressed message.
    topk  — per-leaf top-k magnitude sparsification: keep the ``frac``
            largest-|value| coordinates of each (flattened) difference;
            payload = (values f32, indices int32) with leading peer axis.
            Decompress scatters into zeros, so kept slots round-trip EXACTLY
            and the estimate picks up the difference's largest coordinates
            bit for bit.
    qint8 — symmetric per-leaf int8 quantization of the difference: one fp32
            scale per peer row (``max|diff| / 127``) plus an int8 tensor; 4x
            fewer payload bytes, per-coordinate error bounded by
            ``scale / 2`` — and the difference (hence the scale) shrinks as
            the estimate converges.

Payloads are NamedTuples of arrays whose LEADING axis is the peer axis, so
the pod runtime can ppermute each payload array over the same ``PermLane``
structure it uses for raw leaves (``consensus.gather_peer_leaf``) — values,
indices, and scale ride the lanes instead of the fp32 tensor.  The push-sum
mass never rides here: it is a (K,) scalar lane, exchanged UNCOMPRESSED, so
mass conservation (sum y == K) is exact under any compressor.
"""

from __future__ import annotations

from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

PyTree = Any


class RawPayload(NamedTuple):
    """The uncompressed message (compressor="none"): the leaf itself, flat."""

    values: jax.Array  # (K, N) f32


class TopKPayload(NamedTuple):
    """Top-k sparsification: the kept coordinates of each flattened leaf."""

    values: jax.Array  # (K, M) f32 — signed values at the kept slots
    indices: jax.Array  # (K, M) int32 — flat coordinate of each kept slot


class QInt8Payload(NamedTuple):
    """Symmetric int8 quantization with one fp32 scale lane per peer row."""

    q: jax.Array  # (K, N) int8
    scale: jax.Array  # (K, 1) f32 — max|h| / 127 per row


def _flat(leaf: jax.Array) -> jax.Array:
    """(K, ...) leaf -> (K, N) f32 working view."""
    return leaf.astype(jnp.float32).reshape(leaf.shape[0], -1)


def _feat_size(like: jax.Array) -> int:
    return int(np.prod(like.shape[1:])) if like.ndim > 1 else 1


class Compressor:
    """One leaf-compression rule; stateless apart from the carried estimate."""

    name: str = "base"
    # identity compressors make the runtimes take the EXACT uncompressed code
    # path (the fp32 bit-parity guarantee is structural, not numerical)
    identity: bool = False

    def init_estimate(self, params: PyTree) -> PyTree:
        """The public-estimate stack carried in ``P2PState.compression``.

        WARM-STARTED at the initial (peer-stacked) parameters: the stack is
        built once on the host before any sharding, so every node holds the
        same deterministic estimate of every peer — the setup handshake every
        decentralized run already performs (with common-seed initialization
        it costs nothing on the wire).  Compressed payloads then only ever
        carry TRAINING DRIFT ``x - x̂``, which starts at zero instead of at
        the full parameter magnitude — a cold (zeros) start spends the first
        many rounds shipping the initialization itself through the sparsified
        wire, injecting estimate noise exactly when the non-IID peers most
        need consensus.  The error-feedback residual is implicit:
        ``params - estimate``.  ``()`` for the identity (no estimate to
        carry, no state-leaf overhead).
        """
        if self.identity:
            return ()
        return jax.tree.map(lambda x: jnp.array(x, copy=True), params)

    def compress(self, leaf: jax.Array) -> NamedTuple:
        """(K, ...) leaf -> payload NamedTuple of arrays with leading K axis."""
        raise NotImplementedError

    def decompress(self, payload: NamedTuple, like: jax.Array) -> jax.Array:
        """Payload -> the receivers' estimate, shaped ``(K_payload,) + like.shape[1:]``.

        ``like`` supplies the feature shape and dtype only; the leading axis
        comes from the payload (the pod runtime decompresses a gathered (K,
        ...) payload against its local (1, ...) block).  All-zero payload rows
        (peers this shard never heard from) decompress to zero rows, which
        meet zero mixing weights downstream.
        """
        raise NotImplementedError


class NoneCompressor(Compressor):
    """Identity: runtimes bypass compression entirely (``identity = True``).

    ``compress``/``decompress`` are still real (the flat fp32 leaf as payload)
    so bytes accounting and property tests can treat every compressor
    uniformly — the runtimes just never call them.
    """

    name = "none"
    identity = True

    def compress(self, leaf: jax.Array) -> RawPayload:
        return RawPayload(values=_flat(leaf))

    def decompress(self, payload: RawPayload, like: jax.Array) -> jax.Array:
        k = payload.values.shape[0]
        return payload.values.reshape((k,) + like.shape[1:]).astype(like.dtype)


class TopKCompressor(Compressor):
    """Per-leaf top-k magnitude sparsification (Sparse-Push / CHOCO style)."""

    name = "topk"

    def __init__(self, frac: float = 0.01):
        if not 0.0 < frac <= 1.0:
            raise ValueError(f"topk frac must be in (0, 1], got {frac}")
        self.frac = float(frac)

    def keep(self, n: int) -> int:
        """Kept coordinates for a leaf of N features (static, >= 1)."""
        return max(1, int(round(self.frac * n)))

    def compress(self, leaf: jax.Array) -> TopKPayload:
        flat = _flat(leaf)
        m = self.keep(flat.shape[1])
        # top-k by magnitude, payload carries the SIGNED values at those slots
        _, idx = jax.lax.top_k(jnp.abs(flat), m)
        vals = jnp.take_along_axis(flat, idx, axis=1)
        return TopKPayload(values=vals, indices=idx.astype(jnp.int32))

    def decompress(self, payload: TopKPayload, like: jax.Array) -> jax.Array:
        k = payload.values.shape[0]
        n = _feat_size(like)
        rows = jnp.arange(k, dtype=jnp.int32)[:, None]
        # top_k indices are distinct per row, so .set is scatter-safe; all-zero
        # payload rows write 0.0 at slot 0 repeatedly — still exactly zero
        out = jnp.zeros((k, n), jnp.float32)
        out = out.at[rows, payload.indices].set(payload.values)
        return out.reshape((k,) + like.shape[1:]).astype(like.dtype)


class QInt8Compressor(Compressor):
    """Symmetric per-leaf int8 quantization with an fp32 scale lane."""

    name = "qint8"

    def compress(self, leaf: jax.Array) -> QInt8Payload:
        flat = _flat(leaf)
        amax = jnp.max(jnp.abs(flat), axis=1, keepdims=True)  # (K, 1)
        scale = amax / 127.0
        safe = jnp.where(scale > 0.0, scale, 1.0)  # all-zero row -> q = 0
        q = jnp.clip(jnp.round(flat / safe), -127.0, 127.0).astype(jnp.int8)
        return QInt8Payload(q=q, scale=scale)

    def decompress(self, payload: QInt8Payload, like: jax.Array) -> jax.Array:
        k = payload.q.shape[0]
        out = payload.q.astype(jnp.float32) * payload.scale
        return out.reshape((k,) + like.shape[1:]).astype(like.dtype)


# ---------------------------------------------------------------------------
# Error feedback (estimate tracking)
# ---------------------------------------------------------------------------


def ef_compress_leaf(
    comp: Compressor, x: jax.Array, est: jax.Array
) -> tuple[NamedTuple, jax.Array]:
    """One estimate-tracking compression of a leaf: the payload is the
    compressed difference ``C(x - est)``; everyone (sender and receivers
    alike) advances the public estimate by its decompression.

    Returns ``(payload, est_new)`` with ``est_new = est + D(payload)`` — the
    new ``P2PState.compression`` leaf AND the dense value mixing uses for
    this sender.  The error-feedback residual ``x - est_new`` needs no
    separate state: it stays inside the next difference and is re-compressed
    every step (for top-k the payload picks the difference's largest-|.|
    coordinates exactly, so for a static ``x`` the estimate converges).
    """
    payload = comp.compress(x - est)
    return payload, est + comp.decompress(payload, x)


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------

_REGISTRY: dict[str, type[Compressor]] = {}


def register_compressor(cls: type[Compressor]) -> type[Compressor]:
    """Add a compressor class to the registry (name must be unique)."""
    if not cls.name or cls.name == "base":
        raise ValueError("compressor needs a distinct name")
    if cls.name in _REGISTRY:
        raise ValueError(f"compressor {cls.name!r} already registered")
    _REGISTRY[cls.name] = cls
    return cls


def compressor_names() -> tuple[str, ...]:
    return tuple(_REGISTRY)


def get_compressor(name: str, *, topk_frac: float = 0.01) -> Compressor:
    """Instantiate a registered compressor (``topk`` takes its kept fraction)."""
    try:
        cls = _REGISTRY[name]
    except KeyError:
        raise ValueError(
            f"unknown compressor {name!r}; one of {compressor_names()}"
        ) from None
    if cls is TopKCompressor:
        return cls(topk_frac)
    return cls()


def from_config(cfg) -> Compressor:
    """The config's compressor (duck-typed: needs ``.compressor``/``.topk_frac``,
    i.e. any ``repro.core.p2p.P2PConfig``)."""
    return get_compressor(cfg.compressor, topk_frac=cfg.topk_frac)


register_compressor(NoneCompressor)
register_compressor(TopKCompressor)
register_compressor(QInt8Compressor)
