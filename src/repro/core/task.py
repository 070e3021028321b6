"""TrainTask: the registry-backed bundle wiring a model into the peer axis.

Before this module, ``launch.train.run_paper_experiment`` had an implicit
contract — "the loss is always the paper's 2NN MLP built in
``configs/p2pl_mnist.py``" — and the model registry
(``repro.models.registry``: transformer / mamba2 / rwkv6 / moe with their
Pallas kernels) was a disjoint world.  A ``TrainTask`` makes that contract
explicit: everything the P2P drivers need to train a model end-to-end, chosen
by name through ``P2PConfig.model``.

A task provides:

``init_params(rng) -> params``
    One PEER's parameter pytree (the drivers vmap it over K split keys).
``loss_fn(params, batch) -> scalar``
    One peer's training loss on one batch.  It is traced ONCE per run inside
    the shared round step (the one-compile rule), so it must be pure jax with
    no data-dependent python control flow.
``apply_fn(params, inputs) -> (N, C) logits``
    The eval head ``p2p.stratified_accuracy`` vmaps over the stacked fleet.
``make_peer_batches(parts, batch_size, *, seed) -> batcher``
    Batcher over the per-peer shards of ``data/partition.py``; its
    ``round_batches(T)`` returns a batch pytree whose leaves are (T, K, ...)
    device arrays — step-major then peer, the ``local_phase`` layout.
``prepare_eval(x) -> inputs``
    Maps raw evaluation images to the model's input format (identity for the
    MLP; pixel-stream tokenization for sequence models).

``mnist_mlp`` is the legacy path STRUCTURALLY: its callables ARE
``models.mlp.init_2nn / loss_2nn / apply_2nn`` and its batcher IS
``data.pipeline.PeerBatcher`` — not wrappers — so selecting it traces the
exact pre-TrainTask expression graph (the fp32 bit-parity booby trap, like
``compressor="none"`` and ``staleness_bound=0`` before it).

``rwkv6_seqmnist`` is the first real-model workload: RWKV6 (Finch) run as a
recurrent network over the pixel stream of sequential MNIST — each 2x2-pooled
image becomes a 196-token intensity sequence, classified from the final
recurrent state — built from ``models.registry.build_sequence_classifier``
on a reduced ``ModelConfig``, trained under gossip AND push_sum in both the
vmap and pod runtimes via the scan driver.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax

from repro.data import pipeline
from repro.models import mlp

PyTree = Any


@dataclasses.dataclass(frozen=True)
class TrainTask:
    """Everything the P2P drivers need to train one model family."""

    name: str
    init_params: Callable[[jax.Array], PyTree]
    loss_fn: Callable[[PyTree, Any], jax.Array]
    apply_fn: Callable[[PyTree, Any], jax.Array]
    make_peer_batches: Callable[..., Any]
    prepare_eval: Callable[[Any], Any]
    # None: the whole test set in ONE apply per peer (the legacy MLP eval
    # path, part of its bit-parity surface).  An int caps the eval minibatch:
    # sequence trunks materialize O(B * S * D)-and-worse intermediates, and
    # K peers x the full test set in one call OOMs on CI hosts.
    eval_batch_size: int | None = None
    # None: evaluate on the full test set.  An int subsamples it (seeded
    # permutation) — a 196-step recurrent forward over K peers x 10k test
    # sequences per eval round is minutes of CPU for a demo workload.
    eval_set_size: int | None = None
    description: str = ""


_BUILDERS: dict[str, Callable[[], TrainTask]] = {}
_CACHE: dict[str, TrainTask] = {}


def register_task(name: str, builder: Callable[[], TrainTask]) -> None:
    """Register a lazy task builder (built once, on first ``get_task``)."""
    if name in _BUILDERS:
        raise ValueError(f"task {name!r} already registered")
    _BUILDERS[name] = builder


def task_names() -> tuple[str, ...]:
    """Registered task names (no tasks are built)."""
    return tuple(sorted(_BUILDERS))


def get_task(name: str) -> TrainTask:
    """Build (once) and return the named task."""
    if name not in _BUILDERS:
        raise ValueError(f"unknown model {name!r}; one of {task_names()}")
    if name not in _CACHE:
        _CACHE[name] = _BUILDERS[name]()
    return _CACHE[name]


# ---------------------------------------------------------------------------
# mnist_mlp — the paper's 2NN, the structurally-identical legacy path
# ---------------------------------------------------------------------------


def _build_mnist_mlp() -> TrainTask:
    # the callables ARE the legacy ones — identity, not equivalence — so the
    # task-selected run traces the same program as the pre-TrainTask trainer
    return TrainTask(
        name="mnist_mlp",
        init_params=mlp.init_2nn,
        loss_fn=mlp.loss_2nn,
        apply_fn=mlp.apply_2nn,
        make_peer_batches=pipeline.PeerBatcher,
        prepare_eval=lambda x: x,
        description="the paper's 2NN MLP (784-200-200-10) on flat MNIST "
                    "images — the fp32 bit-parity legacy path",
    )


# ---------------------------------------------------------------------------
# rwkv6_seqmnist — RWKV6 in RNN mode over the pixel stream
# ---------------------------------------------------------------------------

# 2x2-pooled 28x28 -> 14x14 = 196 intensity tokens per image.  The classifier
# runs the trunk in RNN mode (token-sequential recurrence); chunk=49 tiles the
# sequence exactly (4 chunks, no padding) if the chunked scan is ever used.
SEQMNIST_POOL = 2
SEQMNIST_BINS = 16
_SEQMNIST_SEQ_LEN = (28 // SEQMNIST_POOL) ** 2


def seqmnist_model_config():
    """The reduced RWKV6 config of the sequential-MNIST task (CI-sized)."""
    from repro.configs.base import ModelConfig, SSMConfig

    return ModelConfig(
        name="rwkv6-seqmnist",
        family="rwkv6",
        num_layers=2,
        d_model=64,
        d_ff=128,
        vocab_size=SEQMNIST_BINS,
        ssm=SSMConfig(kind="rwkv6", state_dim=16, head_dim=16, chunk=49,
                      lora_rank=8),
        tie_embeddings=True,
        dtype="float32",
        remat=False,
    )


def _build_rwkv6_seqmnist() -> TrainTask:
    from repro.models import registry

    cfg = seqmnist_model_config()
    init, apply, loss = registry.build_sequence_classifier(cfg, num_classes=10)

    def make_peer_batches(parts, batch_size, *, seed=0, **kw):
        return pipeline.TokenSequenceBatcher(
            parts, batch_size, seed=seed,
            num_bins=SEQMNIST_BINS, pool=SEQMNIST_POOL, **kw,
        )

    return TrainTask(
        name="rwkv6_seqmnist",
        init_params=init,
        loss_fn=loss,
        apply_fn=apply,
        make_peer_batches=make_peer_batches,
        prepare_eval=lambda x: pipeline.images_to_tokens(
            x, num_bins=SEQMNIST_BINS, pool=SEQMNIST_POOL
        ),
        eval_batch_size=256,
        eval_set_size=512,
        description="RWKV6 (2 layers, d_model=64) as a recurrent net over "
                    f"the {_SEQMNIST_SEQ_LEN}-token pixel stream of "
                    "sequential MNIST, classified from the final state",
    )


register_task("mnist_mlp", _build_mnist_mlp)
register_task("rwkv6_seqmnist", _build_rwkv6_seqmnist)
