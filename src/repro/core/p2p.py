"""The paper's algorithm family, as one parameterized implementation.

P2PL with Affinity (Sec. IV-A) subsumes every baseline in the paper:

    algorithm          T      S    momentum  max-norm-sync  d bias  b bias
    -----------------  -----  ---  --------  -------------  ------  ------
    dsgd               1      1    optional  no             0       0
    local_dsgd         T > 1  1    optional  no             0       0
    p2pl               T > 1  S    yes       yes            0       0
    p2pl_affinity      T > 1  S    optional  yes            yes     optional
    isolated           T > 1  0    optional  no             0       0

Learning phase (Eq. 3):   w <- w - eta * grad F_k(w) + eta_d * d_k
Consensus phase (Eq. 4):  w_k <- sum_j alpha_kj w_j + eta_b * b_k
Affinity biases (Sec. IV-A, "one possible choice", which Sec. V-C uses):
    d_k <- (1/T) sum_j beta_kj (w_j - w_k)   (computed during consensus)
    b_k <- (1/S) w_k                         (computed during local phase)

This module is the *stacked* runtime: every state leaf carries a leading K
(peer) axis.  Its execution modes share the math bit for bit:

  * ``make_round_fn`` — the K axis is vmapped (CPU experiments); the mix is a
    dense (K, K) einsum.
  * ``make_sharded_round_fn`` — the K axis is ``shard_map``'d over a real mesh
    (``peer_axis="pod"``): each mesh slice holds ONE peer's replica, local
    phases run embarrassingly parallel, and the schedule-aware mix lowers to
    ``ppermute`` sends along the round's edges (``graph.schedule_lanes``),
    leaf-pipelined so the next leaf's sends overlap the current leaf's mix.
    With ``peers_per_device > 1`` each slice holds a block of peers (the
    hierarchical runtime).  See repro/launch/train.py (``--peer-axis pod``)
    for the production path.

Every mode runs the same round step (``_make_round_step``) and the same
consensus phase (``consensus_phase``): what differs is the EXCHANGE, how a
block of peers sees its neighbours (``_Exchange`` and its subclasses), and,
picked from the config, the WIRE stage: what receivers see of a sender.
Each mode dispatches one jitted round per call; ``make_scan_driver`` wraps
the round step in a ``lax.scan`` over a whole eval-period chunk of rounds
(donated state buffers, stacked per-round metrics) — one dispatch and at most
one host transfer per chunk, bit-identical results.

The consensus step itself is pluggable (``P2PConfig.protocol``, see
repro/core/protocols.py): ``gossip`` is the paper's row-stochastic mix and
keeps ``P2PState.protocol == ()`` (stateless, bit-identical to the
pre-protocol runtime); ``push_sum`` carries a per-peer scalar mass in
``P2PState.protocol`` (a ``PushSumState``) and runs column-stochastic
push-sum so *directed* and churning ``GraphSchedule``s average correctly.
Either way every round indexes the protocol's stacked (R, K, K) constants
with ``round_idx % R`` inside one jitted program.

Topologies themselves may be *state-dependent* (``cfg.schedule ==
"adaptive"``): instead of indexing a pretraced stack, the round step computes
its (K, K) W/Beta on device from the previous round's per-peer losses and a
PRNG key carried in ``P2PState.adaptive`` (an ``AdaptiveState``) via
``graph.adaptive_round_matrices`` — loss-proximity / random / eps-greedy
partner matching à la Onoszko et al., preserving the one-compile property in
all four {vmap, pod} x {python, scan} driver cells.
"""
from __future__ import annotations

import dataclasses
import functools
import warnings
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import compression as compression_lib
from repro import telemetry
from repro.core import consensus as consensus_lib
from repro.core import features as features_lib
from repro.core import graph as graph_lib
from repro.core import protocols as protocols_lib

PyTree = Any
LossFn = Callable[[PyTree, Any], jax.Array]  # (per-peer params, per-peer batch) -> scalar

ALGORITHMS = ("dsgd", "local_dsgd", "p2pl", "p2pl_affinity", "isolated")


def resolve_loss_fn(task_or_loss) -> LossFn:
    """A ``core.task.TrainTask`` or a bare loss callable -> the loss callable.

    Every driver entry point (``local_phase``, ``run_round``, ``make_*``)
    accepts either form; a task contributes exactly its ``loss_fn``
    attribute — no wrapper — so passing ``get_task("mnist_mlp")`` traces the
    IDENTICAL program as passing ``models.mlp.loss_2nn`` directly (the
    bit-parity contract of the legacy task).
    """
    loss_fn = getattr(task_or_loss, "loss_fn", None)
    return task_or_loss if loss_fn is None else loss_fn


def resolve_init_fn(task_or_init) -> Callable[[jax.Array], PyTree]:
    """A ``core.task.TrainTask`` or a bare per-peer init callable -> the init."""
    init_fn = getattr(task_or_init, "init_params", None)
    return task_or_init if init_fn is None else init_fn

# Config-declared per-peer compute profiles (``P2PConfig.steps_profile``):
# "uniform" is the bulk-synchronous baseline (every peer runs the full T local
# steps and publishes every round — structurally the legacy code path);
# "straggler" slows the last ``round(K * straggler_frac)`` peers down by
# ``straggler_period`` (fewer local steps per round, one publication every
# ``straggler_period`` rounds); "linear" spreads compute speeds linearly from
# 1 down to ``1 / straggler_period`` with every peer still publishing every
# round (heterogeneous steps only, no staleness).
STEPS_PROFILES = ("uniform", "straggler", "linear")


@dataclasses.dataclass(frozen=True)
class P2PConfig:
    """Hyperparameters of the P2PL-with-Affinity family."""

    algorithm: str = "p2pl_affinity"
    num_peers: int = 2
    local_steps: int = 1  # T
    consensus_steps: int = 1  # S
    lr: float = 0.01  # eta
    momentum: float = 0.0  # mu (PyTorch-default Polyak: buf = mu*buf + g; w -= lr*buf)
    eta_d: float = 1.0  # learning-phase bias step size
    eta_b: float = 0.0  # consensus-phase bias step size (paper's experiments: b = 0)
    topology: str = "complete"
    mixing: str = "data_weighted"
    consensus_step_size: float = 1.0  # epsilon_k
    max_norm_init: bool = False
    erdos_renyi_p: float = 0.3
    graph_seed: int = 0
    protocol: str = "gossip"  # one of protocols_lib.protocol_names()
    # -- time-varying communication (GraphSchedule) -------------------------
    schedule: str = "static"  # one of graph_lib.SCHEDULES, or "adaptive"
    schedule_rounds: int = 16  # period R of a stochastic schedule (cycled)
    link_survival_prob: float = 0.8  # q for schedule="link_dropout"
    peer_online_prob: float = 0.8  # for schedule="peer_churn"
    schedule_seed: int = 0
    round_robin_topologies: tuple[str, ...] = ()  # named topologies for "round_robin"
    # -- adaptive (state-dependent) partner selection, schedule="adaptive" --
    partner_rule: str = "loss_proximity"  # one of graph_lib.ADAPTIVE_RULES
    adaptive_eps: float = 0.1  # exploration probability for "eps_greedy"
    adaptive_seed: int = 0  # seeds the PRNG key threaded through P2PState
    # -- consensus-payload compression (repro/compression) ------------------
    compressor: str = "none"  # one of compression_lib.compressor_names()
    topk_frac: float = 0.01  # kept fraction per leaf for compressor="topk"
    # -- asynchronous rounds: compute profile + bounded-staleness gossip ----
    steps_profile: str = "uniform"  # one of STEPS_PROFILES
    staleness_bound: int = 0  # max snapshot age in rounds; 0 = synchronous
    staleness_decay: float = 0.5  # weight decay base per round of staleness
    straggler_frac: float = 0.25  # slow-peer fraction ("straggler" profile)
    straggler_period: int = 4  # slowdown factor of the slowest peer
    # -- training task (core/task.py registry): what the peers train --------
    model: str = "mnist_mlp"  # one of task.task_names()

    def __post_init__(self):
        """Validate the config and reject unsupported feature compositions."""
        if self.algorithm not in ALGORITHMS:
            raise ValueError(f"unknown algorithm {self.algorithm!r}")
        if self.algorithm == "dsgd" and (self.local_steps != 1 or self.consensus_steps != 1):
            raise ValueError("dsgd fixes T = S = 1")
        if self.algorithm == "isolated" and self.consensus_steps != 0:
            raise ValueError("isolated fixes S = 0")
        if self.local_steps < 1:
            raise ValueError("need at least one local step per round")
        if self.protocol not in protocols_lib.protocol_names():
            raise ValueError(
                f"unknown protocol {self.protocol!r}; one of "
                f"{protocols_lib.protocol_names()}"
            )
        if self.schedule not in graph_lib.SCHEDULES + ("adaptive",):
            raise ValueError(
                f"unknown schedule {self.schedule!r}; one of "
                f"{graph_lib.SCHEDULES + ('adaptive',)}"
            )
        if self.schedule_rounds < 1:
            raise ValueError("schedule_rounds must be >= 1")
        if self.partner_rule not in graph_lib.ADAPTIVE_RULES:
            raise ValueError(
                f"unknown partner_rule {self.partner_rule!r}; one of "
                f"{graph_lib.ADAPTIVE_RULES}"
            )
        if not 0.0 <= self.adaptive_eps <= 1.0:
            raise ValueError("adaptive_eps must be in [0, 1]")
        if self.schedule == "adaptive" and self.num_peers < 2:
            raise ValueError("adaptive partner selection needs at least two peers")
        if self.compressor not in compression_lib.compressor_names():
            raise ValueError(
                f"unknown compressor {self.compressor!r}; one of "
                f"{compression_lib.compressor_names()}"
            )
        if not 0.0 < self.topk_frac <= 1.0:
            raise ValueError("topk_frac must be in (0, 1]")
        if self.steps_profile not in STEPS_PROFILES:
            raise ValueError(
                f"unknown steps_profile {self.steps_profile!r}; one of "
                f"{STEPS_PROFILES}"
            )
        if self.staleness_bound < 0:
            raise ValueError("staleness_bound must be >= 0 (0 = synchronous)")
        if not 0.0 < self.staleness_decay <= 1.0:
            raise ValueError("staleness_decay must be in (0, 1]")
        if not 0.0 < self.straggler_frac <= 1.0:
            raise ValueError("straggler_frac must be in (0, 1]")
        if self.straggler_period < 1:
            raise ValueError("straggler_period must be >= 1")
        from repro.core import task as task_lib  # lazy: avoids import weight

        if self.model not in task_lib.task_names():
            raise ValueError(
                f"unknown model {self.model!r}; one of {task_lib.task_names()}"
            )
        # every pairwise composition rule lives in the ONE declarative table
        # (core/features.py) — config-level pairs fire here, runtime-level
        # pairs (e.g. x hierarchical) fire where peers_per_device is known
        features_lib.check_config(self)
        if self.schedule == "round_robin" and not self.round_robin_topologies:
            raise ValueError("round_robin schedule needs round_robin_topologies")
        object.__setattr__(
            self, "round_robin_topologies", tuple(self.round_robin_topologies)
        )
        for topo in self.round_robin_topologies:
            if not isinstance(topo, str):
                raise ValueError(
                    f"round_robin_topologies must be topology names, got {topo!r}"
                )
            if topo not in graph_lib.TOPOLOGIES:
                raise ValueError(
                    f"unknown round_robin topology {topo!r}; one of "
                    f"{graph_lib.TOPOLOGIES}"
                )

    @property
    def use_affinity_d(self) -> bool:
        """Whether the learning-phase affinity bias d (Eq. 3) is active."""
        return self.algorithm == "p2pl_affinity" and self.eta_d != 0.0

    @property
    def use_affinity_b(self) -> bool:
        """Whether the consensus-phase affinity bias b (Eq. 4) is active."""
        return self.algorithm == "p2pl_affinity" and self.eta_b != 0.0

    @property
    def use_max_norm_init(self) -> bool:
        """Whether peers synchronize to the max-norm init (Sec. IV-A)."""
        return self.max_norm_init or self.algorithm in ("p2pl", "p2pl_affinity")

    @property
    def use_async(self) -> bool:
        """Whether any asynchronous-round machinery is active.

        True iff the round is NOT the bulk-synchronous baseline: either
        consensus mixes bounded-staleness snapshots (``staleness_bound > 0``)
        or peers run heterogeneous local step counts (``steps_profile !=
        "uniform"``).  False means the legacy synchronous code path runs
        structurally unchanged (the fp32 bit-identity contract of
        ``staleness_bound=0``).
        """
        return self.staleness_bound > 0 or self.steps_profile != "uniform"


def compute_profile(cfg: P2PConfig) -> tuple[np.ndarray, np.ndarray]:
    """Per-peer compute profile of a config: ``(steps_k, period_k)``.

    Host-side (numpy, trace-time constant) arrays of shape (K,):

    ``steps_k``   int32 — local SGD steps peer k completes per round
                  (``<= cfg.local_steps``; the local-phase scan still runs
                  the full T iterations, peers past their budget hold their
                  parameters fixed so every runtime keeps one static shape).
    ``period_k``  int32 — rounds between peer k's snapshot publications: a
                  peer at speed ``1 / period_k`` finishes a local phase every
                  ``period_k`` rounds of fast-peer wall-clock.  Delivery is
                  additionally forced whenever a snapshot would otherwise
                  exceed ``cfg.staleness_bound`` rounds of age.

    Invariants: every entry of ``steps_k`` is >= 1 and every entry of
    ``period_k`` is >= 1; the "uniform" profile returns (T, 1) for every peer.
    """
    k, t = cfg.num_peers, cfg.local_steps
    steps = np.full((k,), t, np.int32)
    period = np.ones((k,), np.int32)
    if cfg.steps_profile == "straggler":
        n_slow = max(1, int(round(k * cfg.straggler_frac)))
        slow = np.arange(k) >= k - n_slow
        steps[slow] = max(1, t // cfg.straggler_period)
        period[slow] = cfg.straggler_period
    elif cfg.steps_profile == "linear":
        speed = np.linspace(1.0, 1.0 / cfg.straggler_period, k)
        steps = np.maximum(1, np.round(t * speed)).astype(np.int32)
    return steps, period


class AdaptiveState(NamedTuple):
    """Run state of the adaptive (state-dependent) partner selection.

    Both leaves carry the stacked leading K axis like every other state leaf
    (one row per peer in the vmap runtime, a (1, ...) block per mesh slice in
    the pod runtime), so the existing sharding specs, scan carry, and buffer
    donation apply unchanged:

    ``key``         (K, 2) uint32 — the PRNG key driving partner randomness,
                    replicated row-wise (every peer holds the SAME key, so all
                    peers derive the SAME matching with no extra traffic); one
                    split is consumed per round inside the jitted step.
    ``last_losses`` (K,) f32 — each peer's mean training loss of the previous
                    round, the selection signal of loss-proximity pairing.  In
                    the pod runtime this is the "cheap K-vector" exchanged per
                    round: one all_gather of K scalars.
    """

    key: jax.Array  # (K, 2) uint32, identical rows
    last_losses: jax.Array  # (K,) f32


class StalenessState(NamedTuple):
    """Bounded-staleness delivery buffer (``cfg.staleness_bound > 0``).

    Sender-side snapshot model: a straggling peer fails to publish to ALL of
    its out-neighbors at once, so one buffered snapshot per SENDER is exactly
    the per-neighbor "last received state" — every receiver of peer j holds
    the same stale copy — at O(params) instead of O(K * params) memory.  Both
    leaves carry the stacked leading K axis (a (1, ...) block per mesh slice
    in the pod runtime), so sharding specs, scan carry, and buffer donation
    apply unchanged:

    ``published``  params-shaped pytree — each sender's last published
                   parameter snapshot, the source of every OFF-diagonal
                   consensus term while the sender is between publications
                   (the self term always uses the receiver's live params).
    ``age``        (K,) int32 — rounds since each snapshot was taken.
                   Invariant: ``age <= cfg.staleness_bound`` after every
                   round (delivery is forced before the bound is crossed).
    """

    published: PyTree
    age: jax.Array  # (K,) int32


class P2PState(NamedTuple):
    """Stacked peer state; every leaf has leading axis K.

    ``protocol`` holds the consensus protocol's own state: ``()`` for gossip
    (stateless), ``protocols.PushSumState(mass=(K,))`` for push_sum — the
    per-peer scalar mass whose ratio de-biases the parameters.  It rides
    through the jitted round like any other leaf.  ``adaptive`` is ``()``
    unless ``cfg.schedule == "adaptive"``, in which case it carries the
    ``AdaptiveState`` (PRNG key + previous-round per-peer losses) that the
    round step consumes to build the round's topology on device.
    ``compression`` is ``()`` unless ``cfg.compressor != "none"``, in which
    case it carries the CHOCO-style public-estimate stack (zeros_like params
    at init): every node's dense running estimate of every peer's parameters,
    advanced by the decompressed payloads each consensus step — the
    error-feedback residual is implicitly ``params - estimate``.  In the
    sharded runtime this tree is REPLICATED per device, not peer-sharded
    (``sharding.specs.peer_stacked_pspecs`` special-cases it): receivers need
    every sender's estimate, and all replicas advance identically because
    they see the same payloads.
    ``staleness`` is ``()`` unless ``cfg.staleness_bound > 0``, in which case
    it carries the ``StalenessState`` (each sender's last published snapshot
    + its integer age) that bounded-staleness consensus mixes in place of the
    live neighbor parameters.  Unlike ``compression`` it IS peer-sharded in
    the pod runtime (published rows ride the same ppermute lanes as live
    parameters; only the (K,) ages are all-gathered).
    """

    params: PyTree
    momentum: PyTree
    d_bias: PyTree  # affinity learning-phase bias (Eq. 3)
    b_bias: PyTree  # affinity consensus-phase bias (Eq. 4)
    round_idx: jax.Array  # scalar int32
    protocol: PyTree = ()  # consensus-protocol state (see protocols.py)
    adaptive: PyTree = ()  # AdaptiveState for schedule="adaptive", else ()
    compression: PyTree = ()  # public-estimate stack for cfg.compressor != "none"
    staleness: PyTree = ()  # StalenessState for cfg.staleness_bound > 0, else ()


def build_schedule(cfg: P2PConfig) -> graph_lib.GraphSchedule:
    """The config's communication-graph schedule (period 1 for "static")."""
    build = lambda topo: graph_lib.build_graph(  # noqa: E731
        topo, cfg.num_peers, p=cfg.erdos_renyi_p, seed=cfg.graph_seed
    )
    if cfg.schedule == "adaptive":
        raise ValueError(
            "schedule='adaptive' has no pretraced graph sequence: each "
            "round's topology is computed on device from run state "
            "(graph.adaptive_round_matrices inside the jitted round step); "
            "there is no GraphSchedule to build"
        )
    if cfg.schedule == "static":
        return graph_lib.static_schedule(build(cfg.topology))
    if cfg.schedule == "link_dropout":
        return graph_lib.link_dropout_schedule(
            build(cfg.topology), cfg.link_survival_prob, cfg.schedule_rounds,
            seed=cfg.schedule_seed,
        )
    if cfg.schedule == "random_matching":
        return graph_lib.random_matching_schedule(
            cfg.num_peers, cfg.schedule_rounds, seed=cfg.schedule_seed
        )
    if cfg.schedule == "one_way_matching":
        return graph_lib.one_way_matching_schedule(
            cfg.num_peers, cfg.schedule_rounds, seed=cfg.schedule_seed
        )
    if cfg.schedule == "peer_churn":
        return graph_lib.peer_churn_schedule(
            build(cfg.topology), cfg.peer_online_prob, cfg.schedule_rounds,
            seed=cfg.schedule_seed,
        )
    # round_robin (validated in __post_init__)
    return graph_lib.round_robin_schedule(
        [build(t) for t in cfg.round_robin_topologies]
    )


def mixing_constants(
    cfg: P2PConfig, data_sizes: np.ndarray | None = None
) -> tuple[np.ndarray, np.ndarray, graph_lib.GraphSchedule]:
    """Stacked per-round row-stochastic (W, Beta, schedule) for a config.

    The pre-protocol entry point, equivalent to the gossip protocol's
    ``constants``: returns (R, K, K) numpy stacks — R = 1 for the static
    schedule — that the jitted round fn closes over and indexes with
    ``round_idx % R``, so a time-varying run still compiles exactly once.
    """
    sched = build_schedule(cfg)
    w, beta = graph_lib.schedule_matrices(
        sched, cfg.mixing, data_sizes=data_sizes,
        consensus_step_size=cfg.consensus_step_size,
    )
    return w, beta, sched


def protocol_constants(
    cfg: P2PConfig, data_sizes: np.ndarray | None = None
) -> tuple[protocols_lib.ProtocolConstants, graph_lib.GraphSchedule]:
    """Stacked (R, K, K) round constants of the config's consensus protocol."""
    sched = _checked_schedule(cfg)
    consts = protocols_lib.get_protocol(cfg.protocol).constants(
        sched, cfg.mixing, data_sizes=data_sizes,
        consensus_step_size=cfg.consensus_step_size,
    )
    return consts, sched


def _checked_schedule(cfg: P2PConfig) -> graph_lib.GraphSchedule:
    """``build_schedule``, warning when the protocol is biased on it."""
    sched = build_schedule(cfg)
    if sched.directed and not protocols_lib.get_protocol(cfg.protocol).directed_capable:
        warnings.warn(
            f"protocol {cfg.protocol!r} on a directed schedule "
            f"({sched.name!r}): a row-stochastic consensus point is biased on "
            "asymmetric graphs — use protocol='push_sum' unless the bias is "
            "deliberate",
            stacklevel=3,
        )
    return sched


def init_state(
    rng: jax.Array,
    init_fn: Callable[[jax.Array], PyTree],
    cfg: P2PConfig,
    data_sizes: np.ndarray | None = None,
) -> P2PState:
    """Independent per-peer init (PyTorch-style default), then optional max-norm sync.

    ``data_sizes`` seeds the protocol state — for push_sum, initial mass
    proportional to n_k makes the de-biased estimates track the
    *data-weighted* parameter average (uniform mass without it).

    ``init_fn`` may be a bare per-peer init callable or a
    ``core.task.TrainTask`` (its ``init_params`` is used).
    """
    init_fn = resolve_init_fn(init_fn)
    keys = jax.random.split(rng, cfg.num_peers)
    params = jax.vmap(init_fn)(keys)
    if cfg.use_max_norm_init:
        params = consensus_lib.max_norm_sync(params)
    zeros = jax.tree.map(jnp.zeros_like, params)
    proto = protocols_lib.get_protocol(cfg.protocol)
    adaptive: PyTree = ()
    if cfg.schedule == "adaptive":
        # every peer holds the SAME key (replicated rows), so all peers derive
        # the same matching each round; losses start at 0, so round 0's
        # loss-proximity matching is the deterministic tie-break pairing
        sel_key = jax.random.PRNGKey(cfg.adaptive_seed)
        adaptive = AdaptiveState(
            key=jnp.broadcast_to(sel_key[None, :], (cfg.num_peers, 2)),
            last_losses=jnp.zeros((cfg.num_peers,), jnp.float32),
        )
    comp = compression_lib.from_config(cfg)
    staleness: PyTree = ()
    if cfg.staleness_bound > 0:
        # warm start: every sender's first snapshot is its (possibly
        # max-norm-synced) init, age 0 — exactly what a synchronous round 0
        # would deliver.  jnp.copy, not an alias: the scan driver donates the
        # state, and a buffer appearing under two leaves cannot be donated
        staleness = StalenessState(
            published=jax.tree.map(jnp.copy, params),
            age=jnp.zeros((cfg.num_peers,), jnp.int32),
        )
    return P2PState(
        params=params,
        momentum=zeros,
        d_bias=jax.tree.map(jnp.zeros_like, params),
        b_bias=jax.tree.map(jnp.zeros_like, params),
        round_idx=jnp.zeros((), jnp.int32),
        protocol=proto.init_state(params, data_sizes),
        adaptive=adaptive,
        compression=comp.init_estimate(params),
        staleness=staleness,
    )


# ---------------------------------------------------------------------------
# Learning phase (Eq. 3)
# ---------------------------------------------------------------------------


@telemetry.scoped("repro.local")
def _local_phase_stats(
    state: P2PState,
    loss_fn: LossFn,
    batches: PyTree,
    cfg: P2PConfig,
    *,
    axis_name: str | None = None,
    steps_k: jax.Array | None = None,
) -> tuple[P2PState, jax.Array]:
    """``local_phase`` returning the full (T, K) per-step per-peer losses.

    The public ``local_phase`` reduces them to the (T,) per-step mean; the
    adaptive schedule path needs the K axis intact (each peer's mean loss is
    the next round's partner-selection signal), so the scan body lives here
    and both consumers apply their own reduction to the SAME materialized
    buffer — which is what keeps the reported losses bit-identical across the
    runtimes and drivers.

    ``axis_name`` is set by the sharded runtime, where K is a mesh axis and
    the leaves seen here are (1, ...) blocks: the (T, 1) per-step losses then
    all-gather the K per-peer scalars, so any later reduction runs over the
    same (T, K) buffer — and produces the same bits — as the vmap runtime.

    ``steps_k`` (int32, leading axis matching the stacked leaves: (K,) in the
    vmap runtime, this peer's (1,) block in the pod runtime) caps peer k at
    ``steps_k[k]`` local updates: the scan still runs the full T iterations —
    one static shape for every compute profile — but iterations at or past a
    peer's budget hold its parameters and momentum fixed (``jnp.where`` on
    the traced step index, so heterogeneous profiles share one compile).
    Losses keep reporting all T slots; a finished peer re-reports its frozen
    parameters' loss on each later step's batch.  ``None`` (the "uniform"
    profile) is the structurally unmasked legacy scan — the bit-identity
    baseline.
    """
    loss_fn = resolve_loss_fn(loss_fn)
    # one forward serves both the loss value and the gradient: cheaper than
    # separate vmap(loss)/vmap(grad) passes, and it pins the loss to the same
    # expression graph in the vmap and shard_map runtimes (a standalone
    # vmap(loss_fn) fuses differently at batch K than at batch 1, breaking
    # the runtimes' bit-parity contract on the reported losses)
    value_and_grad_fn = jax.value_and_grad(loss_fn)

    def step(carry, xs):
        params, mom = carry
        batch_t = xs if steps_k is None else xs[0]
        losses, grads = jax.vmap(value_and_grad_fn)(params, batch_t)
        if cfg.momentum:
            new_mom = jax.tree.map(lambda m, g: cfg.momentum * m + g, mom, grads)
            update = new_mom
        else:
            new_mom = mom
            update = grads
        if cfg.use_affinity_d:
            new_params = jax.tree.map(
                lambda w, u, d: w - cfg.lr * u + cfg.eta_d * d,
                params,
                update,
                state.d_bias,  # d fixed during the local phase (Sec. IV-A)
            )
        else:
            new_params = jax.tree.map(lambda w, u: w - cfg.lr * u, params, update)
        if steps_k is not None:
            active = xs[1] < steps_k  # (K,) or (1,) bool

            def keep(new, old):
                mask = active.reshape((-1,) + (1,) * (old.ndim - 1))
                return jnp.where(mask, new, old)

            new_params = jax.tree.map(keep, new_params, params)
            if cfg.momentum:
                new_mom = jax.tree.map(keep, new_mom, mom)
        return (new_params, new_mom), losses

    xs = (
        batches
        if steps_k is None
        else (batches, jnp.arange(cfg.local_steps, dtype=jnp.int32))
    )
    (params, mom), losses = jax.lax.scan(step, (state.params, state.momentum), xs)
    # cross-peer reductions OUTSIDE the scan, on the materialized (T, K)
    # buffer: an in-scan mean compiles differently in the (XLA-peeled) first
    # iteration than in the loop body, so the vmap and shard_map runtimes
    # would disagree in the last ulp; out here both reduce identical buffers
    if axis_name is not None:
        losses = jax.lax.all_gather(losses, axis_name, axis=1, tiled=True)  # (T, K)

    # b <- (1/S) w (updated during local learning; fixed during consensus).
    b_bias = state.b_bias
    if cfg.use_affinity_b:
        s = max(cfg.consensus_steps, 1)
        b_bias = jax.tree.map(lambda w: w / s, params)

    return state._replace(params=params, momentum=mom, b_bias=b_bias), losses


def local_phase(
    state: P2PState,
    loss_fn: LossFn,
    batches: PyTree,
    cfg: P2PConfig,
    *,
    axis_name: str | None = None,
    steps_k: jax.Array | None = None,
) -> tuple[P2PState, jax.Array]:
    """Run up to T local steps on every peer.

    batches: pytree whose leaves are (T, K, ...) — step-major, then peer.
    ``steps_k`` (optional per-peer int32 budget, see ``_local_phase_stats``)
    caps how many of the T steps each peer applies.  Returns (new_state,
    per-step mean loss (T,)).
    """
    state, losses = _local_phase_stats(
        state, loss_fn, batches, cfg, axis_name=axis_name, steps_k=steps_k
    )
    return state, jnp.mean(losses, axis=1)  # (T,) per-step mean over peers


# ---------------------------------------------------------------------------
# Consensus phase (Eq. 4)
# ---------------------------------------------------------------------------


class _Exchange(protocols_lib.StackedExchange):
    """How a runtime sees its neighbours: the stacked (vmap) exchange, where
    every block holds all K peers and nothing crosses a device.  The mesh
    runtimes subclass it (``_Lanes``, ``_Bridge``, ``_Segment``).  Besides
    ``rows``/``own``/``view`` (see ``protocols.StackedExchange``) the phase
    uses:

    ``out(a)``        — this runtime's rows of a ``rows``-shaped result (the
                        bridge slices its full-K products after the reduction);
    ``everyone(a)``   — every peer's entries of a per-peer vector (pod: one
                        ``all_gather`` of K scalars);
    ``average(b, v)`` — the affinity average ``sum_j beta_kj v_j`` of a view.
    """

    def out(self, a: jax.Array) -> jax.Array:
        """This runtime's rows of a ``rows``-shaped result."""
        return a

    def everyone(self, a: jax.Array) -> jax.Array:
        """Every peer's entries of a per-peer vector."""
        return a

    def average(self, beta_rows: jax.Array, view: jax.Array) -> jax.Array:
        """The beta-weighted neighbour average of a dense view."""
        return self.out(consensus_lib.mix_leaf(beta_rows, view))


class _Lanes(_Exchange):
    """Pod: one peer per mesh slice; neighbour rows arrive through one
    ``ppermute`` per ``PermLane`` (``consensus.gather_peer_leaf``), and the
    mix is this peer's (1, K) row of the stacked einsum, which keeps the
    runtimes fp32 bit-identical.  Leaves are double-buffered: leaf i+1's
    lanes are issued before leaf i's view is consumed, and an
    ``optimization_barrier`` pins the pair so XLA cannot serialize the
    in-flight sends behind the compute."""

    def __init__(self, axis_name: str, lanes, num_peers: int):
        self.axis_name, self.lanes, self.num_peers = axis_name, lanes, num_peers

    def rows(self, a: jax.Array) -> jax.Array:
        """This peer's row."""
        my = jax.lax.axis_index(self.axis_name)
        return jax.lax.dynamic_slice_in_dim(a, my, 1, axis=0)

    own = rows

    def view(self, v: jax.Array) -> jax.Array:
        """The (K, ...) reconstruction, zero rows for non-in-neighbours."""
        return consensus_lib.gather_peer_leaf(
            v, self.axis_name, self.lanes, self.num_peers
        )

    def everyone(self, a: jax.Array) -> jax.Array:
        """All K peers' entries: one tiled ``all_gather``."""
        return jax.lax.all_gather(a, self.axis_name, axis=0, tiled=True)

    def with_own(self, full: jax.Array, x: jax.Array) -> jax.Array:
        """``full`` with this peer's row replaced by its (1, ...) block."""
        return full.at[jax.lax.axis_index(self.axis_name)].set(x[0])


class _Bridge(_Exchange):
    """Hierarchical, K <= 64: a (p, ...) block of peers per slice.  Each leaf
    is all-gathered and the stacked runtime's FULL dense einsum runs before
    this block's rows are sliced out: slicing after the reduction keeps every
    bit, where (p, K)-row forms of narrow leaves (scalars, the push-sum mass)
    reduce in another order and drift by an ulp.  The round's constants are
    the sparse schedule densified losslessly."""

    def __init__(self, axis_name: str, row0: jax.Array, block: int):
        self.axis_name, self.row0, self.block = axis_name, row0, block

    def own(self, a: jax.Array) -> jax.Array:
        """This device's p rows."""
        return jax.lax.dynamic_slice_in_dim(a, self.row0, self.block, axis=0)

    out = own

    def view(self, v: jax.Array) -> jax.Array:
        """The all-gathered (K, ...) stack."""
        return jax.lax.all_gather(v, self.axis_name, axis=0, tiled=True)


class _Segment(_Exchange):
    """Hierarchical, large K: each leaf's peer blocks stream around the ring
    and only this block's (p, D, ...) neighbour slots are kept
    (``consensus.ring_gather_slots``); the mix is a weighted slot sum over
    the round's degree-bounded ``SparseRoundOps`` block.  O(K * D * feat /
    devices) memory, no (K, K) or (K, feat) intermediate — allclose to the
    dense forms, not bitwise (slot-ordered sums)."""

    def __init__(self, axis_name: str, num_devices: int, nbr_idx: jax.Array):
        self.axis_name, self.num_devices, self.nbr_idx = axis_name, num_devices, nbr_idx

    def view(self, v: jax.Array) -> jax.Array:
        """The (p, D, ...) neighbour slots."""
        return consensus_lib.ring_gather_slots(
            v, self.nbr_idx, self.axis_name, self.num_devices
        )

    def average(self, beta_rows: jax.Array, view: jax.Array) -> jax.Array:
        """The slot-weighted neighbour average."""
        return consensus_lib.slot_sum(beta_rows, view)


def _staleness_delivery(
    cfg: P2PConfig, round_idx: jax.Array, age: jax.Array
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """One async round's delivery decision from the full (K,) snapshot ages.

    Returns ``(delivered, new_age, decay)``, all (K,):

    ``delivered``  bool — sender k publishes a fresh snapshot this round,
                   either on its compute schedule (every ``period_k`` rounds
                   of the config's profile) or FORCED because its snapshot
                   would otherwise exceed ``cfg.staleness_bound`` rounds of
                   age — the bounded-staleness guarantee.  Traced per-round
                   booleans: the mask gates buffer updates only, never the
                   (static) communication structure, so one compile covers
                   every round.
    ``new_age``    int32 — post-delivery snapshot ages (0 where delivered);
                   invariant ``new_age <= cfg.staleness_bound``.
    ``decay``      f32 — ``staleness_decay ** new_age``, the per-SENDER
                   weight multiplier of this round's mix (1.0 for fresh
                   snapshots).

    Every runtime calls this on the same (K,) age vector (the pod runtime
    all-gathers its K scalar ages first), so the delivery pattern — and with
    it the round's effective mixing matrix — is identical across runtimes.
    """
    _, periods_np = compute_profile(cfg)
    periods = jnp.asarray(periods_np)  # (K,) int32, trace-time constant
    scheduled = jax.lax.rem(round_idx, periods) == periods - 1
    delivered = scheduled | (age + 1 > cfg.staleness_bound)
    new_age = jnp.where(delivered, 0, age + 1)
    base = jnp.asarray(cfg.staleness_decay, jnp.float32)
    decay = base ** new_age.astype(jnp.float32)
    return delivered, new_age, decay


def _snapshot_round(
    state: P2PState,
    cfg: P2PConfig,
    consts: protocols_lib.ProtocolConstants,
    stochasticity: str,
    ex: _Exchange,
) -> tuple[protocols_lib.ProtocolConstants, StalenessState]:
    """The published-snapshot wire's once-a-round advance.

    Decide delivery per sender (``_staleness_delivery``, on every peer's
    age), refresh the ``published`` rows of delivering senders with their
    live post-local-phase parameters, and decay the round's constants
    (``protocols.age_decayed_constants``): stale senders' outgoing weights
    shrink by ``staleness_decay ** age`` and the freed mass moves onto the
    diagonal, keeping gossip rows and push-sum columns stochastic, so
    push-sum mass conservation survives stale delivery exactly.  Delivery
    happens once per ROUND: all S steps mix the same buffer (a straggler
    cannot publish mid-round).  Returns the decayed constants and the new
    ``StalenessState``.
    """
    st: StalenessState = state.staleness
    delivered, age, decay = _staleness_delivery(
        cfg, state.round_idx, ex.everyone(st.age)
    )
    mine = ex.own(delivered)
    published = jax.tree.map(
        lambda p, q: jnp.where(mine.reshape((-1,) + (1,) * (p.ndim - 1)), p, q),
        state.params,
        st.published,
    )
    consts = protocols_lib.age_decayed_constants(consts, decay, stochasticity)
    return consts, StalenessState(published=published, age=ex.own(age))


def _choco_advance(
    comp: compression_lib.Compressor, x: jax.Array, est: jax.Array, ex: _Exchange
) -> jax.Array:
    """The CHOCO wire, one leaf: ship ``C(x - x̂)`` and advance the estimate.

    This runtime's rows compress their difference to their own public
    estimate; every runtime then advances EVERY row of the (K, ...) stack by
    the same payloads (``x̂ <- x̂ + D(payload)``, CHOCO-SGD's estimate
    tracking, see ``repro.compression``), including its own rows, which must
    match what other devices hold for this sender.  On the pod runtime the
    payload arrays are broadcast with one tiled ``all_gather`` each and the
    stack stays replicated (``sharding.specs.peer_stacked_pspecs``).  That
    broadcast is a SIMULATOR artifact: the modeled per-edge system stores
    estimate rows only for each node's union in-neighbours and delivers
    payloads on every union lane every step; rows outside the union stay
    frozen and are never read (zero weights in every round), so its
    read-observable dynamics equal this simulation, and
    ``benchmarks.wire.estimate_gossip_bytes_per_round`` prices exactly that
    union-lane traffic.
    """
    payload = jax.tree.map(ex.everyone, comp.compress(x - ex.own(est)))
    return est + comp.decompress(payload, est)


@telemetry.scoped("repro.consensus")
def consensus_phase(
    state: P2PState,
    cfg: P2PConfig,
    consts: protocols_lib.ProtocolConstants,
    *,
    exchange: _Exchange | None = None,
) -> P2PState:
    """Run S consensus steps of the config's protocol; updates the affinity
    bias d en route.

    ``consts`` is ONE round's (K, K) slice of the protocol constants (select
    it from the stacked schedule with ``protocols.round_constants``), or this
    device's ``SparseRoundOps`` block on the segment exchange.  ``exchange``
    is the runtime's view of its neighbours (``None``: the stacked vmap
    runtime); the round step builds it.  Per step and per leaf:

      (a) the WIRE stage gives what receivers see of a sender, picked from
          ``cfg``: the live parameters; the CHOCO estimates
          (``compressor != "none"``, advanced every step by
          ``_choco_advance``; d on estimate differences); or the published
          snapshots (``staleness_bound > 0``, advanced once a round by
          ``_snapshot_round``; d against the live parameters);
      (b) the EXCHANGE gives this runtime's view of those senders;
      (c) affinity d from the view, with the isolated-peer guard;
      (d) the protocol's mix: the plain form on live parameters, the convex
          split form (self term on the true block, off-diagonal terms on the
          substitutes) on estimates and snapshots — except the pod CHOCO
          mix, the plain row over the estimates with this peer's own row
          replaced by its true block (allclose to the split form);
      (e) ``eta_b * b``.

    The affinity biases operate on the *de-biased* parameters for every
    protocol: push_sum's mix divides the mass back out before returning.
    """
    return _consensus(state, cfg, consts, exchange or _Exchange())


@telemetry.scoped("repro.consensus")
def consensus_phase_hier(
    state: P2PState,
    cfg: P2PConfig,
    consts,
    *,
    exchange: _Exchange,
) -> P2PState:
    """``consensus_phase`` on the hierarchical runtime (``_Bridge`` or
    ``_Segment`` exchange): the same phase, entered under its own name so
    the hierarchical step can be patched apart from the others."""
    return _consensus(state, cfg, consts, exchange)


def _consensus(
    state: P2PState, cfg: P2PConfig, consts, ex: _Exchange
) -> P2PState:
    """The body of ``consensus_phase``: the one S-step loop."""
    if cfg.consensus_steps == 0:
        return state._replace(round_idx=state.round_idx + 1)

    proto = protocols_lib.get_protocol(cfg.protocol)
    comp = compression_lib.from_config(cfg)
    wire = "choco" if not comp.identity else (
        "snapshot" if cfg.staleness_bound > 0 else "live"
    )
    params, d_bias, proto_state = state.params, state.d_bias, state.protocol
    # Peers whose beta row is all-zero (isolated this round — e.g. churned
    # out of a time-varying schedule) have no neighbors to be biased toward:
    # their d stays 0 rather than decaying toward the origin.  Read from the
    # UNDECAYED beta: staleness shrinks weights but never disconnects a peer.
    beta = ex.rows(consts.beta)
    has_nbrs = ex.out(jnp.sum(beta, axis=1) > 0)
    carried = {}
    if wire == "choco":
        est = jax.tree.leaves(state.compression)  # (K, ...) each
    elif wire == "snapshot":
        consts, carried["staleness"] = _snapshot_round(
            state, cfg, consts, proto.stochasticity, ex
        )
        beta = ex.rows(consts.beta)  # decayed: leans toward fresher senders
        # the buffer is fixed for the round: one exchange, not one per step
        snapshots = [ex.view(p) for p in jax.tree.leaves(carried["staleness"].published)]
    lanes = isinstance(ex, _Lanes)
    split = wire == "snapshot" or (wire == "choco" and not lanes)
    b_leaves = jax.tree.leaves(state.b_bias)
    for _ in range(cfg.consensus_steps):
        proto_state, ctx = proto.begin(proto_state, consts, ex, split=split)
        leaves, treedef = jax.tree.flatten(params)

        def sent(i):
            """(a) + (b): this runtime's view of sender leaf i."""
            if wire == "choco":
                est[i] = _choco_advance(comp, leaves[i], est[i], ex)
                return est[i]
            return snapshots[i] if wire == "snapshot" else ex.view(leaves[i])

        mixed, ds = [], []
        nxt = sent(0) if leaves else None
        for i, x in enumerate(leaves):
            view, nxt = nxt, sent(i + 1) if i + 1 < len(leaves) else None
            d_i = None
            if cfg.use_affinity_d:
                # (c) d_k <- (1/T) sum_j beta_kj (w_j - w_k), from what this
                # peer received (Sec. IV-A)
                own = ex.own(view) if wire == "choco" else x
                d_i = jnp.where(
                    has_nbrs.reshape((-1,) + (1,) * (x.ndim - 1)),
                    (ex.average(beta, view) - own) / cfg.local_steps,
                    jnp.zeros_like(x),
                )
            # (d)
            if split:
                m_i = proto.mix_split_leaf(ctx, x, view)
            else:
                m_i = proto.mix_leaf(
                    ctx, x, ex.with_own(view, x) if wire == "choco" else view
                )
            m_i = ex.out(m_i)
            if cfg.use_affinity_b:  # (e)
                m_i = m_i + cfg.eta_b * b_leaves[i]
            if nxt is not None and lanes and wire == "live":
                # double-buffer: group the next leaf's in-flight lanes with
                # this leaf's results so neither side is sunk past the other
                if d_i is not None:
                    nxt, m_i, d_i = jax.lax.optimization_barrier((nxt, m_i, d_i))
                else:
                    nxt, m_i = jax.lax.optimization_barrier((nxt, m_i))
            mixed.append(m_i)
            ds.append(d_i)
        params = jax.tree.unflatten(treedef, mixed)
        if cfg.use_affinity_d:
            d_bias = jax.tree.unflatten(treedef, ds)

    if wire == "choco":
        carried["compression"] = jax.tree.unflatten(treedef, est)
    return state._replace(
        params=params, d_bias=d_bias, protocol=proto_state,
        round_idx=state.round_idx + 1, **carried,
    )


def run_round(
    state: P2PState,
    loss_fn: LossFn,
    batches: PyTree,
    cfg: P2PConfig,
    consts: protocols_lib.ProtocolConstants,
    *,
    steps_k: jax.Array | None = None,
) -> tuple[P2PState, P2PState, jax.Array]:
    """One full round: local phase then consensus phase.

    ``consts`` is the round's (K, K) ``ProtocolConstants`` slice; ``steps_k``
    the optional (K,) per-peer local-step budget of a heterogeneous compute
    profile (see ``compute_profile``).  Returns (state_after_local,
    state_after_consensus, local losses (T,)) so callers can evaluate test
    accuracy at both phase boundaries — the paper's central measurement
    (Figs. 2-6).
    """
    after_local, losses = local_phase(state, loss_fn, batches, cfg, steps_k=steps_k)
    after_consensus = consensus_phase(after_local, cfg, consts)
    return after_local, after_consensus, losses


# ---------------------------------------------------------------------------
# The round step (every runtime)
# ---------------------------------------------------------------------------


def _shard_map_fn():
    """``jax.shard_map`` with the varying-manual-axes check off: the runtime's
    replicated outputs (round_idx, losses) are replicated by construction."""
    return functools.partial(jax.shard_map, check_vma=False)


MIX_MODES = ("auto", "bridge", "segment")
_BRIDGE_MAX_PEERS = 64  # "auto" uses the bit-parity bridge mix up to here


def _make_round_step(
    loss_fn: LossFn,
    cfg: P2PConfig,
    data_sizes: np.ndarray | None = None,
    *,
    mesh=None,
    axis_name: str = "pod",
    peers_per_device: int | None = None,
    mix_mode: str = "auto",
):
    """The UNJITTED (state, batches) -> (after_local, after_consensus, losses)
    round step shared by every driver and every runtime.

    One block: this round's constants -> local phase -> consensus phase.
    ``mesh=None`` runs it on the stacked (vmap) runtime; a mesh wraps it in
    one ``shard_map`` over ``axis_name``: one peer per slice (pod, the
    ``_Lanes`` exchange), or ``peers_per_device > 1`` peers vmapped inside
    each slice (hierarchical: ``_Bridge`` for K <= 64, else ``_Segment``;
    ``mix_mode`` forces one), which decouples K from the device count.
    ``make_round_fn`` / ``make_sharded_round_fn`` jit it per round;
    ``make_scan_driver`` scans a whole chunk of calls inside one jitted
    program.  Sharing the step is what keeps the python-loop and scan drivers
    running the SAME per-round expression graph — the basis of their fp32
    bit-parity contract.

    The only thing that varies is where the round's constants come from:
    the pretraced (R, ...) stacks indexed by ``round_idx % R`` (dense
    ``ProtocolConstants``; on the hierarchical runtime the sparse
    ``SparseRoundOps``, or their lossless densification for the bridge), or,
    for ``cfg.schedule == "adaptive"``, ``graph.adaptive_round_matrices`` of
    ``state.adaptive`` (previous round's per-peer losses, all-gathered on
    pod, + the threaded PRNG key); the step then stores this round's
    per-peer mean losses and the advanced key.  Still one compile per run.
    """
    from jax.sharding import PartitionSpec as P

    from repro.sharding import specs as specs_lib

    loss_fn = resolve_loss_fn(loss_fn)
    proto = protocols_lib.get_protocol(cfg.protocol)
    hier = peers_per_device is not None and peers_per_device != 1
    mode = None
    if hier:
        # adaptive / compression / async / real-model x hierarchical: all
        # four rejections come from the declarative table
        features_lib.check_config(cfg, peers_per_device=peers_per_device)
        if mesh is None:
            raise ValueError("peers_per_device > 1 needs a mesh (hierarchical runtime)")
        if mix_mode not in MIX_MODES:
            raise ValueError(f"unknown mix_mode {mix_mode!r}; one of {MIX_MODES}")
        num_devices, _ = specs_lib.hierarchical_layout(
            cfg.num_peers, mesh, peer_axis=axis_name,
            peers_per_device=peers_per_device,
        )
        mode = mix_mode
        if mode == "auto":
            mode = "bridge" if cfg.num_peers <= _BRIDGE_MAX_PEERS else "segment"
    elif mesh is not None and dict(mesh.shape).get(axis_name) != cfg.num_peers:
        raise ValueError(
            f"mesh axis {axis_name!r} must have exactly num_peers="
            f"{cfg.num_peers} slices, got mesh shape {dict(mesh.shape)} "
            "(see repro.launch.mesh.make_peer_mesh)"
        )
    adaptive = cfg.schedule == "adaptive"
    sizes_dev = (
        None if data_sizes is None
        else jnp.asarray(np.asarray(data_sizes), jnp.float32)
    )
    # heterogeneous per-peer step budgets (None for "uniform": the masked
    # scan is never built, so the synchronous path stays structurally — and
    # bit-for-bit — the legacy one)
    steps_dev: jax.Array | None = None
    if cfg.steps_profile != "uniform":
        steps_np, _ = compute_profile(cfg)
        steps_dev = jnp.asarray(steps_np)  # (K,) int32

    stacks: tuple = ()  # the pretraced (R, ...) round constants
    if hier:
        sparse = graph_lib.SparseSchedule.from_schedule(
            _checked_schedule(cfg), cfg.mixing, data_sizes=data_sizes,
            consensus_step_size=cfg.consensus_step_size,
            stochasticity=proto.stochasticity,
        )
        if mode == "bridge":
            stacks = tuple(jnp.asarray(m, jnp.float32) for m in sparse.to_dense())
        else:  # degree-bounded operands — R*K*D floats, replicated
            stacks = (
                jnp.asarray(sparse.self_w, jnp.float32),
                jnp.asarray(sparse.nbr_idx, jnp.int32),
                jnp.asarray(sparse.nbr_w, jnp.float32),
                jnp.asarray(sparse.beta, jnp.float32),
            )
    elif not adaptive:
        consts_np, sched = protocol_constants(cfg, data_sizes)
        stacks = (
            jnp.asarray(consts_np.w, jnp.float32),  # (R, K, K)
            jnp.asarray(consts_np.beta, jnp.float32),
        )
    # the stacked or pod exchange; the hierarchical ones hold the round's
    # traced rows, so the block builds them
    exchange = _Exchange()
    if mesh is not None and not hier:
        # Any pair may be matched on an adaptive round, so its candidate lane
        # set covers the COMPLETE graph: the ppermute structure stays a
        # trace-time constant while the round's on-device weights null every
        # edge the matching did not select (zero rows meet zero weights).
        lanes = (
            graph_lib.edge_color_lanes(~np.eye(cfg.num_peers, dtype=bool))
            if adaptive else graph_lib.schedule_lanes(sched)
        )
        exchange = _Lanes(axis_name, lanes, cfg.num_peers)

    def block(state: P2PState, batches: PyTree, *stacks):
        ex = exchange
        if adaptive:
            ad = state.adaptive
            key_round, key_next = jax.random.split(ad.key[0])
            # the selection signal is the same (K,) bits on every runtime, so
            # the matching, and with it the round's whole topology, is too
            w, beta = graph_lib.adaptive_round_matrices(
                ex.everyone(ad.last_losses), key_round, rule=cfg.partner_rule,
                eps=cfg.adaptive_eps, data_sizes=sizes_dev,
                consensus_step_size=cfg.consensus_step_size,
                stochasticity=proto.stochasticity,
            )
            consts = protocols_lib.ProtocolConstants(w=w, beta=beta)
        else:
            idx = jax.lax.rem(state.round_idx, jnp.int32(stacks[0].shape[0]))
            picked = [s[idx] for s in stacks]
            if hier:
                row0 = (jax.lax.axis_index(axis_name) * peers_per_device).astype(jnp.int32)
                ex = _Bridge(axis_name, row0, peers_per_device)
            if mode == "segment":  # this device's rows of the sparse operands
                consts = protocols_lib.SparseRoundOps(*(ex.own(o) for o in picked))
                ex = _Segment(axis_name, num_devices, consts.nbr_idx)
            else:
                consts = protocols_lib.ProtocolConstants(*picked)
        steps_k = None if steps_dev is None else ex.own(steps_dev)
        # the per-step losses all-gather inside the local phase on a mesh, so
        # they are replicated — and reduced over the same (K,) vector as on
        # the vmap runtime
        after_local, losses_tk = _local_phase_stats(
            state, loss_fn, batches, cfg,
            axis_name=None if mesh is None else axis_name, steps_k=steps_k,
        )
        losses = jnp.mean(losses_tk, axis=1)  # (T,) per-step mean over peers
        if adaptive:
            after_local = after_local._replace(adaptive=AdaptiveState(
                key=jnp.broadcast_to(key_next[None, :], ad.key.shape),
                last_losses=ex.own(jnp.mean(losses_tk, axis=0)),  # per peer
            ))
        if hier:
            after_cons = consensus_phase_hier(after_local, cfg, consts, exchange=ex)
        else:
            after_cons = consensus_phase(after_local, cfg, consts, exchange=ex)
        return after_local, after_cons, losses

    if mesh is None:
        return lambda state, batches: block(state, batches, *stacks)
    shard_map = _shard_map_fn()

    def step(state: P2PState, batches: PyTree):
        s_specs = specs_lib.peer_stacked_pspecs(state, peer_axis=axis_name)
        b_specs = specs_lib.peer_batch_pspecs(batches, peer_axis=axis_name)
        mapped = shard_map(
            block,
            mesh=mesh,
            in_specs=(s_specs, b_specs) + tuple(P(*(None,) * s.ndim) for s in stacks),
            out_specs=(s_specs, s_specs, P(None)),
        )
        return mapped(state, batches, *stacks)

    return step


def make_sharded_round_fn(
    loss_fn: LossFn,
    cfg: P2PConfig,
    mesh,
    data_sizes: np.ndarray | None = None,
    *,
    axis_name: str = "pod",
    peers_per_device: int | None = None,
    mix_mode: str = "auto",
):
    """jit-compiled round over a REAL mesh: one peer replica per mesh slice.

    The drop-in production form of ``make_round_fn``: same signature for the
    returned callable, same (state, batches) -> (after_local, after_consensus,
    losses) contract, bit-identical fp32 results — but the peer axis is
    ``shard_map``'d over ``mesh``'s ``axis_name`` instead of vmapped, local
    phases run embarrassingly parallel, and the consensus mix lowers to one
    ppermute per schedule lane (``graph.schedule_lanes``) instead of a dense
    (K, K) einsum.  The protocol's (R, K, K) constants stay replicated and are
    sliced with ``round_idx % R`` inside the one jitted program.

    State/batch placement: any input works (jit reshards), but steady-state
    runs should place the state with ``sharding.specs.shard_peer_tree`` to
    avoid a per-round host transfer.

    ``peers_per_device > 1`` selects the hierarchical runtime: p = K /
    mesh-axis-size peers vmapped inside each slice, consensus over the
    degree-bounded sparse schedule (``mix_mode``: "auto" picks the bit-parity
    "bridge" mix for K <= 64 and the O(K * D / devices)-memory "segment" mix
    beyond — see ``consensus_phase_hier``).
    """
    return jax.jit(
        _make_round_step(
            loss_fn, cfg, data_sizes, mesh=mesh, axis_name=axis_name,
            peers_per_device=peers_per_device, mix_mode=mix_mode,
        )
    )


def make_round_fn(loss_fn: LossFn, cfg: P2PConfig, data_sizes: np.ndarray | None = None):
    """jit-compiled round closure over the (possibly time-varying) schedule.

    The protocol's full (R, K, K) constant stacks are closed over as device
    constants and indexed with ``round_idx % R`` *inside* the jitted program:
    one compile covers every round of a time-varying run — for any protocol —
    with no per-round host sync.
    """
    return jax.jit(_make_round_step(loss_fn, cfg, data_sizes))


def make_scan_driver(
    loss_fn: LossFn,
    cfg: P2PConfig,
    data_sizes: np.ndarray | None = None,
    *,
    mesh=None,
    axis_name: str = "pod",
    peers_per_device: int | None = None,
    mix_mode: str = "auto",
    donate: bool = True,
):
    """Fused multi-round driver: a whole chunk of rounds per jitted call.

    Returns ``drive(state, batches) -> (after_local, final_state, losses)``
    where every ``batches`` leaf carries a leading chunk axis C on top of the
    per-round layout — (C, T, K, ...) — and the C rounds run inside ONE
    ``lax.scan`` of the same round step the python-loop drivers jit
    (``_make_round_step``), so the results are fp32 bit-identical to C calls
    of ``make_round_fn`` / ``make_sharded_round_fn``.  ``after_local`` is the
    last round's post-local-phase state (the paper's eval instrument needs
    both phase boundaries), ``losses`` is the stacked (C, T) per-round series.

    Why it's faster than the python loop: one dispatch (and one
    ``device_get``, if the caller fetches anything) per C rounds instead of
    per round, round constants selected by ``round_idx % R`` inside the scan
    carry, and — with ``donate=True`` — ``donate_argnums`` on the input
    ``P2PState``, so params/opt/protocol buffers are reused in place instead
    of reallocated every round.  The donated input is CONSUMED: after
    ``drive(state, ...)`` the caller must use the returned state, never
    ``state`` itself.

    ``mesh=None`` scans the stacked/vmap runtime; a mesh scans the sharded
    (``peer_axis="pod"``) runtime, chunk axis outside the ``shard_map``.
    The chunk length C is not baked in: it is read from the batch shapes, and
    each distinct C compiles once (drive with ONE chunk size per run to keep
    the one-compile property).  The jitted program is ``jit_drive``,
    registered with ``repro.telemetry`` as ``drive``.
    """
    donate_argnums = (0,) if donate else ()
    step = _make_round_step(
        loss_fn, cfg, data_sizes, mesh=mesh, axis_name=axis_name,
        peers_per_device=peers_per_device, mix_mode=mix_mode,
    )

    @telemetry.program("drive", donate_argnums=donate_argnums)
    def drive(state: P2PState, batches: PyTree):
        def body(carry, batches_r):
            st, _ = carry
            after_local, after_cons, losses = step(st, batches_r)
            return (after_cons, after_local), losses

        # the second carry slot threads the LAST round's after-local state out
        # of the scan (stacking every round's would hold C copies of params)
        (final, last_local), losses = jax.lax.scan(body, (state, state), batches)
        return last_local, final, losses

    return jax.jit(drive, donate_argnums=donate_argnums)


# ---------------------------------------------------------------------------
# Serving extraction (the trained fleet's artifacts)
# ---------------------------------------------------------------------------


def serving_params(state: P2PState) -> PyTree:
    """Extract the personalized serving artifact from a trained state.

    The stacked (K, ...) per-peer parameter tree, detached from the
    optimizer/consensus leaves — P2PL's product is K *divergent* models, and
    this is the exact layout the stacked serving runtime consumes
    (``repro.launch.serve.make_fleet_generate_fn`` /
    ``make_fleet_classify_fn``): the same leading-K axis, so
    ``sharding.specs.peer_stacked_pspecs`` places training state and serving
    fleet identically.
    """
    return state.params


def consensus_averaged_params(
    stacked_params: PyTree, data_sizes: np.ndarray | None = None
) -> PyTree:
    """The ONE-model serving baseline: average the K peer rows, broadcast back.

    Collapses the stacked tree to its (data-weighted, else uniform) fp32
    average and re-broadcasts it to all K rows, so the averaged baseline
    routes through the IDENTICAL stacked serving path as the personalized
    fleet — the per-peer accuracy A/B (what personalization buys) differs
    only in the parameter rows, never in the serving code.
    """
    k = jax.tree.leaves(stacked_params)[0].shape[0]
    if data_sizes is None:
        w = jnp.full((k,), 1.0 / k, jnp.float32)
    else:
        sizes = jnp.asarray(data_sizes, jnp.float32)
        w = sizes / jnp.sum(sizes)

    def avg(p):
        mean = jnp.tensordot(w, p.astype(jnp.float32), axes=1)
        return jnp.broadcast_to(mean.astype(p.dtype), p.shape)

    return jax.tree.map(avg, stacked_params)


# ---------------------------------------------------------------------------
# Evaluation helpers (stratified accuracy — the paper's seen/unseen split)
# ---------------------------------------------------------------------------


def evaluate_stacked(
    apply_fn: Callable[[PyTree, jax.Array], jax.Array],
    params: PyTree,
    images: jax.Array,
    labels: jax.Array,
) -> jax.Array:
    """Per-peer test accuracy: (K,) from stacked params on a shared test set."""

    def acc(p):
        logits = apply_fn(p, images)
        return jnp.mean((jnp.argmax(logits, -1) == labels).astype(jnp.float32))

    return jax.vmap(acc)(params)


@telemetry.scoped("repro.eval")
def stratified_accuracy(
    apply_fn: Callable[[PyTree, jax.Array], jax.Array],
    params: PyTree,
    images: jax.Array,
    labels: jax.Array,
    class_groups: dict[str, np.ndarray],
) -> dict[str, jax.Array]:
    """Accuracy per named class group (e.g. {"seen": [0,1], "unseen": [7,8]}).

    Predictions are restricted to the union of all group classes, matching the
    paper's K-class tasks (e.g. 4-class task over {0,1,7,8}).
    """
    all_classes = np.sort(np.concatenate(list(class_groups.values())))

    def preds(p):
        # restrict predictions to the task's class set (the paper's K-class tasks)
        logits = apply_fn(p, images)
        m = jnp.full((logits.shape[-1],), -1e9, jnp.float32).at[jnp.asarray(all_classes)].set(0.0)
        return jnp.argmax(logits + m, axis=-1)

    pred = jax.vmap(preds)(params)  # (K, N)
    out = {}
    for name, classes in class_groups.items():
        sel = jnp.isin(labels, jnp.asarray(classes))
        denom = jnp.maximum(jnp.sum(sel), 1)
        out[name] = jnp.sum((pred == labels[None, :]) & sel[None, :], axis=1) / denom
    return out


def oscillation_amplitude(after_local: np.ndarray, after_consensus: np.ndarray) -> np.ndarray:
    """Mean |acc_after_consensus - acc_after_local| per round — the paper's
    sawtooth size.  Inputs: (rounds,) or (rounds, K)."""
    a = np.asarray(after_local, np.float64)
    c = np.asarray(after_consensus, np.float64)
    return np.abs(c - a).mean(axis=-1) if a.ndim > 1 else np.abs(c - a)
