"""The paper's own workload: 2NN MLP on (synthetic-)MNIST under P2PL.

Sec. V hyperparameters: B=10, eta=0.01, mu=0.5 (IID) / 0 (non-IID),
T=60 gradient steps per round (IID, n_k=600) — one epoch per round,
data-size-weighted row-stochastic mixing, epsilon_k = 1.
"""
import dataclasses

from repro.core.p2p import P2PConfig


@dataclasses.dataclass(frozen=True)
class PaperExperiment:
    name: str
    p2p: P2PConfig
    batch_size: int = 10
    samples_per_class: int = 50
    rounds: int = 40
    seen_classes: tuple = ()
    peer_classes: tuple = ()  # tuple of per-peer class tuples (non-IID)
    model: str = "mnist_mlp"  # one of core.task.task_names()

    def __post_init__(self):
        # the model is named in two places (the experiment, for the launcher
        # and data pipeline; the P2PConfig, for the feature table) — keep them
        # one value: a non-default on either side propagates to both, and two
        # CONFLICTING non-defaults are an error, not a silent pick
        if self.model != self.p2p.model:
            if self.model != "mnist_mlp" and self.p2p.model != "mnist_mlp":
                raise ValueError(
                    f"experiment model {self.model!r} conflicts with "
                    f"p2p.model {self.p2p.model!r}"
                )
            chosen = self.model if self.model != "mnist_mlp" else self.p2p.model
            object.__setattr__(self, "model", chosen)
            object.__setattr__(
                self, "p2p", dataclasses.replace(self.p2p, model=chosen)
            )


def iid_k100(*, topology: str = "complete") -> PaperExperiment:
    """Fig. 2: K=100, IID, 600 samples each, T=60, momentum 0.5."""
    return PaperExperiment(
        name=f"iid_k100_{topology}",
        p2p=P2PConfig(
            algorithm="p2pl",
            num_peers=100,
            local_steps=60,
            consensus_steps=1,
            lr=0.01,
            momentum=0.5,
            topology=topology,
            mixing="data_weighted",
        ),
        batch_size=10,
        rounds=100,
    )


def timevarying_k2(
    *,
    schedule: str = "link_dropout",
    algorithm: str = "p2pl_affinity",
    local_steps: int = 10,
    schedule_rounds: int = 16,
    link_survival_prob: float = 0.7,
    peer_online_prob: float = 0.8,
    schedule_seed: int = 0,
    protocol: str = "gossip",
    round_robin_topologies: tuple = ("complete", "disconnected"),
    partner_rule: str = "loss_proximity",
    adaptive_eps: float = 0.1,
    adaptive_seed: int = 0,
) -> PaperExperiment:
    """Beyond-paper: the K=2 non-IID workload over a churning link.

    With ``link_dropout`` the single A-B edge vanishes on ~(1-q) of rounds —
    those rounds behave like isolated training, so consensus (and the
    sawtooth) only happens when the link is up.  eta_d=0.5 for the affinity
    variant (observation O1: 1.0 is marginally stable at K=2 full averaging).
    """
    return PaperExperiment(
        name=f"timevarying_k2_{schedule}_{algorithm}_T{local_steps}",
        p2p=P2PConfig(
            algorithm=algorithm,
            num_peers=2,
            local_steps=local_steps,
            consensus_steps=1,
            lr=0.01,
            momentum=0.0,
            eta_d=0.5,
            topology="complete",
            mixing="data_weighted",
            schedule=schedule,
            schedule_rounds=schedule_rounds,
            link_survival_prob=link_survival_prob,
            peer_online_prob=peer_online_prob,
            schedule_seed=schedule_seed,
            protocol=protocol,
            round_robin_topologies=round_robin_topologies,
            partner_rule=partner_rule,
            adaptive_eps=adaptive_eps,
            adaptive_seed=adaptive_seed,
        ),
        batch_size=10,
        samples_per_class=50,
        rounds=60,
        peer_classes=((0, 1), (7, 8)),
    )


def timevarying_k8(
    *,
    schedule: str = "random_matching",
    algorithm: str = "p2pl_affinity",
    local_steps: int = 10,
    schedule_rounds: int = 16,
    link_survival_prob: float = 0.7,
    peer_online_prob: float = 0.8,
    schedule_seed: int = 0,
    protocol: str = "gossip",
    round_robin_topologies: tuple = ("ring", "star"),
    partner_rule: str = "loss_proximity",
    adaptive_eps: float = 0.1,
    adaptive_seed: int = 0,
    compressor: str = "none",
    topk_frac: float = 0.01,
) -> PaperExperiment:
    """Beyond-paper: 8 peers, 2 classes each, gossiping over a time-varying
    graph (pairwise random matchings, dropped links, peer churn on a ring —
    or ``schedule="adaptive"``: pairwise matchings selected on device each
    round from the peers' own training losses)."""
    peer_classes = tuple(((2 * k) % 10, (2 * k + 1) % 10) for k in range(8))
    return PaperExperiment(
        name=f"timevarying_k8_{schedule}_{algorithm}_T{local_steps}",
        p2p=P2PConfig(
            algorithm=algorithm,
            num_peers=8,
            local_steps=local_steps,
            consensus_steps=1,
            lr=0.01,
            momentum=0.0,
            eta_d=0.5,
            topology="ring",
            mixing="data_weighted",
            schedule=schedule,
            schedule_rounds=schedule_rounds,
            link_survival_prob=link_survival_prob,
            peer_online_prob=peer_online_prob,
            schedule_seed=schedule_seed,
            protocol=protocol,
            round_robin_topologies=round_robin_topologies,
            partner_rule=partner_rule,
            adaptive_eps=adaptive_eps,
            adaptive_seed=adaptive_seed,
            compressor=compressor,
            topk_frac=topk_frac,
        ),
        batch_size=10,
        samples_per_class=50,
        rounds=60,
        peer_classes=peer_classes,
    )


def directed_k8(
    *,
    schedule: str = "static",
    protocol: str = "push_sum",
    algorithm: str = "p2pl_affinity",
    local_steps: int = 10,
    schedule_rounds: int = 16,
    link_survival_prob: float = 0.7,
    schedule_seed: int = 0,
    partner_rule: str = "loss_proximity",
    adaptive_eps: float = 0.1,
    adaptive_seed: int = 0,
) -> PaperExperiment:
    """Beyond-paper: 8 non-IID peers on a DIRECTED ring — each peer only
    pushes forward (Sparse-Push-style one-way links).

    Row-stochastic gossip has no correct answer here (a directed round is not
    average-preserving); the default ``push_sum`` protocol carries a per-peer
    mass scalar whose ratio de-biases the estimates, so consensus still lands
    on the data-weighted average.  Schedules: ``static`` (the directed ring),
    ``link_dropout`` (each one-way link drops independently), or
    ``one_way_matching`` (random sender->receiver pairs each round).

    Shards are deliberately UNEQUAL and non-uniformly placed (the first half
    of the ring carries a third class: 150-sample peers feeding 100-sample
    peers): with uniform — or even alternating — sizes on a degree-regular
    directed ring the data-weighted row matrix is coincidentally unbiased
    (its stationary vector is exactly proportional to n) and push-sum
    degenerates to gossip; varying n_k + n_{k-1} around the ring is what
    makes the mass correction observable.
    """
    peer_classes = tuple(
        ((2 * k) % 10, (2 * k + 1) % 10, (2 * k + 2) % 10) if k < 4
        else ((2 * k) % 10, (2 * k + 1) % 10)
        for k in range(8)
    )
    return PaperExperiment(
        name=f"directed_k8_{schedule}_{protocol}_{algorithm}_T{local_steps}",
        p2p=P2PConfig(
            algorithm=algorithm,
            num_peers=8,
            local_steps=local_steps,
            consensus_steps=1,
            lr=0.01,
            momentum=0.0,
            eta_d=0.5,
            topology="directed_ring",
            mixing="data_weighted",
            schedule=schedule,
            schedule_rounds=schedule_rounds,
            link_survival_prob=link_survival_prob,
            schedule_seed=schedule_seed,
            protocol=protocol,
            partner_rule=partner_rule,
            adaptive_eps=adaptive_eps,
            adaptive_seed=adaptive_seed,
        ),
        batch_size=10,
        samples_per_class=50,
        rounds=60,
        peer_classes=peer_classes,
    )


def sharded_k8(
    *,
    num_peers: int = 8,
    schedule: str = "static",
    protocol: str = "gossip",
    algorithm: str = "p2pl_affinity",
    local_steps: int = 10,
    topology: str = "ring",
    schedule_rounds: int = 16,
    link_survival_prob: float = 0.7,
    schedule_seed: int = 0,
    round_robin_topologies: tuple = ("ring", "star"),
    partner_rule: str = "loss_proximity",
    adaptive_eps: float = 0.1,
    adaptive_seed: int = 0,
) -> PaperExperiment:
    """The sharded peer-axis runtime's demo workload: 8 non-IID peers sized to
    CI's 8 forced host devices (``--peer-axis pod``).

    Same learning problem as ``timevarying_k8`` (2 classes per peer on a
    ring), but parameterized over protocol AND schedule so every runtime
    parity axis — gossip/push_sum x static/link_dropout/round_robin/
    one_way_matching — has a named entry point:

        XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
            python -m repro.launch.train --experiment sharded_k8 --peer-axis pod

    ``num_peers`` shrinks the ring (first K of the same class pairs) to fit
    a host with fewer devices, e.g. K=4 on one four-chip TPU host.
    """
    peer_classes = tuple(((2 * k) % 10, (2 * k + 1) % 10) for k in range(num_peers))
    return PaperExperiment(
        name=f"sharded_k{num_peers}_{schedule}_{protocol}_{algorithm}_T{local_steps}",
        p2p=P2PConfig(
            algorithm=algorithm,
            num_peers=num_peers,
            local_steps=local_steps,
            consensus_steps=1,
            lr=0.01,
            momentum=0.0,
            eta_d=0.5,
            topology=topology,
            mixing="data_weighted",
            schedule=schedule,
            schedule_rounds=schedule_rounds,
            link_survival_prob=link_survival_prob,
            schedule_seed=schedule_seed,
            protocol=protocol,
            round_robin_topologies=round_robin_topologies,
            partner_rule=partner_rule,
            adaptive_eps=adaptive_eps,
            adaptive_seed=adaptive_seed,
        ),
        batch_size=10,
        samples_per_class=50,
        rounds=60,
        peer_classes=peer_classes,
    )


def straggler_k8(
    *,
    schedule: str = "static",
    protocol: str = "gossip",
    algorithm: str = "p2pl_affinity",
    local_steps: int = 8,
    steps_profile: str = "straggler",
    staleness_bound: int = 3,
    staleness_decay: float = 0.5,
    straggler_frac: float = 0.25,
    straggler_period: int = 4,
    eta_d: float = 0.25,
    topology: str = "ring",
    schedule_rounds: int = 16,
    round_robin_topologies: tuple = ("ring", "star"),
) -> PaperExperiment:
    """Beyond-paper: 8 non-IID peers with heterogeneous compute (stragglers).

    Same learning problem as ``timevarying_k8`` (2 classes per peer on a
    ring), but the last quarter of the fleet is 4x slower: under the
    ``straggler`` compute profile they complete T/4 local steps per round and
    only publish every 4th round.  With ``staleness_bound=3`` their neighbors
    keep mixing the last *published* snapshot (age-decayed, renormalized per
    the active protocol) instead of blocking the fleet — the bounded-staleness
    async round of ``core/p2p.py``.  ``staleness_bound=0`` with a uniform
    profile recovers the synchronous round bit for bit.

    eta_d defaults to 0.25, HALF the sync experiments' 0.5: the affinity bias
    is a feedback loop through the neighbors' states, and snapshot delay eats
    its gain margin — at a 4-round staleness delay eta_d=0.5 diverges
    (exponential d growth, NaN by round ~50) while 0.25 stays stable, the
    same gain-margin arithmetic as observation O1's "eta_d=1.0 is marginally
    stable at K=2" but with the margin halved again by the delay.

        python -m repro.launch.train --experiment straggler_k8 \\
            --steps-profile straggler --staleness-bound 3 --rounds 8
    """
    peer_classes = tuple(((2 * k) % 10, (2 * k + 1) % 10) for k in range(8))
    return PaperExperiment(
        name=f"straggler_k8_{schedule}_{protocol}_{steps_profile}_b{staleness_bound}",
        p2p=P2PConfig(
            algorithm=algorithm,
            num_peers=8,
            local_steps=local_steps,
            consensus_steps=1,
            lr=0.01,
            momentum=0.0,
            eta_d=eta_d,
            topology=topology,
            mixing="data_weighted",
            schedule=schedule,
            schedule_rounds=schedule_rounds,
            round_robin_topologies=round_robin_topologies,
            protocol=protocol,
            steps_profile=steps_profile,
            staleness_bound=staleness_bound,
            staleness_decay=staleness_decay,
            straggler_frac=straggler_frac,
            straggler_period=straggler_period,
        ),
        batch_size=10,
        samples_per_class=50,
        rounds=60,
        peer_classes=peer_classes,
    )


def noniid_k2(*, algorithm: str = "local_dsgd", local_steps: int = 10) -> PaperExperiment:
    """Fig. 3cd/6: K=2, pathological non-IID (A: {0,1}, B: {7,8})."""
    return PaperExperiment(
        name=f"noniid_k2_{algorithm}_T{local_steps}",
        p2p=P2PConfig(
            algorithm=algorithm,
            num_peers=2,
            local_steps=local_steps,
            consensus_steps=0 if algorithm == "isolated" else 1,
            lr=0.01,
            momentum=0.0,
            topology="disconnected" if algorithm == "isolated" else "complete",
            mixing="identity" if algorithm == "isolated" else "data_weighted",
        ),
        batch_size=10,
        samples_per_class=50,
        rounds=60,
        peer_classes=((0, 1), (7, 8)),
    )


def seqmnist_k8(
    *,
    schedule: str = "static",
    protocol: str = "gossip",
    algorithm: str = "p2pl",
    local_steps: int = 4,
    lr: float = 0.05,
    topology: str = "ring",
    rounds: int = 20,
    schedule_rounds: int = 16,
    round_robin_topologies: tuple = ("ring", "star"),
) -> PaperExperiment:
    """The first real-model workload: RWKV6 on sequential MNIST, 8 peers.

    Same non-IID shape as ``sharded_k8`` (2 classes per peer on a ring, sized
    to CI's 8 forced host devices) but the task is ``rwkv6_seqmnist``: each
    image becomes a 196-token pixel stream and every peer trains the reduced
    RWKV6 of ``core.task.seqmnist_model_config`` — so gossip and push_sum mix
    a real multi-layer parameter tree (embeddings, layernorms, time/channel
    mixes, LoRA decay projections), not the 2NN's four matrices.

    T=4 and lr=0.05: the recurrent trunk is ~50x the MLP's FLOPs per step,
    and plain SGD on the (max-norm-synced — algorithm="p2pl") init moves the
    cross-entropy reliably at 0.05 where 0.01 is visibly slow in 20 rounds.

        XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
            python -m repro.launch.train --experiment seqmnist_k8 --rounds 4
    """
    peer_classes = tuple(((2 * k) % 10, (2 * k + 1) % 10) for k in range(8))
    return PaperExperiment(
        name=f"seqmnist_k8_{schedule}_{protocol}_{algorithm}_T{local_steps}",
        p2p=P2PConfig(
            algorithm=algorithm,
            num_peers=8,
            local_steps=local_steps,
            consensus_steps=1,
            lr=lr,
            momentum=0.0,
            topology=topology,
            mixing="data_weighted",
            schedule=schedule,
            schedule_rounds=schedule_rounds,
            round_robin_topologies=round_robin_topologies,
            protocol=protocol,
            model="rwkv6_seqmnist",
        ),
        batch_size=10,
        samples_per_class=50,
        rounds=rounds,
        peer_classes=peer_classes,
        model="rwkv6_seqmnist",
    )
