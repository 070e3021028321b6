"""The declarative feature-compatibility table — ONE source of truth.

Every pairwise "feature A does not compose with feature B" rejection in the
repo lives here: the config layer (``P2PConfig.__post_init__``), the runtime
builders (``p2p._make_round_step`` via ``make_sharded_round_fn``), the
launcher (``launch.train.run_paper_experiment``), and the CLI argparse layer
all call ``check()`` / ``check_config()`` and raise the SAME formatted
message through ``format_violation`` — so the error a user sees is identical
no matter which layer catches the combination first, and the README support
matrix is GENERATED from this table (``tools/check_support_matrix.py``)
instead of hand-maintained prose.

Structure:

* ``Feature`` — a named axis of the system with a ``predicate`` over a
  ``FeatureContext`` (is it active in this run?), a static ``title`` for the
  generated matrix, and a ``describe`` callback producing the concrete
  "what you asked for" clause of an error (e.g. ``compressor='topk'``).
* ``Incompatibility`` — an ordered (a, b) pair of feature names with the
  ``reason`` it cannot work and the ``workaround`` the error should suggest.
  Ordering is presentation only: the message reads "<a> is not supported
  with <b>: <reason>; <workaround>".
* ``FeatureContext`` — the plain-value snapshot the predicates see: the
  config axes plus the runtime axes a frozen config cannot know
  (``peers_per_device``).

Value validation (unknown names, out-of-range scalars) stays where the value
lives — this module owns only the *composition* rules.
"""
from __future__ import annotations

import dataclasses
from typing import Callable


@dataclasses.dataclass(frozen=True)
class FeatureContext:
    """Plain-value snapshot of one run's feature axes.

    Built from a ``P2PConfig`` via ``context_from_config`` (runtime layers
    add ``peers_per_device``); kept as primitives so the table has no import
    edge back into ``core.p2p``.
    """

    schedule: str = "static"
    compressor: str = "none"
    steps_profile: str = "uniform"
    staleness_bound: int = 0
    model: str = "mnist_mlp"
    peers_per_device: int = 1


def context_from_config(cfg, *, peers_per_device: int = 1) -> FeatureContext:
    """Snapshot a ``P2PConfig``(-shaped) object into a ``FeatureContext``."""
    return FeatureContext(
        schedule=cfg.schedule,
        compressor=cfg.compressor,
        steps_profile=cfg.steps_profile,
        staleness_bound=cfg.staleness_bound,
        model=getattr(cfg, "model", "mnist_mlp"),
        peers_per_device=peers_per_device,
    )


@dataclasses.dataclass(frozen=True)
class Feature:
    """One composable axis: when is it on, and how is it named in errors."""

    name: str
    title: str  # static label for the generated support matrix
    predicate: Callable[[FeatureContext], bool]
    describe: Callable[[FeatureContext], str]  # concrete clause for errors


@dataclasses.dataclass(frozen=True)
class Incompatibility:
    """An (a, b) feature pair that must never be active together."""

    a: str
    b: str
    reason: str
    workaround: str


FEATURES: dict[str, Feature] = {
    f.name: f
    for f in (
        Feature(
            name="adaptive",
            title="schedule `adaptive` (loss-driven partner selection)",
            predicate=lambda c: c.schedule == "adaptive",
            describe=lambda c: "schedule='adaptive' (state-dependent partner "
                               "selection)",
        ),
        Feature(
            name="compression",
            title="compression `topk` / `qint8` (error feedback)",
            predicate=lambda c: c.compressor != "none",
            describe=lambda c: f"compressor={c.compressor!r} (compressed "
                               "gossip payloads)",
        ),
        Feature(
            name="staleness",
            title="async `staleness_bound > 0` (bounded-staleness gossip)",
            predicate=lambda c: c.staleness_bound > 0,
            describe=lambda c: f"staleness_bound={c.staleness_bound} "
                               "(bounded-staleness gossip)",
        ),
        Feature(
            name="async",
            title="async rounds (`--steps-profile` / `--staleness-bound`)",
            predicate=lambda c: (c.staleness_bound > 0
                                 or c.steps_profile != "uniform"),
            describe=lambda c: "asynchronous rounds (--steps-profile "
                               f"{c.steps_profile}, --staleness-bound "
                               f"{c.staleness_bound})",
        ),
        Feature(
            name="hierarchical",
            title="hierarchical runtime (`--peers-per-device > 1`)",
            predicate=lambda c: c.peers_per_device > 1,
            describe=lambda c: "the hierarchical runtime (peers_per_device "
                               f"= {c.peers_per_device} > 1)",
        ),
        Feature(
            name="real_model",
            title="registry TrainTask (`model != \"mnist_mlp\"`)",
            predicate=lambda c: c.model != "mnist_mlp",
            describe=lambda c: f"model={c.model!r} (a registry TrainTask)",
        ),
    )
}


INCOMPATIBILITIES: tuple[Incompatibility, ...] = (
    Incompatibility(
        a="staleness",
        b="adaptive",
        reason="the adaptive matching is derived from FRESH per-peer losses "
               "every round, which is exactly what a straggler cannot provide",
        workaround="run bounded-staleness gossip on a pretraced schedule, or "
                   "adaptive selection synchronously (staleness_bound=0)",
    ),
    Incompatibility(
        a="staleness",
        b="compression",
        reason="the staleness buffer stores raw sender snapshots while the "
               "compressed wire stores payload-advanced estimates — composing "
               "the two buffers is an open item",
        workaround="run async rounds uncompressed, or compression "
                   "synchronously (staleness_bound=0)",
    ),
    Incompatibility(
        a="adaptive",
        b="hierarchical",
        reason="the adaptive candidate set is the complete graph — dense "
               "O(K^2) matrices the hierarchical runtime's sparse "
               "degree-bounded path exists to avoid",
        workaround="run adaptive schedules with one peer per device "
                   "(peers_per_device=1), or use a pretraced schedule here",
    ),
    Incompatibility(
        a="compression",
        b="hierarchical",
        reason="the hierarchical bridge/segment mixes stream raw fp32 blocks, "
               "not payload-advanced estimates",
        workaround="run compressed gossip with one peer per device "
                   "(peers_per_device=1), or compressor='none' here",
    ),
    Incompatibility(
        a="async",
        b="hierarchical",
        reason="the hierarchical bridge/segment mixes stream live parameter "
               "blocks with no staleness buffer",
        workaround="run async rounds with one peer per device "
                   "(peers_per_device=1), or the uniform synchronous profile "
                   "here",
    ),
    Incompatibility(
        a="real_model",
        b="hierarchical",
        reason="the bridge/segment mixes and their sparse degree-bounded "
               "schedules are validated on the paper's 2NN only; a registry "
               "task's deep parameter tree has no hierarchical parity "
               "baseline yet",
        workaround="run registry tasks with one peer per device "
                   "(peers_per_device=1), or model='mnist_mlp' here",
    ),
)


def active_features(ctx: FeatureContext) -> tuple[str, ...]:
    """Names of the features a context switches on."""
    return tuple(n for n, f in FEATURES.items() if f.predicate(ctx))


def violations(ctx: FeatureContext) -> tuple[Incompatibility, ...]:
    """Table entries whose BOTH features are active in the context."""
    on = set(active_features(ctx))
    return tuple(i for i in INCOMPATIBILITIES if i.a in on and i.b in on)


def format_violation(inc: Incompatibility, ctx: FeatureContext) -> str:
    """THE formatter: every layer's composition error reads identically."""
    a, b = FEATURES[inc.a], FEATURES[inc.b]
    return (f"{a.describe(ctx)} is not supported with {b.describe(ctx)}: "
            f"{inc.reason}; {inc.workaround}")


def check(ctx: FeatureContext) -> None:
    """Raise ``ValueError`` on the first active incompatibility."""
    for inc in violations(ctx):
        raise ValueError(format_violation(inc, ctx))


def check_config(cfg, *, peers_per_device: int = 1) -> None:
    """``check`` over a ``P2PConfig``(-shaped) object, the common entry."""
    check(context_from_config(cfg, peers_per_device=peers_per_device))


def support_matrix_markdown() -> str:
    """Render the incompatibility table as the README's generated section.

    One row per table entry; regenerated/verified by
    ``tools/check_support_matrix.py`` so prose and code cannot drift.
    """
    lines = [
        "| feature | does not compose with | why |",
        "|---|---|---|",
    ]
    for inc in INCOMPATIBILITIES:
        lines.append(
            f"| {FEATURES[inc.a].title} | {FEATURES[inc.b].title} "
            f"| {inc.reason} |"
        )
    return "\n".join(lines) + "\n"
