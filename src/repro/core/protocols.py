"""Composable consensus protocols: how one gossip step moves parameters.

The paper hardwires Eq. 4 as a row-stochastic mix; this module turns that
choice into one instance of a ``ConsensusProtocol`` so the same runtime
(``repro.core.p2p``) can also run directed, Sparse-Push-style schedules where
a peer sends without receiving.  A protocol owns three things:

    init_state(params, data_sizes)   -> per-run protocol state (leading K axis)
    constants(schedule, mixing, ..)  -> stacked (R, K, K) numpy round constants
    begin(proto_state, consts, ex)   -> per consensus step: scalar work and
                                        this runtime's weight operands, then
    mix_leaf / mix_split_leaf(ctx, x, view) per parameter leaf

``constants`` runs once on the host at setup; the jitted round function
closes over the stack and feeds ``begin`` one round's (K, K) slice selected
by ``round_idx % R`` *inside* the traced program, preserving the
one-compile-per-run property for every protocol.  ``mix`` is the whole-tree
stacked step built from ``begin`` and ``mix_leaf``.

State layout per protocol (the ``P2PState.protocol`` leaf):

    gossip   — ``()``: stateless.  ``mix`` is the paper's row-stochastic
               einsum, bit-identical to the pre-protocol runtime.
    push_sum — ``PushSumState(mass=(K,) f32)``: each peer carries a scalar
               push-sum mass y_k.  ``mix`` re-biases the (always de-biased)
               parameters by y, pushes numerators and mass through the
               column-stochastic weights, and divides back:

                   num_k = sum_j A[k, j] * y_j * w_j
                   y_k'  = sum_j A[k, j] * y_j
                   w_k'  = num_k / y_k'

               Column-stochastic A conserves sum_k y_k == K on ANY directed
               or churning round, and w' converges to the mass-weighted
               average of the initial parameters wherever the schedule's
               union graph is strongly connected.  Data weighting enters
               through the mass init (y_k proportional to n_k), not through A —
               the push-sum limit depends only on the initial (numerator,
               mass) totals, never on the weights themselves.
"""
from __future__ import annotations

from typing import Any, NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import consensus as consensus_lib
from repro.core import graph as graph_lib

PyTree = Any


class ProtocolConstants(NamedTuple):
    """Per-round mixing constants a protocol's ``mix`` consumes.

    ``w``/``beta`` are (R, K, K) stacks on the host (numpy) or device, or one
    round's (K, K) slice when already selected via ``round_constants``.  For
    gossip ``w`` is row-stochastic; for push_sum it is column-stochastic.
    """

    w: Any
    beta: Any


def round_constants(consts: ProtocolConstants, idx) -> ProtocolConstants:
    """Select round ``idx`` of a stacked (R, ...) constants tree (traceable)."""
    return ProtocolConstants(w=consts.w[idx], beta=consts.beta[idx])


def age_decayed_constants(
    consts: ProtocolConstants, decay: jax.Array, stochasticity: str
) -> ProtocolConstants:
    """One async round's renormalized age-decayed mixing constants.

    Bounded-staleness consensus (``p2p._snapshot_round``) mixes each
    SENDER j's last published snapshot with its weight scaled by ``decay[j]``
    (``staleness_decay ** age_j`` in (0, 1]; 1.0 = fresh).  Scaling alone
    would break stochasticity, so the freed mass is absorbed by the
    DIAGONAL — the one term that never rides the wire and is always fresh:

    * ``stochasticity="row"`` (gossip): off-diagonal entry (k, j) becomes
      ``w_kj * decay_j``; the diagonal is rebuilt as ``1 - sum_j'`` of the
      row's decayed off-diagonals, so every row still sums to 1 and the mix
      stays a convex combination (receivers lean toward their own live
      params when their in-neighbors are stale).
    * ``stochasticity="column"`` (push_sum): the same off-diagonal scaling,
      diagonal rebuilt from COLUMN sums — a stale sender keeps the mass it
      could not ship — so every column still sums to 1 and push-sum mass
      conservation (``sum_k y_k == K``) survives stale delivery exactly (up
      to one fp rounding of the ``1 - sum`` per column).

    ``beta`` (the affinity-average weights, rows summing to 1 over
    in-neighbors) is decayed per sender and then ROW-renormalized back to a
    distribution: the affinity average leans toward fresher neighbors but
    remains an average of received states.  Scaling without renormalizing
    would shrink ``nbr_avg`` toward the origin (rows summing to < 1), and
    the bias ``d = (nbr_avg - w) / T`` would then drag every parameter
    toward zero each local step — enough to stall learning outright on the
    straggler workload.  All-zero rows (isolated peers) stay zero.

    Args: ``consts`` — one round's (K, K) slice; ``decay`` — (K,) f32
    per-sender multipliers; ``stochasticity`` — the active protocol's
    declared normalization.  With ``decay == 1`` everywhere the result
    equals ``consts`` up to fp reassociation of the diagonal.
    """
    if stochasticity not in ("row", "column"):
        raise ValueError(f"unknown stochasticity {stochasticity!r}")
    w = consts.w.astype(jnp.float32)
    decay = decay.astype(jnp.float32)
    diag = jnp.diagonal(w)
    off = (w - jnp.diag(diag)) * decay[None, :]  # axis 1 indexes the sender
    axis = 1 if stochasticity == "row" else 0
    new_diag = 1.0 - jnp.sum(off, axis=axis)
    beta_d = consts.beta * decay[None, :]
    row_sums = jnp.sum(beta_d, axis=1, keepdims=True)
    beta = jnp.where(row_sums > 0, beta_d / jnp.where(row_sums > 0, row_sums, 1.0), 0.0)
    return ProtocolConstants(w=off + jnp.diag(new_diag), beta=beta)


class PushSumState(NamedTuple):
    """Per-peer push-sum mass y_k; sum_k y_k == K is conserved every round."""

    mass: jax.Array  # (K,) f32


class SparseRoundOps(NamedTuple):
    """One round of ``graph.SparseSchedule`` on device: the degree-bounded
    mixing operands the hierarchical runtime consumes.

    Full-K form (replicated across the mesh) or a device's row block — the
    leading axis is K or K/devices accordingly.  ``nbr_idx`` holds GLOBAL
    peer indices either way; padding slots point at the row's own index with
    weight 0.0.
    """

    self_w: jax.Array  # (K,) f32 — diagonal of W (row) / A (column)
    nbr_idx: jax.Array  # (K, D) int32 — in-neighbor global indices
    nbr_w: jax.Array  # (K, D) f32 — off-diagonal weights
    beta: jax.Array  # (K, D) f32 — affinity weights


class StackedExchange:
    """How a runtime sees its neighbours, as far as a protocol needs to know:
    the stacked (vmap) runtime's exchange, where every block holds all K
    peers and each method is the identity.  The mesh runtimes' exchanges
    (``p2p._Lanes``, ``p2p._Bridge``, ``p2p._Segment``) override them:

      ``rows(a)`` — the rows of a (K, ...) array this runtime's mix computes:
                    all K (vmap; the hierarchical bridge, which slices after
                    the reduction), this peer's one row (pod), or the block
                    as given (segment);
      ``own(a)``  — the rows of a (K, ...) array this runtime holds;
      ``view(v)`` — what this runtime sees of its senders' block ``v``: the
                    stack itself (vmap), the lanes' reconstruction (pod), the
                    all-gathered stack (bridge) or the (p, D) neighbour slots
                    (segment).
    """

    def rows(self, a: jax.Array) -> jax.Array:
        """The rows this runtime's mix computes."""
        return a

    def own(self, a: jax.Array) -> jax.Array:
        """The rows this runtime holds."""
        return a

    def view(self, v: jax.Array) -> jax.Array:
        """This runtime's view of its senders' block."""
        return v


class ConsensusProtocol:
    """Interface of one consensus-step rule over stacked (K, ...) parameters.

    Every runtime drives a protocol through one per-step ``begin`` and one
    per-leaf mix (``mix_leaf``, or ``mix_split_leaf`` on substituting
    wires); what varies between runtimes is the exchange ``ex`` the phase
    passes in (``StackedExchange`` and its subclasses).  The protocol's
    scalar state (push-sum's mass) rides ``ex.view`` like the parameters,
    but always uncompressed and undelayed: the phase's wire stages
    substitute parameter views only.
    """

    name: str = "base"
    # Whether the protocol's consensus point is unbiased on directed
    # (asymmetric-adjacency) schedules; the runtime warns when a
    # directed-incapable protocol is configured on a directed schedule.
    directed_capable: bool = False
    # Which stochasticity the protocol's ``w`` matrix obeys ("row" for
    # gossip-style averaging, "column" for push-sum mass splitting).  The
    # adaptive (state-dependent) schedule path reads this to build each
    # round's on-device matrices with the right normalization
    # (``graph.adaptive_round_matrices(..., stochasticity=...)``); the
    # pretraced path encodes the same choice inside ``constants``.
    stochasticity: str = "row"

    def init_state(self, params: PyTree, data_sizes: Sequence[int] | None = None) -> PyTree:
        """Per-run protocol state (a pytree carried in ``P2PState.protocol``)."""
        raise NotImplementedError

    def constants(
        self,
        schedule: graph_lib.GraphSchedule,
        mixing: str = "data_weighted",
        *,
        data_sizes: Sequence[int] | None = None,
        consensus_step_size: float | np.ndarray = 1.0,
    ) -> ProtocolConstants:
        """Stacked (R, K, K) numpy round constants for a whole schedule."""
        raise NotImplementedError

    def begin(
        self, proto_state: PyTree, consts, ex, *, split: bool = False
    ) -> tuple[PyTree, Any]:
        """Per-consensus-step setup, run once per step before the leaves.

        Everything that does not scale with the parameter leaves lives here:
        the protocol's scalar work (push-sum's mass lane and new mass) and
        this runtime's weight operands.  ``consts`` is the round's
        ``ProtocolConstants`` (one (K, K) slice) or, on the hierarchical
        segment exchange, this device's ``SparseRoundOps`` block.  ``split``
        asks for the operands of ``mix_split_leaf`` instead of ``mix_leaf``.
        Returns ``(new proto_state, ctx)``; ``ctx`` is opaque to the runtime.
        """
        raise NotImplementedError

    def mix_leaf(self, ctx, x: jax.Array, view: jax.Array) -> jax.Array:
        """One leaf of the plain mix: every sender, own row included, is
        read from ``view`` (``x`` is this runtime's own block, used only by
        the segment form's self term).  Returns ``ex.rows`` rows of output:
        the runtime keeps its own with ``ex.own``-style slicing after the
        reduction, which is what keeps every dense form fp32 bit-identical
        to the stacked einsum."""
        raise NotImplementedError

    def mix_split_leaf(self, ctx, x: jax.Array, view: jax.Array) -> jax.Array:
        """One leaf of the CONVEX split mix, for wires whose senders are
        seen through a substitute (compressed estimates, stale snapshots).

        The self term — never on the wire — runs on this runtime's TRUE
        block ``x`` (the diagonal weights), the off-diagonal accumulation on
        the substitute stack ``view``, whose own rows are never read.  This
        is a contraction of ``x`` toward values the substitutes bound, so
        estimate lag or staleness cannot feed back into parameter growth;
        CHOCO's additive correction form ``x + (mix(x_hat) - x_hat_self)``
        was tried and diverges exponentially on the non-IID k8 workload at
        1% top-k (the own-estimate error enters with a POSITIVE sign and
        compounds through local training).
        """
        raise NotImplementedError

    def mix(
        self, proto_state: PyTree, params: PyTree, consts: ProtocolConstants
    ) -> tuple[PyTree, PyTree]:
        """One plain step on the whole stacked tree (the vmap runtime's
        mix); returns (new proto_state, new params)."""
        proto_state, ctx = self.begin(proto_state, consts, StackedExchange())
        return proto_state, jax.tree.map(lambda x: self.mix_leaf(ctx, x, x), params)


def _diag_split(a: jax.Array, ex) -> tuple[jax.Array, jax.Array]:
    """(off-diagonal rows, own diagonal entries) of a (K, K) f32 matrix."""
    diag = jnp.diagonal(a)  # (K,)
    return ex.rows(a - jnp.diag(diag)), ex.own(diag)


def _bcast(v: jax.Array, like: jax.Array) -> jax.Array:
    """``v`` (one entry per leading row) reshaped to broadcast over ``like``."""
    return v.reshape(v.shape + (1,) * (like.ndim - v.ndim))


class GossipProtocol(ConsensusProtocol):
    """The paper's protocol: row-stochastic averaging (Eq. 4), stateless."""

    name = "gossip"

    def init_state(self, params: PyTree, data_sizes: Sequence[int] | None = None) -> PyTree:
        """Gossip carries no protocol state: always ``()``."""
        return ()

    def constants(
        self,
        schedule: graph_lib.GraphSchedule,
        mixing: str = "data_weighted",
        *,
        data_sizes: Sequence[int] | None = None,
        consensus_step_size: float | np.ndarray = 1.0,
    ) -> ProtocolConstants:
        """Row-stochastic (R, K, K) W/Beta stacks for the schedule."""
        w, beta = graph_lib.schedule_matrices(
            schedule, mixing, data_sizes=data_sizes,
            consensus_step_size=consensus_step_size,
        )
        return ProtocolConstants(w=w, beta=beta)

    def begin(
        self, proto_state: PyTree, consts, ex, *, split: bool = False
    ) -> tuple[PyTree, Any]:
        """This runtime's rows of W (split: off-diagonal rows + own
        diagonal), or the segment block's slot weights as they are."""
        if isinstance(consts, SparseRoundOps):
            return proto_state, consts
        if split:
            return proto_state, _diag_split(consts.w.astype(jnp.float32), ex)
        return proto_state, ex.rows(consts.w)

    def mix_leaf(self, ctx, x: jax.Array, view: jax.Array) -> jax.Array:
        """``W x`` (Eq. 4's averaging): the rows' einsum over the view, or
        the weighted slot sum of the segment form."""
        if isinstance(ctx, SparseRoundOps):
            return consensus_lib.mix_slots(ctx.self_w, ctx.nbr_w, x, view)
        return consensus_lib.mix_leaf(ctx, view)

    def mix_split_leaf(self, ctx, x: jax.Array, view: jax.Array) -> jax.Array:
        """Convex gossip: ``W_kk x_k + sum_{j != k} W_kj view_j``.

        Row-stochastic W makes this a convex combination of the true own
        parameters and the substitutes — exactly ``W x`` once the
        substitutes equal the live parameters.
        """
        w_off, diag = ctx
        own = _bcast(diag, x) * x.astype(jnp.float32)
        nbr = jnp.einsum(
            "kj,j...->k...", w_off, view.astype(jnp.float32),
            precision=jax.lax.Precision.HIGHEST,
        )
        return (own + nbr).astype(x.dtype)


class PushSumProtocol(ConsensusProtocol):
    """Directed push-sum gossip: column-stochastic weights + mass correction."""

    name = "push_sum"
    directed_capable = True
    stochasticity = "column"

    def init_state(
        self, params: PyTree, data_sizes: Sequence[int] | None = None
    ) -> PushSumState:
        """Initial (K,) mass: data-size-proportional, normalized to sum K."""
        k = jax.tree.leaves(params)[0].shape[0]
        if data_sizes is None:
            mass = np.ones(k)
        else:
            n = np.asarray(data_sizes, dtype=np.float64)
            if n.shape != (k,) or (n <= 0).any():
                raise ValueError("data_sizes must be positive, one per peer")
            # y_k proportional to n_k, normalized to sum K: the de-biased
            # estimates then converge to the data-weighted parameter average.
            mass = k * n / n.sum()
        return PushSumState(mass=jnp.asarray(mass, jnp.float32))

    def constants(
        self,
        schedule: graph_lib.GraphSchedule,
        mixing: str = "data_weighted",
        *,
        data_sizes: Sequence[int] | None = None,
        consensus_step_size: float | np.ndarray = 1.0,
    ) -> ProtocolConstants:
        """Column-stochastic (R, K, K) A/Beta stacks for the schedule."""
        w, beta = graph_lib.schedule_matrices(
            schedule, mixing, data_sizes=data_sizes,
            consensus_step_size=consensus_step_size, stochasticity="column",
        )
        return ProtocolConstants(w=w, beta=beta)

    def begin(
        self, proto_state: PushSumState, consts, ex, *, split: bool = False
    ) -> tuple[PushSumState, Any]:
        """The mass lane: the senders' masses ride ``ex.view`` (the same
        lanes, gather or ring as the parameters), then the new mass.

        The dense forms run the FULL (K, K) x (K,) matvec and keep this
        runtime's rows: a (1, K) x (K,) dot is too narrow for XLA to reduce
        in the same order as the stacked matvec, while the full product —
        on zero-padded masses whose foreign rows are discarded — shares its
        primitive shape and therefore its bits.  The segment form pre-scales
        the slot weights by the sender masses (the mass-lane trick of
        kernels/consensus_mix/ops.py, block-sharded).
        """
        if isinstance(consts, SparseRoundOps):
            y = proto_state.mass.astype(jnp.float32)  # (p,)
            y_slots = ex.view(y)  # (p, D)
            ops = consts._replace(
                self_w=consts.self_w * y, nbr_w=consts.nbr_w * y_slots
            )
            y_new = ops.self_w + jnp.sum(ops.nbr_w, axis=1)
            return PushSumState(mass=y_new), (ops, y_slots, y_new)
        a = consts.w.astype(jnp.float32)  # (K, K)
        y = proto_state.mass.astype(jnp.float32)  # this runtime's masses
        y_send = ex.view(y)  # (K,) — zero for peers this one never hears
        y_new = jnp.einsum("kj,j->k", a, y_send, precision=jax.lax.Precision.HIGHEST)
        state = PushSumState(mass=ex.own(y_new))
        if split:
            return state, _diag_split(a, ex) + (y, y_send, ex.rows(y_new))
        return state, (ex.rows(a), y_send, ex.rows(y_new))

    def mix_leaf(self, ctx, x: jax.Array, view: jax.Array) -> jax.Array:
        """One push-sum step: mass-biased numerators, de-biased by the new
        mass (``num_k = sum_j A_kj y_j w_j``, ``w_k' = num_k / y_k'``).

        Zero view rows (non-in-neighbours) stay zero after the bias multiply
        and meet zero weights — exactly +-0.0, as in the dense einsum.
        """
        w, y_send, y_new = ctx
        if isinstance(w, SparseRoundOps):
            xf = x.astype(jnp.float32)
            num = _bcast(w.self_w, xf) * xf + jnp.sum(
                _bcast(w.nbr_w, view) * view.astype(jnp.float32), axis=1
            )
        else:
            biased = view.astype(jnp.float32) * _bcast(y_send, view)
            num = jnp.einsum(
                "kj,j...->k...", w, biased, precision=jax.lax.Precision.HIGHEST
            )
        return (num / _bcast(y_new, num)).astype(x.dtype)

    def mix_split_leaf(self, ctx, x: jax.Array, view: jax.Array) -> jax.Array:
        """Convex push-sum: the numerator's self term on the true biased
        block, the off-diagonal terms on the sender-mass-biased substitutes,
        divided by the new mass (mass conservation stays exact)."""
        a_off, diag, y, y_send, y_new = ctx
        own = _bcast(diag, x) * (x.astype(jnp.float32) * _bcast(y, x))
        nbr = jnp.einsum(
            "kj,j...->k...", a_off, view.astype(jnp.float32) * _bcast(y_send, view),
            precision=jax.lax.Precision.HIGHEST,
        )
        return ((own + nbr) / _bcast(y_new, own)).astype(x.dtype)


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------

_REGISTRY: dict[str, ConsensusProtocol] = {}


def register_protocol(protocol: ConsensusProtocol) -> ConsensusProtocol:
    """Add a protocol instance to the registry (name must be unique)."""
    if not protocol.name or protocol.name == "base":
        raise ValueError("protocol needs a distinct name")
    if protocol.name in _REGISTRY:
        raise ValueError(f"protocol {protocol.name!r} already registered")
    _REGISTRY[protocol.name] = protocol
    return protocol


def get_protocol(name: str) -> ConsensusProtocol:
    """Look up a registered protocol by name (ValueError on unknown)."""
    try:
        return _REGISTRY[name]
    except KeyError:
        raise ValueError(
            f"unknown protocol {name!r}; one of {protocol_names()}"
        ) from None


def protocol_names() -> tuple[str, ...]:
    """Registered protocol names, in registration order."""
    return tuple(_REGISTRY)


register_protocol(GossipProtocol())
register_protocol(PushSumProtocol())
