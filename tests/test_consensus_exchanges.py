"""The one consensus phase on every exchange, without a mesh.

A named ``jax.vmap`` axis stands in for the mesh axis: the pod exchange
(``p2p._Lanes``, one peer per slice) and the hierarchical exchanges
(``p2p._Bridge`` and ``p2p._Segment``, a block of peers per slice) run their
ppermutes and all-gathers across the vmapped blocks, so the single-device
test run reaches the paths the ``mesh`` tests cover on a real mesh.  Every
case is compared with the stacked (vmap) phase at its own contract's
tolerance: bit equality on the bridge; allclose on the CHOCO wire (its pod
form mixes a plain row over the estimates, the stacked form the
diagonal/off-diagonal split) and on the segment form (slot-ordered sums).
The pod live and snapshot wires are bit-identical to the stacked phase on a
real mesh by contract, but on the CPU the (1, K) row of a (K, F) leaf
reduces in another order than the stacked (K, K) product, by an ulp or two
(the same leaf the ``mesh`` parity tests trip on with this jax, ROADMAP
design debt 8); they keep the 1e-6 bound the earlier vmap-axis tests used.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import compression as compression_lib
from repro.core import graph as gl
from repro.core import p2p, protocols

K = 8
DEVICES, BLOCK = 4, 2  # the hierarchical layout: K = DEVICES * BLOCK
SIZES = np.arange(1, K + 1)


def _cfg(wire, protocol, affinity_d):
    extra = {}
    if wire in ("topk", "qint8"):
        extra = dict(compressor=wire, topk_frac=0.25)
    elif wire == "snapshot":
        extra = dict(steps_profile="straggler", staleness_bound=2,
                     straggler_frac=0.5, straggler_period=3)
    return p2p.P2PConfig(
        algorithm="p2pl_affinity", num_peers=K, local_steps=3,
        consensus_steps=2, eta_d=0.5 if affinity_d else 0.0, eta_b=0.1,
        topology="ring", protocol=protocol, **extra,
    )


def _state(cfg):
    """A post-local-phase state whose substitutes differ from the params."""
    rng = np.random.default_rng(0)

    def rand(*shape):
        return jnp.asarray(rng.normal(size=shape), jnp.float32)

    params = {"w": rand(K, 5, 3), "b": rand(K, 7)}
    near = lambda p: jax.tree.map(lambda x: x + 0.1 * rand(*x.shape), p)  # noqa: E731
    staleness = ()
    if cfg.staleness_bound > 0:
        staleness = p2p.StalenessState(
            published=near(params),
            age=jnp.asarray([0, 1, 2, 0, 1, 2, 0, 1], jnp.int32),
        )
    return p2p.P2PState(
        params=params,
        momentum=jax.tree.map(jnp.zeros_like, params),
        d_bias=jax.tree.map(jnp.zeros_like, params),
        b_bias=near(jax.tree.map(jnp.zeros_like, params)),
        round_idx=jnp.asarray(4, jnp.int32),
        protocol=protocols.get_protocol(cfg.protocol).init_state(params, SIZES),
        compression=compression_lib.from_config(cfg).init_estimate(near(params)),
        staleness=staleness,
    )


def _schedule():
    return gl.static_schedule(gl.build_graph("ring", K))


def _dense_consts(cfg):
    c = protocols.get_protocol(cfg.protocol).constants(
        _schedule(), cfg.mixing, data_sizes=SIZES
    )
    return protocols.ProtocolConstants(
        jnp.asarray(c.w[0], jnp.float32), jnp.asarray(c.beta[0], jnp.float32)
    )


def _axes(state, peer_axis):
    """vmap in_axes: peer-stacked leaves split, round_idx and the replicated
    estimate stack broadcast."""
    return state._replace(
        params=peer_axis, momentum=peer_axis, d_bias=peer_axis,
        b_bias=peer_axis, round_idx=None, protocol=peer_axis,
        compression=None, staleness=peer_axis,
    )


def _split(state, blocks):
    """Peer-stacked leaves (K, ...) -> (blocks, K / blocks, ...)."""
    def split(ax, sub):
        if ax is None:
            return sub
        return jax.tree.map(lambda x: x.reshape((blocks, -1) + x.shape[1:]), sub)

    return jax.tree.map(split, _axes(state, 0), state, is_leaf=lambda a: a is None)


def _merge(out, state):
    """Undo ``_split`` on a vmapped phase's output; replicated leaves (round
    index, estimate stack) must agree across blocks and are returned once."""
    def replicated(x):
        x = np.asarray(x)
        for rep in x[1:]:
            np.testing.assert_array_equal(rep, x[0])
        return x[0]

    def merge(ax, sub):
        if ax is None:
            return jax.tree.map(replicated, sub)
        return jax.tree.map(lambda x: np.asarray(x).reshape((-1,) + x.shape[2:]), sub)

    return jax.tree.map(merge, _axes(state, 0), out, is_leaf=lambda a: a is None)


def _compare(got, want, exact, **tol):
    for (path, w), (_, g) in zip(
        jax.tree_util.tree_leaves_with_path(want),
        jax.tree_util.tree_leaves_with_path(got),
    ):
        msg = jax.tree_util.keystr(path)
        if exact:
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w), err_msg=msg)
        else:
            np.testing.assert_allclose(
                np.asarray(g, np.float64), np.asarray(w, np.float64),
                err_msg=msg, **tol,
            )


@pytest.mark.parametrize("affinity_d", [True, False], ids=["d", "no_d"])
@pytest.mark.parametrize("protocol", ["gossip", "push_sum"])
@pytest.mark.parametrize("wire", ["live", "topk", "qint8", "snapshot"])
def test_pod_exchange_matches_stacked_phase(wire, protocol, affinity_d):
    """Every wire on the pod exchange (one peer per vmapped block, lanes as
    ppermutes) against the stacked phase."""
    cfg = _cfg(wire, protocol, affinity_d)
    state, consts = _state(cfg), _dense_consts(cfg)
    want = p2p.consensus_phase(state, cfg, consts)
    ex = p2p._Lanes("pod", gl.schedule_lanes(_schedule()), K)
    out = jax.vmap(
        lambda s: p2p.consensus_phase(s, cfg, consts, exchange=ex),
        in_axes=(_axes(state, 0),), axis_name="pod",
    )(_split(state, K))
    if wire in ("live", "snapshot"):
        _compare(_merge(out, state), want, exact=False, rtol=0, atol=1e-6)
    else:  # the pod CHOCO contract (tests/test_compression.py, mesh)
        _compare(_merge(out, state), want, exact=False, atol=5e-3, rtol=1e-3)


@pytest.mark.parametrize("affinity_d", [True, False], ids=["d", "no_d"])
@pytest.mark.parametrize("protocol", ["gossip", "push_sum"])
@pytest.mark.parametrize("mode", ["bridge", "segment"])
def test_hier_exchange_matches_stacked_phase(mode, protocol, affinity_d):
    """The hierarchical exchanges (BLOCK peers per vmapped device; the
    segment ring as ppermutes) against the stacked phase on the same
    losslessly densified constants."""
    cfg = _cfg("live", protocol, affinity_d)
    proto = protocols.get_protocol(protocol)
    sparse = gl.SparseSchedule.from_schedule(
        _schedule(), cfg.mixing, data_sizes=SIZES,
        stochasticity=proto.stochasticity,
    )
    w, beta = sparse.to_dense()
    dense = protocols.ProtocolConstants(
        jnp.asarray(w[0], jnp.float32), jnp.asarray(beta[0], jnp.float32)
    )
    ops = protocols.SparseRoundOps(
        jnp.asarray(sparse.self_w[0], jnp.float32),
        jnp.asarray(sparse.nbr_idx[0], jnp.int32),
        jnp.asarray(sparse.nbr_w[0], jnp.float32),
        jnp.asarray(sparse.beta[0], jnp.float32),
    )
    state = _state(cfg)
    want = p2p.consensus_phase(state, cfg, dense)

    def per_device(s):
        row0 = (jax.lax.axis_index("pod") * BLOCK).astype(jnp.int32)
        ex, consts = p2p._Bridge("pod", row0, BLOCK), dense
        if mode == "segment":
            consts = protocols.SparseRoundOps(*(ex.own(o) for o in ops))
            ex = p2p._Segment("pod", DEVICES, consts.nbr_idx)
        return p2p.consensus_phase_hier(s, cfg, consts, exchange=ex)

    out = jax.vmap(per_device, in_axes=(_axes(state, 0),), axis_name="pod")(
        _split(state, DEVICES)
    )
    _compare(_merge(out, state), want, exact=mode == "bridge",
             rtol=1e-5, atol=1e-5)


def _mlp_init(key):
    return {"w": jax.random.normal(key, (6, 4)), "b": jnp.zeros((4,))}


def _mlp_loss(p, batch):
    x, y = batch
    return jnp.mean(jnp.square(x @ p["w"] + p["b"] - y))


@pytest.mark.parametrize("runtime", [
    "vmap",
    "hier",
    pytest.param("pod", marks=[
        pytest.mark.mesh,
        pytest.mark.skipif(jax.device_count() < K, reason=f"needs >= {K} devices"),
    ]),
])
def test_patched_phase_replaces_consensus(runtime, monkeypatch):
    """The round steps look the phase up by name when they trace: patching
    ``consensus_phase`` (vmap, pod) or ``consensus_phase_hier`` (the
    hierarchical runtime) replaces that runtime's whole consensus, the way
    the benchmark's ``no_exchange`` fault does."""
    from repro.launch import mesh as mesh_lib
    from repro.sharding import specs as specs_lib

    cfg = _cfg("live", "gossip", True)
    state = p2p.init_state(jax.random.PRNGKey(0), _mlp_init, cfg)
    rng = np.random.default_rng(0)
    batches = (jnp.asarray(rng.normal(size=(3, K, 5, 6)), jnp.float32),
               jnp.asarray(rng.normal(size=(3, K, 5, 4)), jnp.float32))
    if runtime == "vmap":
        make = lambda: p2p.make_round_fn(_mlp_loss, cfg)  # noqa: E731
    else:
        mesh = (jax.sharding.Mesh(np.asarray(jax.devices()[:1]), ("pod",))
                if runtime == "hier" else mesh_lib.make_peer_mesh(K))
        state = specs_lib.shard_peer_tree(state, mesh)
        make = lambda: p2p.make_sharded_round_fn(  # noqa: E731
            _mlp_loss, cfg, mesh, peers_per_device=K if runtime == "hier" else None
        )
    local, mixed, _ = make()(state, batches)
    assert not np.array_equal(np.asarray(local.params["w"]), np.asarray(mixed.params["w"]))

    def keep(s, *a, **kw):
        return s._replace(round_idx=s.round_idx + 1)

    name = "consensus_phase_hier" if runtime == "hier" else "consensus_phase"
    monkeypatch.setattr(p2p, name, keep)
    local, kept, _ = make()(state, batches)
    for a, b in zip(jax.tree.leaves(local.params), jax.tree.leaves(kept.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert int(kept.round_idx) == int(local.round_idx) + 1
