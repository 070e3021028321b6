"""Sharded peer-axis runtime: shard_map over a REAL mesh vs the vmap runtime.

The parity tests assert fp32 BIT-identity (np.array_equal, not allclose) on
every state leaf, every round, for both protocols on every schedule family —
the acceptance contract of the sharded runtime.  They need one device per
peer, so they carry the ``mesh`` marker and skip unless launched with

    XLA_FLAGS=--xla_force_host_platform_device_count=8 python -m pytest -m mesh

(CI's multi-device job does exactly this).  The fail-fast tests at the bottom
run everywhere — including the single-device tier-1 environment, where they
exercise the too-few-devices error paths.
"""
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import consensus as cl
from repro.core import graph as gl
from repro.core import p2p, protocols
from repro.launch import mesh as mesh_lib
from repro.sharding import specs as specs_lib

K = 8

needs_mesh = pytest.mark.skipif(
    jax.device_count() < K,
    reason=f"needs >= {K} devices "
           f"(XLA_FLAGS=--xla_force_host_platform_device_count={K})",
)


def _init_fn(key):
    k1, k2 = jax.random.split(key)
    return {
        "w1": jax.random.normal(k1, (6, 16)),
        "b1": jnp.zeros((16,)),
        "w2": jax.random.normal(k2, (16, 4)),
    }


def _mlp_loss(p, batch):
    x, y = batch
    h = jnp.tanh(x @ p["w1"] + p["b1"])
    return jnp.mean(jnp.sum(jnp.square(h @ p["w2"] - y), axis=-1))


def _round_batches(rng, t):
    x = jnp.asarray(rng.normal(size=(t, K, 10, 6)), jnp.float32)
    y = jnp.asarray(rng.normal(size=(t, K, 10, 4)), jnp.float32)
    return (x, y)


SCHEDULE_GRID = [
    ("static", {}),
    ("link_dropout", {}),
    ("round_robin", {"round_robin_topologies": ("ring", "star")}),
    ("one_way_matching", {}),
    ("random_matching", {}),
    ("peer_churn", {}),  # degree-0 rounds: churned-out peers keep their state
    # state-dependent topology: partners picked ON DEVICE from run state each
    # round (pod side: complete-graph candidate lanes, adaptively nulled)
    ("adaptive", {"partner_rule": "loss_proximity"}),
    ("adaptive", {"partner_rule": "eps_greedy"}),
]


@pytest.mark.mesh
@needs_mesh
@pytest.mark.parametrize("protocol", ["gossip", "push_sum"])
@pytest.mark.parametrize("schedule,extra", SCHEDULE_GRID, ids=[s for s, _ in SCHEDULE_GRID])
def test_shard_map_round_bit_identical_to_vmap(protocol, schedule, extra):
    """Every leaf of (after_local, after_consensus, losses) matches the vmap
    runtime bit for bit, on every round of a full schedule period."""
    cfg = p2p.P2PConfig(
        algorithm="p2pl_affinity", num_peers=K, local_steps=3,
        consensus_steps=2, lr=0.1, momentum=0.3, eta_d=0.5, eta_b=0.1,
        topology="ring", protocol=protocol, schedule=schedule,
        schedule_rounds=5, **extra,
    )
    sizes = np.arange(1, K + 1)
    with warnings.catch_warnings():
        # gossip on the directed one_way_matching schedule warns (biased
        # consensus point) — deliberate here: parity covers the grid anyway
        warnings.simplefilter("ignore")
        vmap_fn = p2p.make_round_fn(_mlp_loss, cfg, data_sizes=sizes)
        mesh = mesh_lib.make_peer_mesh(K)
        shard_fn = p2p.make_sharded_round_fn(_mlp_loss, cfg, mesh, data_sizes=sizes)
    s_vmap = p2p.init_state(jax.random.PRNGKey(0), _init_fn, cfg, data_sizes=sizes)
    s_shard = specs_lib.shard_peer_tree(s_vmap, mesh)

    rng = np.random.default_rng(0)
    for r in range(6):  # crosses the period boundary (R=5)
        batches = _round_batches(rng, cfg.local_steps)
        al_v, s_vmap, loss_v = vmap_fn(s_vmap, batches)
        al_s, s_shard, loss_s = shard_fn(s_shard, batches)
        want = jax.tree_util.tree_leaves_with_path((al_v, s_vmap, loss_v))
        got = jax.tree_util.tree_leaves_with_path((al_s, s_shard, loss_s))
        assert len(want) == len(got)
        for (path, w), (_, g) in zip(want, got):
            assert np.array_equal(np.asarray(w), np.asarray(g)), (
                f"{protocol}/{schedule} round {r} leaf "
                f"{jax.tree_util.keystr(path)} diverged: max |diff| = "
                f"{np.abs(np.asarray(w, np.float64) - np.asarray(g, np.float64)).max():.3e}"
            )


@pytest.mark.mesh
@needs_mesh
def test_sharded_runtime_one_compile():
    """The sharded round keeps the one-compile property on a time-varying
    schedule (round selection happens inside the traced program)."""
    traces = [0]

    def counting_loss(params, batch):
        traces[0] += 1
        return _mlp_loss(params, batch)

    cfg = p2p.P2PConfig(
        algorithm="p2pl_affinity", num_peers=K, local_steps=2,
        consensus_steps=1, lr=0.05, eta_d=0.5, topology="ring",
        schedule="link_dropout", schedule_rounds=4,
    )
    mesh = mesh_lib.make_peer_mesh(K)
    fn = p2p.make_sharded_round_fn(counting_loss, cfg, mesh)
    state = specs_lib.shard_peer_tree(
        p2p.init_state(jax.random.PRNGKey(1), _init_fn, cfg), mesh
    )
    rng = np.random.default_rng(1)
    for _ in range(9):
        _, state, losses = fn(state, _round_batches(rng, cfg.local_steps))
    assert int(state.round_idx) == 9
    assert np.isfinite(float(jnp.mean(losses)))
    assert traces[0] <= 2  # value + grad trace of the single compile


@pytest.mark.mesh
@needs_mesh
@pytest.mark.parametrize("protocol", ["gossip", "push_sum"])
@pytest.mark.parametrize("schedule,extra", [
    ("static", {}),
    ("round_robin", {"round_robin_topologies": ("ring", "star")}),
    ("adaptive", {"partner_rule": "loss_proximity"}),
], ids=["static", "round_robin", "adaptive"])
def test_scan_driver_pod_bit_identical_to_python_loop_and_vmap(
    protocol, schedule, extra
):
    """The scanned driver on the POD runtime: bit-identical to (a) the
    python-loop pod driver and (b) the scanned VMAP driver, across two chunks
    that cross the schedule period — the leaf-pipelined ppermute overlap must
    not cost a single ulp."""
    cfg = p2p.P2PConfig(
        algorithm="p2pl_affinity", num_peers=K, local_steps=3,
        consensus_steps=2, lr=0.1, momentum=0.3, eta_d=0.5, eta_b=0.1,
        topology="ring", protocol=protocol, schedule=schedule,
        schedule_rounds=5, **extra,
    )
    sizes = np.arange(1, K + 1)
    chunk = 3
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        mesh = mesh_lib.make_peer_mesh(K)
        pod_round = p2p.make_sharded_round_fn(_mlp_loss, cfg, mesh, data_sizes=sizes)
        pod_drive = p2p.make_scan_driver(
            _mlp_loss, cfg, data_sizes=sizes, mesh=mesh, donate=False
        )
        vmap_drive = p2p.make_scan_driver(_mlp_loss, cfg, data_sizes=sizes, donate=False)
    state0 = p2p.init_state(jax.random.PRNGKey(0), _init_fn, cfg, data_sizes=sizes)
    state0_pod = specs_lib.shard_peer_tree(state0, mesh)

    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(2, chunk, 3, K, 10, 6)), jnp.float32)
    y = jnp.asarray(rng.normal(size=(2, chunk, 3, K, 10, 4)), jnp.float32)

    s_py, al_py, losses_py = state0_pod, None, []
    for c in range(2):
        for r in range(chunk):
            al_py, s_py, loss_r = pod_round(s_py, (x[c, r], y[c, r]))
            losses_py.append(np.asarray(loss_r))
    s_pod, al_pod, losses_pod = state0_pod, None, []
    s_vmap, al_vmap, losses_vmap = state0, None, []
    for c in range(2):
        al_pod, s_pod, loss_c = pod_drive(s_pod, (x[c], y[c]))
        losses_pod.append(np.asarray(loss_c))
        al_vmap, s_vmap, loss_v = vmap_drive(s_vmap, (x[c], y[c]))
        losses_vmap.append(np.asarray(loss_v))

    for tag, want, got in [
        ("pod python-loop vs pod scan",
         (al_py, s_py, np.stack(losses_py)),
         (al_pod, s_pod, np.concatenate(losses_pod))),
        ("pod scan vs vmap scan",
         (al_pod, s_pod, np.concatenate(losses_pod)),
         (al_vmap, s_vmap, np.concatenate(losses_vmap))),
    ]:
        want_l = jax.tree_util.tree_leaves_with_path(want)
        got_l = jax.tree_util.tree_leaves_with_path(got)
        assert len(want_l) == len(got_l)
        for (path, w), (_, g) in zip(want_l, got_l):
            assert np.array_equal(np.asarray(w), np.asarray(g)), (
                f"{protocol}/{schedule} {tag}: leaf "
                f"{jax.tree_util.keystr(path)} diverged"
            )


@pytest.mark.mesh
@needs_mesh
def test_scan_driver_pod_one_compile_and_donation():
    """One compile for a multi-chunk pod scan run + the donated input state
    is consumed (its sharded buffers deleted)."""
    traces = [0]

    def counting_loss(params, batch):
        traces[0] += 1
        return _mlp_loss(params, batch)

    cfg = p2p.P2PConfig(
        algorithm="p2pl_affinity", num_peers=K, local_steps=2,
        consensus_steps=1, lr=0.05, eta_d=0.5, topology="ring",
        schedule="link_dropout", schedule_rounds=4,
    )
    mesh = mesh_lib.make_peer_mesh(K)
    drive = p2p.make_scan_driver(counting_loss, cfg, mesh=mesh)
    state = specs_lib.shard_peer_tree(
        p2p.init_state(jax.random.PRNGKey(1), _init_fn, cfg), mesh
    )
    first_state = state
    rng = np.random.default_rng(1)
    chunk = 4
    for _ in range(3):
        x = jnp.asarray(rng.normal(size=(chunk, 2, K, 10, 6)), jnp.float32)
        y = jnp.asarray(rng.normal(size=(chunk, 2, K, 10, 4)), jnp.float32)
        _, state, losses = drive(state, (x, y))
    assert int(state.round_idx) == 3 * chunk
    assert np.isfinite(np.asarray(losses)).all()
    assert traces[0] <= 2  # value + grad trace of the single compile
    assert drive._cache_size() == 1  # the jit cache agrees
    assert all(leaf.is_deleted() for leaf in jax.tree.leaves(first_state))


@pytest.mark.mesh
@needs_mesh
@pytest.mark.parametrize("protocol", ["gossip", "push_sum"])
def test_adaptive_sharded_one_compile(protocol):
    """Adaptive partner selection inside the sharded round: the on-device
    matching (all_gather'd loss K-vector + threaded key) keeps the
    one-compile property — no host callback, no retrace across rounds."""
    traces = [0]

    def counting_loss(params, batch):
        traces[0] += 1
        return _mlp_loss(params, batch)

    cfg = p2p.P2PConfig(
        algorithm="p2pl_affinity", num_peers=K, local_steps=2,
        consensus_steps=1, lr=0.05, eta_d=0.5, schedule="adaptive",
        partner_rule="eps_greedy", protocol=protocol,
    )
    mesh = mesh_lib.make_peer_mesh(K)
    fn = p2p.make_sharded_round_fn(counting_loss, cfg, mesh)
    state = specs_lib.shard_peer_tree(
        p2p.init_state(jax.random.PRNGKey(3), _init_fn, cfg), mesh
    )
    rng = np.random.default_rng(3)
    for _ in range(6):
        _, state, losses = fn(state, _round_batches(rng, cfg.local_steps))
    assert int(state.round_idx) == 6
    assert np.isfinite(float(jnp.mean(losses)))
    assert traces[0] <= 2  # value + grad trace of the single compile
    assert fn._cache_size() == 1  # the jit cache agrees
    if protocol == "push_sum":
        mass = np.asarray(state.protocol.mass)
        np.testing.assert_allclose(mass.sum(), K, rtol=1e-5)


@pytest.mark.mesh
@needs_mesh
def test_sharded_push_sum_mass_conservation():
    """The ppermute'd mass lane conserves sum_k y_k == K across rounds."""
    cfg = p2p.P2PConfig(
        algorithm="p2pl_affinity", num_peers=K, local_steps=2,
        consensus_steps=1, lr=0.05, eta_d=0.5, protocol="push_sum",
        schedule="one_way_matching", schedule_rounds=6,
    )
    mesh = mesh_lib.make_peer_mesh(K)
    fn = p2p.make_sharded_round_fn(_mlp_loss, cfg, mesh)
    state = specs_lib.shard_peer_tree(
        p2p.init_state(jax.random.PRNGKey(2), _init_fn, cfg), mesh
    )
    rng = np.random.default_rng(2)
    for _ in range(8):
        _, state, _ = fn(state, _round_batches(rng, cfg.local_steps))
        mass = np.asarray(state.protocol.mass)
        np.testing.assert_allclose(mass.sum(), K, rtol=1e-5)
        assert (mass > 0).all()


@pytest.mark.mesh
@needs_mesh
@pytest.mark.slow
def test_sharded_paper_experiment_matches_vmap_end_to_end(mnist_small):
    """The full training driver (--peer-axis pod) reproduces the vmap
    driver's accuracy trajectories exactly on the sharded_k8 workload."""
    from repro.configs.p2pl_mnist import sharded_k8
    from repro.launch.train import run_paper_experiment

    exp = sharded_k8(schedule="link_dropout", protocol="gossip", local_steps=2)
    log_v = run_paper_experiment(exp, rounds=2, data=mnist_small, peer_axis="vmap")
    log_p = run_paper_experiment(exp, rounds=2, data=mnist_small, peer_axis="pod")
    for attr in ("after_local", "after_consensus"):
        want, got = getattr(log_v, attr), getattr(log_p, attr)
        assert want.keys() == got.keys()
        for group in want:
            assert np.array_equal(np.stack(want[group]), np.stack(got[group])), (
                attr, group,
            )
    assert log_v.train_loss == log_p.train_loss


# ---------------------------------------------------------------------------
# Spec helpers + fail-fast paths (run everywhere, including tier-1's single
# device — that environment is exactly where the error paths are reachable)
# ---------------------------------------------------------------------------


def test_peer_stacked_pspecs_shapes():
    from jax.sharding import PartitionSpec as P

    tree = {
        "w": jnp.zeros((4, 5, 3)),
        "mass": jnp.zeros((4,)),
        "step": jnp.zeros(()),
    }
    specs = specs_lib.peer_stacked_pspecs(tree, peer_axis="pod")
    assert specs["w"] == P("pod", None, None)
    assert specs["mass"] == P("pod")
    assert specs["step"] == P()

    batches = {"x": jnp.zeros((3, 4, 10, 6))}
    bspecs = specs_lib.peer_batch_pspecs(batches, peer_axis="pod")
    assert bspecs["x"] == P(None, "pod", None, None)
    with pytest.raises(ValueError):
        specs_lib.peer_batch_pspecs({"x": jnp.zeros((3,))})


def test_make_peer_mesh_fails_fast_with_hint():
    too_many = jax.device_count() + 1
    with pytest.raises(RuntimeError, match="xla_force_host_platform_device_count"):
        mesh_lib.make_peer_mesh(too_many)
    with pytest.raises(ValueError):
        mesh_lib.make_peer_mesh(0)


def test_make_sharded_round_fn_validates_mesh_axis():
    mesh = mesh_lib.make_peer_mesh(1)
    cfg = p2p.P2PConfig(num_peers=2, local_steps=1)
    with pytest.raises(ValueError, match="num_peers"):
        p2p.make_sharded_round_fn(_mlp_loss, cfg, mesh)
    with pytest.raises(ValueError, match="num_peers"):
        p2p.make_sharded_round_fn(
            _mlp_loss, p2p.P2PConfig(num_peers=1, local_steps=1), mesh,
            axis_name="nope",
        )


@pytest.mark.skipif(
    jax.device_count() >= 2,
    reason="exercises the too-few-devices CLI error (single-device env only)",
)
def test_train_cli_fails_fast_on_missing_devices(capsys):
    from repro.launch import train

    with pytest.raises(SystemExit) as excinfo:
        train.main(["--experiment", "noniid_affinity", "--peer-axis", "pod",
                    "--rounds", "1"])
    assert excinfo.value.code == 2  # argparse error, not an XLA shape error
    err = capsys.readouterr().err
    assert "xla_force_host_platform_device_count" in err
    assert "num_peers=2" in err


def test_gossip_mix_sharded_under_vmap_axis(rng):
    """The protocol's per-leaf mix on the pod exchange is exercisable without
    a mesh: a vmap axis stands in for the pod axis (lane gather + row einsum
    == dense mix)."""
    k = 6
    g = gl.build_graph("ring", k)
    sched = gl.static_schedule(g)
    w, beta = gl.schedule_matrices(sched, "metropolis")
    lanes = gl.schedule_lanes(sched)
    w_dev = jnp.asarray(w[0], jnp.float32)
    consts = protocols.ProtocolConstants(w_dev, jnp.asarray(beta[0], jnp.float32))
    tree = {"w": jnp.asarray(rng.normal(size=(k, 5)), jnp.float32)}
    proto = protocols.get_protocol("gossip")
    ex = p2p._Lanes("peer", lanes, k)

    def per_peer(block):
        _, ctx = proto.begin((), consts, ex)
        return jax.tree.map(lambda x: proto.mix_leaf(ctx, x, ex.view(x))[0], block)

    blocks = jax.tree.map(lambda x: x[:, None], tree)  # (K, 1, ...) blocks
    out = jax.vmap(per_peer, axis_name="peer")(blocks)
    want = cl.mix_stacked(w_dev, tree)
    np.testing.assert_allclose(np.asarray(out["w"]), np.asarray(want["w"]), atol=1e-6)
