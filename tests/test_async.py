"""Asynchronous rounds: per-peer step budgets + bounded-staleness gossip.

Contract under test (the acceptance criteria of the async PR):

* **Config + profiles** — ``compute_profile`` honors its >= 1 invariants and
  the documented straggler/linear shapes; invalid profiles/bounds and the
  unsupported staleness x adaptive / staleness x compressed combinations
  fail loudly at config time, and the CLI surfaces the same errors.
* **Weight renormalization** — ``age_decayed_constants`` keeps gossip rows /
  push-sum columns exactly stochastic for any decay vector, and decay=1 is
  the identity.
* **Synchronous bypass** — ``staleness_bound=0`` with a uniform profile is a
  STRUCTURAL bypass (booby-trap test, like ``compressor="none"``): the async
  machinery is never entered, so bit-parity with the legacy round holds by
  construction — in both runtimes.
* **Staleness semantics** — snapshot ages never exceed the bound (forced
  delivery), a straggler's published row is frozen between publications,
  push-sum mass is conserved exactly under maximal staleness, and capped
  peers freeze parameters exactly at their budget.
* **Drivers + compilation** — the fused scan driver is bit-identical to the
  python round loop on every async state leaf, and a time-varying async run
  keeps the one-compile contract.
* **Runtimes** — the pod (shard_map) async round is fp32 BIT-identical to
  the vmap round, leaf for leaf (mesh marker: one device per peer); the
  hierarchical runtime rejects async configs with an actionable error.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import p2p
from repro.core import protocols as protocols_lib

K = 4
T = 6


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


def _cfg(protocol="gossip", schedule="static", num_peers=K, **kw):
    base = dict(
        algorithm="p2pl_affinity", num_peers=num_peers, local_steps=T,
        consensus_steps=2, lr=0.1, momentum=0.3, eta_d=0.5, eta_b=0.1,
        topology="ring", protocol=protocol, schedule=schedule,
        schedule_rounds=3, steps_profile="straggler", staleness_bound=3,
    )
    if schedule == "round_robin":
        base["round_robin_topologies"] = ("ring", "star")
    base.update(kw)
    return p2p.P2PConfig(**base)


def _round_batches(rng, t, k=K):
    x = jnp.asarray(rng.normal(size=(t, k, 10, 6)), jnp.float32)
    y = jnp.asarray(rng.normal(size=(t, k, 10, 4)), jnp.float32)
    return (x, y)


def _assert_trees_equal(want, got, context):
    want_leaves = jax.tree_util.tree_leaves_with_path(want)
    got_leaves = jax.tree_util.tree_leaves_with_path(got)
    assert len(want_leaves) == len(got_leaves)
    for (path, w), (_, g) in zip(want_leaves, got_leaves):
        assert np.array_equal(np.asarray(w), np.asarray(g)), (
            f"{context} leaf {jax.tree_util.keystr(path)} diverged"
        )


# ---------------------------------------------------------------------------
# config validation + compute profiles
# ---------------------------------------------------------------------------


def test_config_rejects_unknown_steps_profile():
    with pytest.raises(ValueError, match="steps_profile"):
        p2p.P2PConfig(num_peers=K, steps_profile="warp")


def test_config_rejects_negative_bound():
    with pytest.raises(ValueError, match="staleness_bound"):
        p2p.P2PConfig(num_peers=K, staleness_bound=-1)


def test_config_rejects_staleness_with_adaptive():
    with pytest.raises(ValueError, match="adaptive"):
        p2p.P2PConfig(num_peers=K, schedule="adaptive", staleness_bound=2)


def test_config_rejects_staleness_with_compressor():
    with pytest.raises(ValueError, match="compressor"):
        p2p.P2PConfig(num_peers=K, compressor="topk", staleness_bound=2)


def test_steps_profile_composes_with_adaptive_and_compressor():
    """Heterogeneous step budgets alone (bound=0) compose with everything:
    the mask lives in the local phase, which neither subsystem touches."""
    p2p.P2PConfig(num_peers=K, schedule="adaptive", steps_profile="straggler")
    p2p.P2PConfig(num_peers=K, compressor="topk", steps_profile="linear")


def test_use_async_property():
    assert not p2p.P2PConfig(num_peers=K).use_async
    assert p2p.P2PConfig(num_peers=K, staleness_bound=1).use_async
    assert p2p.P2PConfig(num_peers=K, steps_profile="linear").use_async


def test_uniform_profile_is_full_steps_every_round():
    cfg = p2p.P2PConfig(num_peers=K, local_steps=T)
    steps, period = p2p.compute_profile(cfg)
    assert steps.tolist() == [T] * K
    assert period.tolist() == [1] * K


def test_straggler_profile_shapes():
    cfg = p2p.P2PConfig(
        num_peers=8, local_steps=8, steps_profile="straggler",
        straggler_frac=0.25, straggler_period=4,
    )
    steps, period = p2p.compute_profile(cfg)
    # last quarter of the fleet is slow: T/4 steps, publishes every 4 rounds
    assert steps.tolist() == [8] * 6 + [2] * 2
    assert period.tolist() == [1] * 6 + [4] * 2


def test_linear_profile_ramps_and_honors_floor():
    cfg = p2p.P2PConfig(
        num_peers=5, local_steps=4, steps_profile="linear",
        straggler_period=8,
    )
    steps, period = p2p.compute_profile(cfg)
    assert steps[0] == 4 and steps[-1] >= 1
    assert (np.diff(steps) <= 0).all()  # monotone slowdown across the fleet
    assert (steps >= 1).all() and (period >= 1).all()


@pytest.mark.parametrize("profile", sorted(p2p.STEPS_PROFILES))
@pytest.mark.parametrize("num_peers,local_steps", [(2, 1), (3, 2), (8, 8),
                                                   (16, 5)])
@pytest.mark.parametrize("straggler_period", [1, 4, 16])
def test_compute_profile_invariants(profile, num_peers, local_steps,
                                    straggler_period):
    """The documented invariants hold for EVERY profile x shape: per-peer
    budgets and publication periods never fall below 1 (a zero-step peer
    would freeze, a zero period divides by zero in the delivery rule), and
    the uniform profile is exactly the synchronous (T, 1) fleet."""
    cfg = p2p.P2PConfig(
        num_peers=num_peers, local_steps=local_steps, steps_profile=profile,
        straggler_period=straggler_period,
    )
    steps, period = p2p.compute_profile(cfg)
    assert steps.shape == period.shape == (num_peers,)
    assert steps.dtype == np.int32 and period.dtype == np.int32
    assert (steps >= 1).all() and (period >= 1).all()
    assert (steps <= local_steps).all()
    if profile == "uniform":
        assert (steps == local_steps).all() and (period == 1).all()


# ---------------------------------------------------------------------------
# age-decayed weight renormalization
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("stochasticity", ["row", "column"])
def test_age_decayed_constants_stay_stochastic(stochasticity):
    rng = np.random.default_rng(0)
    w = rng.random((K, K)).astype(np.float32)
    w = w / w.sum(axis=0 if stochasticity == "column" else 1, keepdims=True)
    consts = protocols_lib.ProtocolConstants(
        w=jnp.asarray(w), beta=jnp.asarray(w)
    )
    decay = jnp.asarray([1.0, 0.5, 0.25, 0.125], jnp.float32)
    out = protocols_lib.age_decayed_constants(consts, decay, stochasticity)
    sums = np.asarray(out.w).sum(axis=1 if stochasticity == "row" else 0)
    np.testing.assert_allclose(sums, np.ones(K), atol=1e-6)
    # stale senders' outgoing weight shrinks; the diagonal absorbs the slack
    off = np.asarray(out.w) - np.diag(np.diag(np.asarray(out.w)))
    orig_off = w - np.diag(np.diag(w))
    np.testing.assert_allclose(off, orig_off * np.asarray(decay)[None, :],
                               atol=1e-7)
    # beta stays a distribution over neighbors: decayed, then row-renormalized
    # (an unnormalized beta would shrink nbr_avg — and with it every
    # parameter, through d — toward the origin)
    np.testing.assert_allclose(np.asarray(out.beta).sum(axis=1), np.ones(K),
                               atol=1e-6)


def test_age_decayed_constants_identity_at_decay_one():
    w = jnp.asarray(np.full((K, K), 1.0 / K, np.float32))
    consts = protocols_lib.ProtocolConstants(w=w, beta=w)
    out = protocols_lib.age_decayed_constants(
        consts, jnp.ones((K,), jnp.float32), "row"
    )
    np.testing.assert_allclose(np.asarray(out.w), np.asarray(w), atol=1e-7)
    np.testing.assert_allclose(np.asarray(out.beta), np.asarray(w), atol=1e-7)


def test_age_decayed_constants_rejects_unknown_stochasticity():
    w = jnp.eye(K)
    consts = protocols_lib.ProtocolConstants(w=w, beta=w)
    with pytest.raises(ValueError, match="stochasticity"):
        protocols_lib.age_decayed_constants(consts, jnp.ones((K,)), "diagonal")


# ---------------------------------------------------------------------------
# delivery rule
# ---------------------------------------------------------------------------


def test_delivery_on_schedule_and_forced_at_bound():
    cfg = _cfg(num_peers=8, straggler_frac=0.25, straggler_period=4,
               staleness_bound=2)
    # straggler periods: peers 0-5 publish every round, 6-7 every 4th round
    age = jnp.zeros((8,), jnp.int32)
    delivered, age, decay = p2p._staleness_delivery(cfg, jnp.int32(0), age)
    d = np.asarray(delivered)
    assert d[:6].all() and not d[6:].any()  # round 0: rem(0, 4) != 3
    np.testing.assert_allclose(np.asarray(decay)[6:], [0.5, 0.5])
    # ages keep climbing until the bound forces delivery at age+1 > bound
    delivered, age, _ = p2p._staleness_delivery(cfg, jnp.int32(1), age)
    assert not np.asarray(delivered)[6:].any()
    assert np.asarray(age)[6:].tolist() == [2, 2]
    delivered, age, decay = p2p._staleness_delivery(cfg, jnp.int32(2), age)
    assert np.asarray(delivered)[6:].all()  # forced: age would hit 3 > bound
    assert np.asarray(age)[6:].tolist() == [0, 0]
    np.testing.assert_allclose(np.asarray(decay)[6:], [1.0, 1.0])


# ---------------------------------------------------------------------------
# synchronous structural bypass (the compressor="none" idiom)
# ---------------------------------------------------------------------------


def test_sync_config_takes_synchronous_code_path(monkeypatch):
    """bound=0 + uniform profile is a STRUCTURAL bypass: the async machinery
    is never entered, so fp32 bit-parity with the pre-async runtime holds by
    construction.  A round with every async entry point booby-trapped must
    still run."""
    def boom(*a, **k):  # pragma: no cover - must never run
        raise AssertionError("async machinery entered on the sync path")

    monkeypatch.setattr(p2p, "_snapshot_round", boom)
    monkeypatch.setattr(p2p, "_staleness_delivery", boom)
    monkeypatch.setattr(p2p, "compute_profile", boom)
    monkeypatch.setattr(protocols_lib, "age_decayed_constants", boom)
    cfg = _cfg(steps_profile="uniform", staleness_bound=0)
    state = p2p.init_state(jax.random.PRNGKey(0), _init_fn, cfg)
    assert state.staleness == ()
    fn = p2p.make_round_fn(_mlp_loss, cfg)
    x, y = _round_batches(np.random.default_rng(0), T)
    _, state, losses = fn(state, (x, y))
    assert np.isfinite(np.asarray(losses)).all()
    assert state.staleness == ()


def test_uniform_profile_scan_is_structurally_unmasked():
    """The uniform profile passes ``steps_k=None``: the local-phase scan body
    is the legacy one with NO mask in the graph — identical jaxprs, not just
    identical numbers."""
    cfg_sync = _cfg(steps_profile="uniform", staleness_bound=0)
    state = p2p.init_state(jax.random.PRNGKey(0), _init_fn, cfg_sync)
    x, y = _round_batches(np.random.default_rng(0), T)

    def run(steps_k):
        s, losses = p2p.local_phase(
            state, _mlp_loss, (x, y), cfg_sync, steps_k=steps_k
        )
        return s.params, losses

    unmasked = jax.make_jaxpr(lambda: run(None))()
    full_mask = jax.make_jaxpr(lambda: run(jnp.full((K,), T, jnp.int32)))()
    assert "while" in str(unmasked) or "scan" in str(unmasked)
    assert str(unmasked) != str(full_mask)  # the mask would cost real FLOPs
    # ... and the full-budget mask is numerically the identity
    p_unmasked, l_unmasked = run(None)
    p_masked, l_masked = run(jnp.full((K,), T, jnp.int32))
    _assert_trees_equal(p_unmasked, p_masked, "full-budget mask")
    _assert_trees_equal(l_unmasked, l_masked, "full-budget losses")


# ---------------------------------------------------------------------------
# staleness semantics
# ---------------------------------------------------------------------------


def test_ages_never_exceed_bound():
    cfg = _cfg(num_peers=8, protocol="gossip", schedule="round_robin",
               straggler_period=6, staleness_bound=3)
    state = p2p.init_state(jax.random.PRNGKey(1), _init_fn, cfg)
    fn = p2p.make_round_fn(_mlp_loss, cfg)
    rng = np.random.default_rng(1)
    seen_ages = []
    for _ in range(10):
        x, y = _round_batches(rng, T, k=8)
        _, state, losses = fn(state, (x, y))
        assert np.isfinite(np.asarray(losses)).all()
        ages = np.asarray(state.staleness.age)
        seen_ages.append(ages)
        assert (ages <= cfg.staleness_bound).all(), ages
    # the profile actually produces staleness (ages > 0 occur)
    assert max(a.max() for a in seen_ages) > 0


def test_published_rows_frozen_between_publications():
    """A straggler's published snapshot must not move while undelivered, and
    must equal its live post-local params on publication rounds."""
    cfg = _cfg(num_peers=8, straggler_frac=0.25, straggler_period=4,
               staleness_bound=3)
    state = p2p.init_state(jax.random.PRNGKey(2), _init_fn, cfg)
    fn = p2p.make_round_fn(_mlp_loss, cfg)
    rng = np.random.default_rng(2)
    prev_pub = jax.tree.map(np.asarray, state.staleness.published)
    for r in range(8):
        x, y = _round_batches(rng, T, k=8)
        after_local, state, _ = fn(state, (x, y))
        pub = jax.tree.map(np.asarray, state.staleness.published)
        delivered = np.asarray(state.staleness.age) == 0
        for (path, p_leaf), (_, al_leaf), (_, prev_leaf) in zip(
            jax.tree_util.tree_leaves_with_path(pub),
            jax.tree_util.tree_leaves_with_path(after_local.params),
            jax.tree_util.tree_leaves_with_path(prev_pub),
        ):
            al_leaf = np.asarray(al_leaf)
            for k in range(8):
                want = al_leaf[k] if delivered[k] else prev_leaf[k]
                assert np.array_equal(p_leaf[k], want), (
                    f"round {r} peer {k} {jax.tree_util.keystr(path)}"
                )
        prev_pub = pub


@pytest.mark.parametrize("schedule", ["static", "round_robin"])
def test_push_sum_mass_conserved_under_maximal_staleness(schedule):
    """Column-renormalization makes push-sum's invariant EXACT under async
    delivery: sum(mass) == K on every round, even with every straggler at
    the bound."""
    cfg = _cfg(protocol="push_sum", schedule=schedule, num_peers=8,
               straggler_frac=0.5, straggler_period=8, staleness_bound=7)
    state = p2p.init_state(jax.random.PRNGKey(3), _init_fn, cfg)
    fn = p2p.make_round_fn(_mlp_loss, cfg)
    rng = np.random.default_rng(3)
    for _ in range(8):
        x, y = _round_batches(rng, T, k=8)
        _, state, _ = fn(state, (x, y))
        np.testing.assert_allclose(
            float(jnp.sum(state.protocol.mass)), 8.0, rtol=1e-6
        )
        assert (np.asarray(state.staleness.age) <= 7).all()


def test_capped_peers_freeze_exactly_at_budget():
    """Peer k's local phase with budget s equals a T=s run of the legacy
    scan, bit for bit — the mask freezes params, it does not perturb them."""
    cfg = _cfg(steps_profile="uniform", staleness_bound=0, momentum=0.3)
    state = p2p.init_state(jax.random.PRNGKey(4), _init_fn, cfg)
    x, y = _round_batches(np.random.default_rng(4), T)
    s = 2
    steps_k = jnp.asarray([T, s, T, s], jnp.int32)
    capped, _ = p2p.local_phase(state, _mlp_loss, (x, y), cfg, steps_k=steps_k)
    cfg_short = dataclasses.replace(cfg, local_steps=s)
    short, _ = p2p.local_phase(
        state, _mlp_loss, (x[:s], y[:s]), cfg_short
    )
    for (path, a), (_, b) in zip(
        jax.tree_util.tree_leaves_with_path(capped.params),
        jax.tree_util.tree_leaves_with_path(short.params),
    ):
        a, b = np.asarray(a), np.asarray(b)
        for k in (1, 3):  # the capped peers
            assert np.array_equal(a[k], b[k]), (
                f"peer {k} {jax.tree_util.keystr(path)}"
            )


# ---------------------------------------------------------------------------
# drivers + compilation
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("protocol", ["gossip", "push_sum"])
def test_scan_driver_bit_identical_async(protocol):
    """The fused scan driver and the python round loop agree bit for bit on
    every async state leaf — staleness buffer included."""
    cfg = _cfg(protocol=protocol, schedule="round_robin")
    sizes = np.arange(1, K + 1)
    state0 = p2p.init_state(jax.random.PRNGKey(5), _init_fn, cfg, data_sizes=sizes)
    round_fn = p2p.make_round_fn(_mlp_loss, cfg, data_sizes=sizes)
    drive_fn = p2p.make_scan_driver(_mlp_loss, cfg, data_sizes=sizes, donate=False)

    chunk = 4
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.normal(size=(chunk, T, K, 10, 6)), jnp.float32)
    y = jnp.asarray(rng.normal(size=(chunk, T, K, 10, 4)), jnp.float32)

    s_py = state0
    for r in range(chunk):
        _, s_py, _ = round_fn(s_py, (x[r], y[r]))
    _, s_scan, _ = drive_fn(state0, (x, y))
    _assert_trees_equal(s_py, s_scan, f"{protocol} async scan vs python")


def test_async_one_compile():
    """A time-varying async run traces the loss once: delivery masks are
    traced per-round booleans, never compile-time constants."""
    traces = [0]

    def counting_loss(params, batch):
        traces[0] += 1
        return _mlp_loss(params, batch)

    cfg = _cfg(schedule="round_robin")
    state = p2p.init_state(jax.random.PRNGKey(6), _init_fn, cfg)
    fn = p2p.make_round_fn(counting_loss, cfg)
    rng = np.random.default_rng(6)
    for _ in range(5):
        x, y = _round_batches(rng, T)
        _, state, _ = fn(state, (x, y))
    assert traces[0] <= 2  # value + grad trace of the single compile


# ---------------------------------------------------------------------------
# runtime guards
# ---------------------------------------------------------------------------


def test_hier_runtime_rejects_async():
    cfg = _cfg(num_peers=8)
    with pytest.raises(ValueError, match="asynchronous.*not supported"):
        p2p._make_round_step(
            _mlp_loss, cfg, mesh=None, axis_name="pod", peers_per_device=2
        )


def test_hier_runtime_rejects_steps_profile_alone():
    cfg = _cfg(num_peers=8, staleness_bound=0)
    assert cfg.use_async
    with pytest.raises(ValueError, match="asynchronous.*not supported"):
        p2p._make_round_step(
            _mlp_loss, cfg, mesh=None, axis_name="pod", peers_per_device=2
        )


@pytest.mark.parametrize("argv,msg", [
    (["--experiment", "timevarying_k8", "--schedule", "adaptive",
      "--staleness-bound", "2"], "adaptive"),
    (["--experiment", "timevarying_k8", "--compressor", "topk",
      "--staleness-bound", "2"], "compressor"),
    (["--experiment", "straggler_k8", "--compressor", "topk"], "compressor"),
    (["--experiment", "straggler_k8", "--peer-axis", "pod",
      "--peers-per-device", "2"], "steps-profile"),
    (["--experiment", "straggler_k8", "--schedule", "link_dropout"],
     "static|round_robin"),
])
def test_cli_rejects_bad_async_combinations(argv, msg, capsys):
    from repro.launch import train

    with pytest.raises(SystemExit) as ex:
        train.main(argv)
    assert ex.value.code == 2  # argparse usage error, before any training
    assert msg in capsys.readouterr().err


# ---------------------------------------------------------------------------
# pod (shard_map) runtime — mesh marker: one device per peer
# ---------------------------------------------------------------------------

K8 = 8

needs_mesh = pytest.mark.skipif(
    jax.device_count() < K8,
    reason=f"needs >= {K8} devices "
           f"(XLA_FLAGS=--xla_force_host_platform_device_count={K8})",
)


@needs_mesh
@pytest.mark.mesh
@pytest.mark.parametrize("protocol", ["gossip", "push_sum"])
@pytest.mark.parametrize("schedule", ["static", "round_robin"])
def test_pod_bit_identical_to_vmap_async(protocol, schedule):
    """The async pod round — split mix (this peer's row of the vmap path's
    diag/off-diag decomposition) over the once-per-round gathered snapshot
    stack — is fp32 BIT-identical to the vmap round, leaf for leaf."""
    from repro.launch import mesh as mesh_lib
    from repro.sharding import specs as specs_lib

    cfg = _cfg(protocol=protocol, schedule=schedule, num_peers=K8,
               straggler_frac=0.25, straggler_period=4)
    sizes = np.arange(1, K8 + 1)
    state0 = p2p.init_state(jax.random.PRNGKey(7), _init_fn, cfg, data_sizes=sizes)
    vmap_fn = p2p.make_round_fn(_mlp_loss, cfg, data_sizes=sizes)
    mesh = mesh_lib.make_peer_mesh(K8)
    pod_fn = p2p.make_sharded_round_fn(_mlp_loss, cfg, mesh, data_sizes=sizes)

    s_vmap = state0
    s_pod = specs_lib.shard_peer_tree(state0, mesh)
    rng = np.random.default_rng(7)
    for r in range(6):  # crosses both the schedule and straggler periods
        x, y = _round_batches(rng, T, k=K8)
        al_v, s_vmap, loss_v = vmap_fn(s_vmap, (x, y))
        al_p, s_pod, loss_p = pod_fn(s_pod, (x, y))
        _assert_trees_equal(
            (al_v, s_vmap, loss_v), (al_p, s_pod, loss_p),
            f"{protocol}/{schedule} round {r}",
        )


@needs_mesh
@pytest.mark.mesh
def test_pod_sync_config_takes_synchronous_code_path(monkeypatch):
    """The pod runtime's bound=0 bypass is structural too."""
    from repro.launch import mesh as mesh_lib
    from repro.sharding import specs as specs_lib

    def boom(*a, **k):  # pragma: no cover - must never run
        raise AssertionError("async machinery entered on the sync pod path")

    monkeypatch.setattr(p2p, "_snapshot_round", boom)
    monkeypatch.setattr(p2p, "_staleness_delivery", boom)
    monkeypatch.setattr(protocols_lib, "age_decayed_constants", boom)
    cfg = _cfg(steps_profile="uniform", staleness_bound=0, num_peers=K8)
    state = p2p.init_state(jax.random.PRNGKey(8), _init_fn, cfg)
    assert state.staleness == ()
    mesh = mesh_lib.make_peer_mesh(K8)
    fn = p2p.make_sharded_round_fn(_mlp_loss, cfg, mesh)
    state = specs_lib.shard_peer_tree(state, mesh)
    x, y = _round_batches(np.random.default_rng(8), T, k=K8)
    _, state, losses = fn(state, (x, y))
    assert np.isfinite(np.asarray(losses)).all()
    assert state.staleness == ()
