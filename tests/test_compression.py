"""Compressed gossip subsystem (``repro.compression`` + the runtimes).

Contract under test (the acceptance criteria of the compression PR):

* **Registry + config** — the three built-in compressors resolve by name,
  unknown names / out-of-range ``topk_frac`` fail loudly at config time.
* **Compressor semantics** — top-k keeps exactly ``keep(n)`` largest-|.|
  coordinates bit for bit (frac=1.0 is lossless), qint8's per-coordinate
  error is bounded by ``scale / 2``, zero inputs are safe.
* **Error feedback** — estimate tracking converges the public estimate onto
  a static target; the warm start makes the first payload exactly zero
  drift.
* **Runtimes** — ``compressor="none"`` takes the EXACT uncompressed code
  path (structural bypass, not numerical luck); compressed rounds stay
  finite and contract consensus error across protocol x schedule (adaptive
  included); push-sum mass conservation is exact under compression; the
  scan driver is bit-identical to the python loop and compiles once.
* **Guards** — the hierarchical (peers_per_device > 1) runtime and the CLI
  reject compressed / adaptive combinations with actionable errors.
* **Kernel** — the fused dequantize-and-mix Pallas kernel is allclose to
  its dense oracle, honors the no-neighbor guard, and the schedule entry
  compiles once.

The vmap-runtime cases run everywhere (tier-1); the pod-vs-vmap compressed
parity needs one device per peer and carries the ``mesh`` marker.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import compression as compression_lib
from repro.core import consensus as cl
from repro.core import p2p
from repro.kernels.consensus_mix import dequant
from repro.kernels.consensus_mix import ops as cm_ops
from repro.kernels.consensus_mix import ref as cm_ref

K = 4
T = 3


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


def _cfg(compressor="none", protocol="gossip", schedule="static",
         num_peers=K, topk_frac=0.25):
    extra = {}
    if schedule == "round_robin":
        extra["round_robin_topologies"] = ("ring", "star")
    return p2p.P2PConfig(
        algorithm="p2pl_affinity", num_peers=num_peers, local_steps=T,
        consensus_steps=2, lr=0.1, momentum=0.3, eta_d=0.5, eta_b=0.1,
        topology="ring", protocol=protocol, schedule=schedule,
        schedule_rounds=2, compressor=compressor, topk_frac=topk_frac,
        **extra,
    )


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
# registry + config validation
# ---------------------------------------------------------------------------


def test_registry_has_builtins():
    assert set(compression_lib.compressor_names()) >= {"none", "topk", "qint8"}


def test_get_unknown_compressor_raises():
    with pytest.raises(ValueError, match="unknown compressor"):
        compression_lib.get_compressor("gzip")


def test_register_duplicate_raises():
    with pytest.raises(ValueError, match="already registered"):
        compression_lib.register_compressor(compression_lib.TopKCompressor)


@pytest.mark.parametrize("frac", [0.0, -0.1, 1.5])
def test_topk_frac_out_of_range(frac):
    with pytest.raises(ValueError, match="frac"):
        compression_lib.TopKCompressor(frac)
    with pytest.raises(ValueError, match="topk_frac"):
        _cfg(compressor="topk", topk_frac=frac)


def test_config_rejects_unknown_compressor():
    with pytest.raises(ValueError, match="compressor"):
        _cfg(compressor="gzip")


def test_from_config_resolves_frac():
    comp = compression_lib.from_config(_cfg(compressor="topk", topk_frac=0.5))
    assert isinstance(comp, compression_lib.TopKCompressor)
    assert comp.frac == 0.5
    assert not comp.identity
    assert compression_lib.from_config(_cfg()).identity


# ---------------------------------------------------------------------------
# compressor semantics
# ---------------------------------------------------------------------------


def test_topk_keeps_exact_count_and_largest(rng):
    comp = compression_lib.TopKCompressor(0.25)
    leaf = jnp.asarray(rng.normal(size=(2, 16)), jnp.float32)
    payload = comp.compress(leaf)
    assert payload.values.shape == (2, 4)  # keep(16) = 4
    flat = np.asarray(leaf)
    for row in range(2):
        kept = set(np.asarray(payload.indices)[row].tolist())
        order = np.argsort(-np.abs(flat[row]))
        assert kept == set(order[:4].tolist())
        # kept coordinates round-trip bit for bit
        dec = np.asarray(comp.decompress(payload, leaf))
        for i in kept:
            assert dec[row, i] == flat[row, i]


def test_topk_frac_one_is_lossless(rng):
    comp = compression_lib.TopKCompressor(1.0)
    leaf = jnp.asarray(rng.normal(size=(3, 4, 5)), jnp.float32)
    out = comp.decompress(comp.compress(leaf), leaf)
    assert np.array_equal(np.asarray(out), np.asarray(leaf))


def test_topk_keep_floor_is_one():
    assert compression_lib.TopKCompressor(0.01).keep(3) == 1


def test_qint8_error_bounded_by_half_scale(rng):
    comp = compression_lib.QInt8Compressor()
    leaf = jnp.asarray(rng.normal(size=(3, 64)) * 10.0, jnp.float32)
    payload = comp.compress(leaf)
    out = np.asarray(comp.decompress(payload, leaf)).reshape(3, -1)
    err = np.abs(out - np.asarray(leaf).reshape(3, -1))
    bound = np.asarray(payload.scale) / 2.0 + 1e-7
    assert (err <= bound).all()


def test_qint8_zero_leaf_safe():
    comp = compression_lib.QInt8Compressor()
    leaf = jnp.zeros((2, 8), jnp.float32)
    payload = comp.compress(leaf)
    assert np.asarray(payload.scale).max() == 0.0
    out = np.asarray(comp.decompress(payload, leaf))
    assert np.array_equal(out, np.zeros_like(out))


def test_estimate_warm_starts_at_params(key):
    params = jax.vmap(_init_fn)(jax.random.split(key, K))
    est = compression_lib.TopKCompressor(0.25).init_estimate(params)
    _assert_trees_equal(params, est, "warm-start estimate")
    assert compression_lib.NoneCompressor().init_estimate(params) == ()


# ---------------------------------------------------------------------------
# error feedback
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("name", ["topk", "qint8"])
def test_ef_estimate_converges_on_static_target(name, rng):
    """Iterating C(x - x̂) shrinks ||x - x̂|| toward 0: the dropped signal
    re-enters every step (EF conservation)."""
    comp = compression_lib.get_compressor(name, topk_frac=0.2)
    x = jnp.asarray(rng.normal(size=(2, 40)), jnp.float32)
    est = jnp.zeros_like(x)
    errs = []
    for _ in range(60):
        _, est = compression_lib.ef_compress_leaf(comp, x, est)
        errs.append(float(jnp.max(jnp.abs(x - est))))
    assert errs[-1] < 1e-3 * errs[0]
    assert errs[-1] <= errs[0]


def test_ef_first_payload_is_zero_after_warm_start(key):
    """Warm start => the first difference x - x̂ is exactly zero; top-k ships
    zero values and the estimate does not move."""
    params = jax.vmap(_init_fn)(jax.random.split(key, K))
    comp = compression_lib.TopKCompressor(0.1)
    est = comp.init_estimate(params)
    for x, e in zip(jax.tree.leaves(params), jax.tree.leaves(est)):
        payload, e2 = compression_lib.ef_compress_leaf(comp, x, e)
        assert np.asarray(payload.values).max() == 0.0
        _assert_trees_equal(e, e2, "estimate after zero payload")


# ---------------------------------------------------------------------------
# vmap runtime
# ---------------------------------------------------------------------------


def test_none_takes_uncompressed_code_path(monkeypatch):
    """compressor='none' is a STRUCTURAL bypass: the runtimes never touch the
    compression machinery, so fp32 bit-parity with the pre-compression
    runtime holds by construction.  A round with every compressor entry point
    booby-trapped must still run."""
    def boom(*a, **k):  # pragma: no cover - must never run
        raise AssertionError("compression machinery entered on the none path")

    monkeypatch.setattr(compression_lib.NoneCompressor, "compress", boom)
    monkeypatch.setattr(compression_lib.NoneCompressor, "decompress", boom)
    monkeypatch.setattr(p2p, "_choco_advance", boom)
    cfg = _cfg()
    state = p2p.init_state(jax.random.PRNGKey(0), _init_fn, cfg)
    assert state.compression == ()
    fn = p2p.make_round_fn(_mlp_loss, cfg)
    x, y = _round_batches(np.random.default_rng(0), T)
    _, state, losses = fn(state, (x, y))
    assert np.isfinite(np.asarray(losses)).all()


@pytest.mark.parametrize("protocol", ["gossip", "push_sum"])
@pytest.mark.parametrize("compressor", ["topk", "qint8"])
@pytest.mark.parametrize("schedule", ["static", "round_robin", "adaptive"])
def test_compressed_rounds_finite_and_contracting(protocol, compressor, schedule):
    """Compressed rounds run on every protocol x schedule (adaptive included),
    stay finite, and actually advance the carried estimate stack."""
    if schedule == "adaptive":
        cfg = p2p.P2PConfig(
            algorithm="p2pl_affinity", num_peers=K, local_steps=T,
            consensus_steps=2, lr=0.1, momentum=0.3, eta_d=0.5, eta_b=0.1,
            schedule="adaptive", protocol=protocol,
            compressor=compressor, topk_frac=0.25,
        )
    else:
        cfg = _cfg(compressor=compressor, protocol=protocol, schedule=schedule)
    sizes = np.arange(1, K + 1)
    state = p2p.init_state(jax.random.PRNGKey(0), _init_fn, cfg, data_sizes=sizes)
    est0 = jax.tree.map(np.asarray, state.compression)
    fn = p2p.make_round_fn(_mlp_loss, cfg, data_sizes=sizes)
    rng = np.random.default_rng(1)
    for _ in range(3):
        x, y = _round_batches(rng, T)
        _, state, losses = fn(state, (x, y))
        assert np.isfinite(np.asarray(losses)).all()
    for leaf in jax.tree.leaves(state.params):
        assert np.isfinite(np.asarray(leaf)).all()
    moved = [
        not np.array_equal(a, np.asarray(b))
        for a, b in zip(jax.tree.leaves(est0), jax.tree.leaves(state.compression))
    ]
    assert any(moved), "estimate stack never advanced"


def test_compressed_consensus_error_contracts():
    """Gossiping with a compressed wire still pulls non-IID peers together:
    consensus error after compressed-only mixing (lr=0) shrinks."""
    cfg = dataclasses.replace(
        _cfg(compressor="topk", topk_frac=0.5), lr=0.0, momentum=0.0,
        consensus_steps=4, eta_d=0.0, eta_b=0.0, algorithm="p2pl",
    )
    state = p2p.init_state(jax.random.PRNGKey(2), _init_fn, cfg)
    # common-seed init starts at consensus: spread the peers apart first,
    # warm-starting the estimate stack on the spread values
    params = jax.vmap(_init_fn)(jax.random.split(jax.random.PRNGKey(22), K))
    comp = compression_lib.from_config(cfg)
    state = state._replace(params=params, compression=comp.init_estimate(params))
    err0 = float(cl.consensus_error(state.params))
    assert err0 > 0.0
    fn = p2p.make_round_fn(_mlp_loss, cfg)
    rng = np.random.default_rng(2)
    for _ in range(4):
        x, y = _round_batches(rng, T)
        _, state, _ = fn(state, (x, y))
    assert float(cl.consensus_error(state.params)) < 0.5 * err0


def test_push_sum_mass_conserved_under_compression():
    """The mass lane rides uncompressed: sum(y) == K exactly, any compressor."""
    for compressor in ("topk", "qint8"):
        cfg = _cfg(compressor=compressor, protocol="push_sum",
                   schedule="round_robin")
        state = p2p.init_state(jax.random.PRNGKey(3), _init_fn, cfg)
        fn = p2p.make_round_fn(_mlp_loss, cfg)
        rng = np.random.default_rng(3)
        for _ in range(3):
            x, y = _round_batches(rng, T)
            _, state, _ = fn(state, (x, y))
        np.testing.assert_allclose(
            float(jnp.sum(state.protocol.mass)), float(K), rtol=1e-6
        )


@pytest.mark.parametrize("compressor", ["topk", "qint8"])
def test_scan_driver_bit_identical_compressed(compressor):
    """The fused scan driver and the python round loop agree bit for bit on
    every state leaf — estimate stack included — under compression."""
    cfg = _cfg(compressor=compressor, protocol="gossip", schedule="round_robin")
    sizes = np.arange(1, K + 1)
    state0 = p2p.init_state(jax.random.PRNGKey(4), _init_fn, cfg, data_sizes=sizes)
    round_fn = p2p.make_round_fn(_mlp_loss, cfg, data_sizes=sizes)
    drive_fn = p2p.make_scan_driver(_mlp_loss, cfg, data_sizes=sizes, donate=False)

    chunk = 3
    rng = np.random.default_rng(4)
    x = jnp.asarray(rng.normal(size=(chunk, T, K, 10, 6)), jnp.float32)
    y = jnp.asarray(rng.normal(size=(chunk, T, K, 10, 4)), jnp.float32)

    s_py = state0
    for r in range(chunk):
        _, s_py, _ = round_fn(s_py, (x[r], y[r]))
    _, s_scan, _ = drive_fn(state0, (x, y))
    _assert_trees_equal(s_py, s_scan, f"{compressor} scan vs python")


def test_compressed_one_compile():
    """A time-varying compressed run traces the loss once: compression keeps
    the one-compile contract of the round closure."""
    traces = [0]

    def counting_loss(params, batch):
        traces[0] += 1
        return _mlp_loss(params, batch)

    cfg = _cfg(compressor="topk", schedule="round_robin")
    state = p2p.init_state(jax.random.PRNGKey(5), _init_fn, cfg)
    fn = p2p.make_round_fn(counting_loss, cfg)
    rng = np.random.default_rng(5)
    for _ in range(4):
        x, y = _round_batches(rng, T)
        _, state, _ = fn(state, (x, y))
    assert traces[0] <= 2  # value + grad trace of the single compile


# ---------------------------------------------------------------------------
# guards: hierarchical runtime + launcher (satellite: adaptive x ppd > 1)
# ---------------------------------------------------------------------------


def test_hier_runtime_rejects_compression():
    cfg = _cfg(compressor="topk", num_peers=8)
    with pytest.raises(ValueError, match="compressor.*not supported"):
        p2p._make_round_step(
            _mlp_loss, cfg, mesh=None, axis_name="pod", peers_per_device=2
        )


def test_hier_runtime_rejects_adaptive():
    cfg = p2p.P2PConfig(
        algorithm="p2pl_affinity", num_peers=8, local_steps=T,
        schedule="adaptive",
    )
    with pytest.raises(ValueError, match="adaptive.*not supported"):
        p2p._make_round_step(
            _mlp_loss, cfg, mesh=None, axis_name="pod", peers_per_device=2
        )


def test_launcher_rejects_adaptive_with_peers_per_device():
    from repro.configs.p2pl_mnist import timevarying_k8
    from repro.launch import train

    exp = timevarying_k8(schedule="adaptive", algorithm="p2pl_affinity",
                         local_steps=10)
    with pytest.raises(ValueError, match="adaptive.*peers_per_device"):
        train.run_paper_experiment(
            exp, rounds=1, peer_axis="pod", peers_per_device=2
        )


def test_launcher_rejects_compressor_with_peers_per_device():
    from repro.configs.p2pl_mnist import timevarying_k8
    from repro.launch import train

    exp = timevarying_k8(
        schedule="round_robin", algorithm="p2pl_affinity", local_steps=10,
        compressor="qint8",
    )
    with pytest.raises(ValueError, match="compressor.*peers_per_device"):
        train.run_paper_experiment(
            exp, rounds=1, peer_axis="pod", peers_per_device=2
        )


@pytest.mark.parametrize("argv,msg", [
    (["--experiment", "timevarying_k8", "--schedule", "adaptive",
      "--peer-axis", "pod", "--peers-per-device", "2"], "adaptive"),
    (["--experiment", "timevarying_k8", "--compressor", "topk",
      "--peer-axis", "pod", "--peers-per-device", "2"], "compressor"),
    (["--experiment", "timevarying_k8", "--topk-frac", "1.5"], "topk-frac"),
    (["--experiment", "timevarying_k8", "--topk-frac", "0"], "topk-frac"),
])
def test_cli_rejects_bad_combinations(argv, msg, capsys):
    from repro.launch import train

    with pytest.raises(SystemExit) as ex:
        train.main(argv)
    assert ex.value.code == 2  # argparse usage error, before any training
    assert msg in capsys.readouterr().err


# ---------------------------------------------------------------------------
# fused dequantize-and-mix kernel
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n", [64, 257, 1000])
@pytest.mark.parametrize("d", [1, 3, 5])
def test_dequant_mix_matches_oracle(n, d, rng):
    k = 3  # peers ride the kernel's grid axis; the oracle runs per peer
    x = jnp.asarray(rng.normal(size=(k, n)), jnp.float32)
    self_est = jnp.asarray(rng.normal(size=(k, n)), jnp.float32)
    nbrs_est = jnp.asarray(rng.normal(size=(k, d, n)), jnp.float32)
    nbrs_q = jnp.asarray(rng.integers(-127, 128, size=(k, d, n)), jnp.int8)
    scale = jnp.asarray(rng.uniform(0.0, 0.1, size=(k, d)), jnp.float32)
    w_nbr = jnp.asarray(rng.dirichlet(np.ones(d + 1), size=k)[:, :d], jnp.float32)
    w_self = 1.0 - w_nbr.sum(axis=1)
    beta = jnp.asarray(rng.dirichlet(np.ones(d), size=k), jnp.float32)
    got_m, got_d = dequant.dequant_mix_rows(
        x, self_est, nbrs_est, nbrs_q, scale, w_self, w_nbr, beta, 10
    )
    want_m, want_d = jax.vmap(cm_ref.dequant_mix_ref, in_axes=(0,) * 8 + (None,))(
        x, self_est, nbrs_est, nbrs_q, scale, w_self, w_nbr, beta, 10
    )
    np.testing.assert_allclose(np.asarray(got_m), np.asarray(want_m),
                               atol=5e-5, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(got_d), np.asarray(want_d),
                               atol=5e-5, rtol=1e-4)


def test_dequant_mix_zero_beta_keeps_zero_d(rng):
    """The no-neighbor guard reads the RAW beta sum: d is exactly zero even
    when payload scales are nonzero."""
    k, n, d = 2, 256, 3
    x = jnp.asarray(rng.normal(size=(k, n)), jnp.float32)
    self_est = jnp.asarray(rng.normal(size=(k, n)), jnp.float32)
    nbrs_est = jnp.asarray(rng.normal(size=(k, d, n)), jnp.float32)
    nbrs_q = jnp.asarray(rng.integers(-127, 128, size=(k, d, n)), jnp.int8)
    scale = jnp.full((k, d), 0.05, jnp.float32)
    _, got_d = dequant.dequant_mix_rows(
        x, self_est, nbrs_est, nbrs_q, scale, jnp.ones((k,), jnp.float32),
        jnp.zeros((k, d), jnp.float32), jnp.zeros((k, d), jnp.float32), 10
    )
    assert np.array_equal(np.asarray(got_d), np.zeros((k, n), np.float32))


def test_dequant_mix_zero_scale_ignores_payload(rng):
    """scale = 0 (an all-zero difference) folds the payload away entirely:
    the mix runs on the bare estimates."""
    k, n, d = 2, 128, 2
    x = jnp.asarray(rng.normal(size=(k, n)), jnp.float32)
    self_est = jnp.asarray(rng.normal(size=(k, n)), jnp.float32)
    nbrs_est = jnp.asarray(rng.normal(size=(k, d, n)), jnp.float32)
    nbrs_q = jnp.asarray(rng.integers(-127, 128, size=(k, d, n)), jnp.int8)
    w_self = jnp.full((k,), 0.4, jnp.float32)
    w_nbr = jnp.full((k, d), 0.3, jnp.float32)
    beta = jnp.full((k, d), 0.5, jnp.float32)
    zero_scale = jnp.zeros((k, d), jnp.float32)
    got_m, got_d = dequant.dequant_mix_rows(
        x, self_est, nbrs_est, nbrs_q, zero_scale, w_self, w_nbr, beta, 10
    )
    want_m, want_d = jax.vmap(cm_ref.dequant_mix_ref, in_axes=(0,) * 8 + (None,))(
        x, self_est, nbrs_est, jnp.zeros_like(nbrs_q), zero_scale, w_self, w_nbr, beta, 10
    )
    np.testing.assert_allclose(np.asarray(got_m), np.asarray(want_m),
                               atol=5e-5, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(got_d), np.asarray(want_d),
                               atol=5e-5, rtol=1e-4)


def _sparse_round(k):
    from repro.core import protocols as protocols_lib

    cfg = p2p.P2PConfig(num_peers=k, topology="ring", schedule="round_robin",
                        round_robin_topologies=("ring", "star"),
                        schedule_rounds=2, protocol="gossip")
    consts = protocols_lib.get_protocol("gossip").constants(
        p2p.build_schedule(cfg), cfg.mixing,
        data_sizes=np.arange(1, k + 1),
    )
    return cm_ops.sparse_from_schedule(np.asarray(consts.w), np.asarray(consts.beta))


def test_dequant_stacked_matches_per_peer_oracle(rng):
    k = 8
    params = jax.vmap(_init_fn)(jax.random.split(jax.random.PRNGKey(6), k))
    flat, _ = cm_ops.flatten_pytree(params)
    est = jnp.asarray(flat + 0.01 * rng.normal(size=flat.shape), jnp.float32)
    q, scale = dequant.quantize_int8(flat - est)
    self_w_s, nbr_idx_s, nbr_w_s, beta_s = _sparse_round(k)
    r = 0
    mixed, d = dequant.dequant_consensus_mix_stacked(
        params, est, q, scale,
        self_w_s[r], nbr_idx_s[r], nbr_w_s[r], beta_s[r], T,
    )
    mixed_f, _ = cm_ops.flatten_pytree(mixed)
    d_f, _ = cm_ops.flatten_pytree(d)
    for peer in range(k):
        idx = np.asarray(nbr_idx_s[r][peer])
        want_m, want_d = cm_ref.dequant_mix_ref(
            flat[peer], est[peer], est[idx], q[idx], scale[idx],
            self_w_s[r][peer], nbr_w_s[r][peer], beta_s[r][peer], T,
        )
        np.testing.assert_allclose(np.asarray(mixed_f[peer]),
                                   np.asarray(want_m), atol=5e-5, rtol=1e-4)
        np.testing.assert_allclose(np.asarray(d_f[peer]),
                                   np.asarray(want_d), atol=5e-5, rtol=1e-4)


def test_dequant_schedule_compiles_once(rng):
    k = 8
    params = jax.vmap(_init_fn)(jax.random.split(jax.random.PRNGKey(7), k))
    flat, _ = cm_ops.flatten_pytree(params)
    est = jnp.asarray(flat + 0.01 * rng.normal(size=flat.shape), jnp.float32)
    q, scale = dequant.quantize_int8(flat - est)
    operands = _sparse_round(k)
    before = dequant.dequant_consensus_mix_schedule._cache_size()
    outs = []
    for r in range(4):
        m, _ = dequant.dequant_consensus_mix_schedule(
            params, est, q, scale, *operands, jnp.asarray(r), T,
        )
        outs.append(m)
    after = dequant.dequant_consensus_mix_schedule._cache_size()
    assert after - before == 1  # round selected inside the one trace
    # rounds actually differ (ring vs star rows)
    a, _ = cm_ops.flatten_pytree(outs[0])
    b, _ = cm_ops.flatten_pytree(outs[1])
    assert not np.array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# pod runtime parity (mesh marker: one device per peer)
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
@pytest.mark.parametrize("compressor", ["topk", "qint8"])
def test_pod_matches_vmap_compressed(protocol, compressor):
    """Compressed pod runtime (payloads on the wire, replicated estimate
    stack) is allclose to the vmap runtime on every leaf, every round."""
    from repro.launch import mesh as mesh_lib
    from repro.sharding import specs as specs_lib

    cfg = _cfg(compressor=compressor, protocol=protocol,
               schedule="round_robin", num_peers=K8)
    sizes = np.arange(1, K8 + 1)
    state0 = p2p.init_state(jax.random.PRNGKey(8), _init_fn, cfg, data_sizes=sizes)
    vmap_fn = p2p.make_round_fn(_mlp_loss, cfg, data_sizes=sizes)
    mesh = mesh_lib.make_peer_mesh(K8)
    pod_fn = p2p.make_sharded_round_fn(_mlp_loss, cfg, mesh, data_sizes=sizes)

    s_vmap = state0
    s_pod = specs_lib.shard_peer_tree(state0, mesh)
    rng = np.random.default_rng(8)
    for rnd in range(3):
        x, y = _round_batches(rng, T, k=K8)
        _, s_vmap, loss_v = vmap_fn(s_vmap, (x, y))
        _, s_pod, loss_p = pod_fn(s_pod, (x, y))
        np.testing.assert_allclose(np.asarray(loss_v), np.asarray(loss_p),
                                   atol=1e-4, rtol=1e-4)
    # tolerance note: the two runtimes mix with different reduction orders
    # (stacked diag/off-diag einsum vs per-row arithmetic); a one-ULP
    # difference in x - x̂ can flip a qint8 rounding / top-k selection
    # boundary, bounded by the per-step quantization error (~scale / 2),
    # which error feedback re-injects the following step
    for (path, a), (_, b) in zip(
        jax.tree_util.tree_leaves_with_path(s_vmap),
        jax.tree_util.tree_leaves_with_path(s_pod),
    ):
        np.testing.assert_allclose(
            np.asarray(a, np.float64), np.asarray(b, np.float64),
            atol=5e-3, rtol=1e-3,
            err_msg=f"{protocol}/{compressor} leaf {jax.tree_util.keystr(path)}",
        )
