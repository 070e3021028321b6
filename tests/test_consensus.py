"""Consensus operators: stacked einsum vs sparse gather vs lane gather."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import consensus as cl
from repro.core import graph as gl


def _tree(rng, k):
    return {
        "w": jnp.asarray(rng.normal(size=(k, 5, 3)), jnp.float32),
        "b": jnp.asarray(rng.normal(size=(k, 7)), jnp.float32),
    }


def test_mix_stacked_matches_numpy(rng):
    k = 6
    g = gl.build_graph("ring", k)
    w = gl.mixing_matrix(g, "metropolis")
    tree = _tree(rng, k)
    out = cl.mix_stacked(jnp.asarray(w, jnp.float32), tree)
    for key in tree:
        want = np.einsum("kj,j...->k...", w, np.asarray(tree[key]))
        np.testing.assert_allclose(out[key], want, atol=1e-5)


@pytest.mark.parametrize("topo", ["ring", "star", "complete", "erdos_renyi"])
def test_mix_sparse_equals_dense(rng, topo):
    k = 8
    g = gl.build_graph(topo, k)
    w = gl.mixing_matrix(g, "data_weighted", data_sizes=rng.integers(1, 50, k))
    tree = _tree(rng, k)
    self_w, idx, nbr_w = cl.sparse_mixing(w)
    dense = cl.mix_stacked(jnp.asarray(w, jnp.float32), tree)
    sparse = cl.mix_sparse(jnp.asarray(self_w), jnp.asarray(idx), jnp.asarray(nbr_w), tree)
    for key in tree:
        np.testing.assert_allclose(sparse[key], dense[key], atol=1e-5)


@pytest.mark.mesh
@pytest.mark.skipif(
    jax.device_count() < 4,
    reason="needs >= 4 devices (XLA_FLAGS=--xla_force_host_platform_device_count=8)",
)
@pytest.mark.parametrize("topo", ["ring", "star", "erdos_renyi", "directed_ring"])
def test_gather_peer_rows_under_real_mesh(rng, topo):
    """Lane-gathered neighbor rows match the stacked array on edge positions
    and are zero elsewhere — on a real mesh, for every lane decomposition."""
    from jax.sharding import PartitionSpec as P

    from repro.core.p2p import _shard_map_fn
    from repro.launch.mesh import make_peer_mesh

    k = 4
    g = gl.build_graph(topo, k)
    lanes = gl.edge_color_lanes(g.adjacency)
    x = jnp.asarray(rng.normal(size=(k, 3)), jnp.float32)
    mesh = make_peer_mesh(k)
    shard_map = _shard_map_fn()

    fn = jax.jit(
        shard_map(
            lambda v: cl.gather_peer_leaf(v, "pod", lanes, k)[None],
            mesh=mesh,
            in_specs=(P("pod", None),),
            out_specs=P("pod", None, None),
        )
    )
    full = np.asarray(fn(x))  # (K, K, 3): per-peer reconstruction
    for dst in range(k):
        want = np.zeros((k, 3), np.float32)
        srcs = list(g.in_neighbors(dst)) + [dst]
        want[srcs] = np.asarray(x)[srcs]
        np.testing.assert_array_equal(full[dst], want)


def test_max_norm_sync_picks_largest(rng):
    k = 4
    tree = {"w": jnp.asarray(rng.normal(size=(k, 6)), jnp.float32)}
    tree["w"] = tree["w"].at[2].mul(10.0)  # peer 2 has the largest norm
    out = cl.max_norm_sync(tree)
    for i in range(k):
        np.testing.assert_allclose(out["w"][i], tree["w"][2])


def test_consensus_error_and_drift(rng):
    k = 3
    same = {"w": jnp.ones((k, 4), jnp.float32)}
    assert float(cl.consensus_error(same)) < 1e-6
    assert float(cl.pairwise_drift(same)) < 1e-3
    tree = _tree(rng, k)
    assert float(cl.consensus_error(tree)) > 0.1
    # mixing with a complete graph reduces drift
    g = gl.build_graph("complete", k)
    w = jnp.asarray(gl.mixing_matrix(g, "uniform_neighbor"), jnp.float32)
    mixed = cl.mix_stacked(w, tree)
    assert float(cl.pairwise_drift(mixed)) < float(cl.pairwise_drift(tree))


def test_repeated_mixing_converges_to_average(rng):
    k = 8
    g = gl.build_graph("ring", k)
    w = jnp.asarray(gl.mixing_matrix(g, "metropolis"), jnp.float32)
    tree = _tree(rng, k)
    avg = {key: np.asarray(tree[key]).mean(0) for key in tree}
    x = tree
    for _ in range(500):
        x = cl.mix_stacked(w, x)
    for key in tree:
        np.testing.assert_allclose(
            np.asarray(x[key]), np.broadcast_to(avg[key], x[key].shape), atol=1e-3
        )
