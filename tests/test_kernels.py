"""Per-kernel shape/dtype sweeps: pallas (interpret=True) vs pure-jnp oracle."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.consensus_mix import ops as cm_ops
from repro.kernels.consensus_mix import ref as cm_ref
from repro.kernels.flash_attention.flash_attention import flash_attention
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.mamba2.ops import ssd
from repro.kernels.mamba2.ref import ssd_ref
from repro.kernels.rwkv6.ops import wkv6
from repro.kernels.rwkv6.ref import wkv6_ref

TOL = {jnp.float32: dict(atol=5e-5, rtol=1e-4), jnp.bfloat16: dict(atol=5e-2, rtol=5e-2)}


# ---------------------------------------------------------------------------
# consensus_mix
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n", [64, 257, 1000, 4096])
@pytest.mark.parametrize("d", [1, 3, 5])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_consensus_mix_sweep(n, d, dtype, rng):
    k = 3  # peers ride the kernel's grid axis; the oracle runs per peer
    x = jnp.asarray(rng.normal(size=(k, n)), dtype)
    nbrs = jnp.asarray(rng.normal(size=(k, d, n)), dtype)
    w_nbr = jnp.asarray(rng.dirichlet(np.ones(d + 1), size=k)[:, :d], jnp.float32)
    w_self = 1.0 - w_nbr.sum(axis=1)
    beta = jnp.asarray(rng.dirichlet(np.ones(d), size=k), jnp.float32)
    got_m, got_d = cm_ops.consensus_mix_rows(x, nbrs, w_self, w_nbr, beta, 10)
    want_m, want_d = jax.vmap(cm_ref.consensus_mix_ref, in_axes=(0, 0, 0, 0, 0, None))(
        x, nbrs, w_self, w_nbr, beta, 10
    )
    np.testing.assert_allclose(
        np.asarray(got_m, np.float32), np.asarray(want_m, np.float32), **TOL[dtype]
    )
    np.testing.assert_allclose(
        np.asarray(got_d, np.float32), np.asarray(want_d, np.float32), **TOL[dtype]
    )


def test_consensus_mix_preserves_constant(rng):
    """Row-stochastic mixing of identical params is the identity."""
    k, n = 2, 512
    x = jnp.ones((k, n), jnp.float32) * 3.25
    nbrs = jnp.broadcast_to(x[:, None], (k, 4, n))
    w_nbr = jnp.full((k, 4), 0.2, jnp.float32)
    got_m, got_d = cm_ops.consensus_mix_rows(x, nbrs, jnp.full((k,), 0.2), w_nbr,
                                             jnp.full((k, 4), 0.25, jnp.float32), 5)
    np.testing.assert_allclose(np.asarray(got_m), 3.25, rtol=1e-6)
    np.testing.assert_allclose(np.asarray(got_d), 0.0, atol=1e-7)


# ---------------------------------------------------------------------------
# consensus_mix segment (edge-list gather inside the kernel)
# ---------------------------------------------------------------------------


def _sparse_round(k, rng, schedule="link_dropout", stochasticity="row"):
    from repro.core import graph as gl
    from repro.core import p2p

    cfg = p2p.P2PConfig(num_peers=k, topology="ring", schedule=schedule,
                        schedule_rounds=3, protocol="gossip")
    sp = gl.SparseSchedule.from_schedule(
        p2p.build_schedule(cfg), "data_weighted",
        data_sizes=rng.integers(5, 30, size=k),
        consensus_step_size=0.8, stochasticity=stochasticity,
    )
    return sp, sp.to_dense()


@pytest.mark.parametrize("k,n", [(8, 64), (16, 300), (8, 1000)])
def test_segment_mix_matches_dense_ref(k, n, rng):
    from repro.kernels.consensus_mix import segment as cm_seg

    sp, (w_np, b_np) = _sparse_round(k, rng)
    flat = jnp.asarray(rng.normal(size=(k, n)), jnp.float32)
    stacked = {"w": flat}
    for r in range(sp.period):
        got_m, got_d = cm_seg.segment_mix_stacked(
            stacked, jnp.asarray(sp.self_w[r], jnp.float32),
            jnp.asarray(sp.nbr_idx[r]), jnp.asarray(sp.nbr_w[r], jnp.float32),
            jnp.asarray(sp.beta[r], jnp.float32), 5,
        )
        want_m, want_d = cm_ref.segment_mix_ref(
            flat, jnp.asarray(w_np[r], jnp.float32),
            jnp.asarray(b_np[r], jnp.float32), 5,
        )
        np.testing.assert_allclose(
            np.asarray(got_m["w"]), np.asarray(want_m), atol=5e-5, rtol=1e-4
        )
        np.testing.assert_allclose(
            np.asarray(got_d["w"]), np.asarray(want_d), atol=5e-5, rtol=1e-4
        )


def test_segment_mix_push_sum_matches_dense_ref(rng):
    from repro.kernels.consensus_mix import segment as cm_seg

    k, n = 16, 200
    sp, (a_np, b_np) = _sparse_round(k, rng, stochasticity="column")
    flat = jnp.asarray(rng.normal(size=(k, n)), jnp.float32)
    mass = jnp.asarray(rng.uniform(0.5, 2.0, size=k), jnp.float32)
    for r in range(sp.period):
        got_m, got_d, got_y = cm_seg.segment_mix_push_sum_stacked(
            {"w": flat}, mass, jnp.asarray(sp.self_w[r], jnp.float32),
            jnp.asarray(sp.nbr_idx[r]), jnp.asarray(sp.nbr_w[r], jnp.float32),
            jnp.asarray(sp.beta[r], jnp.float32), 5,
        )
        want_m, want_d, want_y = cm_ref.segment_mix_push_sum_ref(
            flat, mass, jnp.asarray(a_np[r], jnp.float32),
            jnp.asarray(b_np[r], jnp.float32), 5,
        )
        np.testing.assert_allclose(
            np.asarray(got_m["w"]), np.asarray(want_m), atol=5e-5, rtol=1e-4
        )
        np.testing.assert_allclose(
            np.asarray(got_d["w"]), np.asarray(want_d), atol=5e-5, rtol=1e-4
        )
        np.testing.assert_allclose(
            np.asarray(got_y), np.asarray(want_y), atol=5e-6, rtol=1e-5
        )


def test_segment_mix_schedule_selects_round(rng):
    from repro.kernels.consensus_mix import segment as cm_seg

    k, n = 8, 128
    sp, (w_np, b_np) = _sparse_round(k, rng)
    flat = jnp.asarray(rng.normal(size=(k, n)), jnp.float32)
    stacks = (
        jnp.asarray(sp.self_w, jnp.float32), jnp.asarray(sp.nbr_idx),
        jnp.asarray(sp.nbr_w, jnp.float32), jnp.asarray(sp.beta, jnp.float32),
    )
    got_m, _ = cm_seg.segment_mix_schedule({"w": flat}, jnp.int32(4), *stacks, 5)
    r = 4 % sp.period
    want_m, _ = cm_ref.segment_mix_ref(
        flat, jnp.asarray(w_np[r], jnp.float32),
        jnp.asarray(b_np[r], jnp.float32), 5,
    )
    np.testing.assert_allclose(
        np.asarray(got_m["w"]), np.asarray(want_m), atol=5e-5, rtol=1e-4
    )


def test_segment_mix_isolated_peer_keeps_zero_d(rng):
    """A peer with an all-zero beta row (degree-0 this round) keeps d = 0."""
    from repro.kernels.consensus_mix import segment as cm_seg

    k, n = 4, 128
    flat = jnp.asarray(rng.normal(size=(k, n)), jnp.float32)
    # peer 0 isolated: its slots point at itself with zero weights
    nbr_idx = jnp.asarray([[0, 0], [0, 2], [1, 3], [2, 2]], jnp.int32)
    nbr_w = jnp.asarray([[0, 0], [0.3, 0.3], [0.3, 0.3], [0.3, 0]], jnp.float32)
    beta = jnp.asarray([[0, 0], [0.5, 0.5], [0.5, 0.5], [1.0, 0]], jnp.float32)
    self_w = jnp.asarray([1.0, 0.4, 0.4, 0.7], jnp.float32)
    _, d = cm_seg.segment_mix_stacked({"w": flat}, self_w, nbr_idx, nbr_w, beta, 5)
    np.testing.assert_array_equal(np.asarray(d["w"][0]), 0.0)
    assert np.abs(np.asarray(d["w"][1:])).max() > 0


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("s,d,bq,bk", [(128, 32, 32, 32), (256, 64, 64, 128), (64, 128, 64, 16)])
@pytest.mark.parametrize("causal,window", [(True, None), (True, 64), (False, None)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_sweep(s, d, bq, bk, causal, window, dtype, rng):
    q = jnp.asarray(rng.normal(size=(1, 2, s, d)), dtype)
    k = jnp.asarray(rng.normal(size=(1, 2, s, d)), dtype)
    v = jnp.asarray(rng.normal(size=(1, 2, s, d)), dtype)
    got = flash_attention(q, k, v, causal=causal, window=window, block_q=bq, block_k=bk)
    want = attention_ref(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), **TOL[dtype]
    )


def test_flash_attention_matches_model_gqa(rng):
    """ops.gqa_flash_attention == the model's _attend for GQA shapes."""
    from repro.kernels.flash_attention.ops import gqa_flash_attention

    b, s, h, kh, d = 2, 64, 4, 2, 16
    q = jnp.asarray(rng.normal(size=(b, s, h, d)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(b, s, kh, d)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, s, kh, d)), jnp.float32)
    got = gqa_flash_attention(q, k, v, causal=True, block_q=32, block_k=32)
    want = gqa_flash_attention(q, k, v, causal=True, impl="ref")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=5e-5)


# ---------------------------------------------------------------------------
# rwkv6 / mamba2
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("t,h,dk,chunk", [(64, 2, 32, 16), (32, 4, 16, 8), (48, 1, 64, 48)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_wkv6_sweep(t, h, dk, chunk, dtype, rng):
    b = 2
    r = jnp.asarray(rng.normal(size=(b, t, h, dk)), dtype)
    k = jnp.asarray(rng.normal(size=(b, t, h, dk)), dtype)
    v = jnp.asarray(rng.normal(size=(b, t, h, dk)), dtype)
    ld = -jnp.asarray(rng.uniform(0.01, 4.0, size=(b, t, h, dk)), jnp.float32)
    u = jnp.asarray(rng.normal(size=(h, dk)), jnp.float32) * 0.5
    got = wkv6(r, k, v, ld, u, chunk=chunk)
    want, _ = wkv6_ref(r, k, v, ld, u)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        **(dict(atol=1e-3, rtol=1e-3) if dtype == jnp.float32 else dict(atol=0.15, rtol=0.1)),
    )


def test_wkv6_extreme_decay_no_overflow(rng):
    """Strong decays must not overflow the chunked form (safe formulation)."""
    b, t, h, dk = 1, 32, 1, 16
    r = jnp.asarray(rng.normal(size=(b, t, h, dk)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(b, t, h, dk)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, t, h, dk)), jnp.float32)
    ld = jnp.full((b, t, h, dk), -50.0, jnp.float32)  # near-instant forgetting
    u = jnp.zeros((h, dk), jnp.float32)
    got = wkv6(r, k, v, ld, u, chunk=8)
    want, _ = wkv6_ref(r, k, v, ld, u)
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-4)


@pytest.mark.parametrize(
    "t,h,p,n,chunk", [(64, 2, 32, 16, 16), (32, 3, 16, 8, 8), (48, 1, 64, 32, 48)]
)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ssd_sweep(t, h, p, n, chunk, dtype, rng):
    b = 2
    x = jnp.asarray(rng.normal(size=(b, t, h, p)), dtype)
    bm = jnp.asarray(rng.normal(size=(b, t, h, n)), dtype)
    cm = jnp.asarray(rng.normal(size=(b, t, h, n)), dtype)
    dt = jnp.asarray(rng.uniform(0.01, 1.0, size=(b, t, h)), jnp.float32)
    a = -jnp.asarray(rng.uniform(0.5, 2.0, size=(h,)), jnp.float32)
    got = ssd(x, bm, cm, dt, a, chunk=chunk)
    want, _ = ssd_ref(x, bm, cm, dt, a)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        **(TOL[dtype] if dtype == jnp.float32 else dict(atol=0.15, rtol=0.1)),
    )
