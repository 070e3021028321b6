"""Optimization variants must be numerically equivalent to their baselines."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.core import graph as gl
from repro.launch import steps as steps_lib
from repro.sharding import specs


def test_psum_consensus_equals_einsum_uniform_complete(rng):
    for k in (2, 4):
        g = gl.build_graph("complete", k)
        w = gl.mixing_matrix(g, "data_weighted", data_sizes=np.ones(k))
        beta = gl.affinity_matrix(g)
        tree = {"a": jnp.asarray(rng.normal(size=(k, 6, 5)), jnp.float32),
                "b": jnp.asarray(rng.normal(size=(k, 3)), jnp.float32)}
        d0 = jax.tree.map(jnp.zeros_like, tree)
        f_ein = steps_lib.make_consensus_step(w, beta, local_steps=10, use_affinity=True)
        f_psum = steps_lib.make_consensus_step_psum(
            k, self_weight=float(w[0, 0]), peer_weight=float(w[0, 1]),
            local_steps=10, use_affinity=True,
        )
        m1, d1 = f_ein(tree, d0)
        m2, d2 = f_psum(tree, d0)
        for key in tree:
            np.testing.assert_allclose(np.asarray(m1[key]), np.asarray(m2[key]), atol=1e-5)
            np.testing.assert_allclose(np.asarray(d1[key]), np.asarray(d2[key]), atol=1e-5)


def test_cache_layout_specs():
    names = ["main", "k"]
    heads = specs.cache_leaf_spec(names, 4, layout="heads")
    seq = specs.cache_leaf_spec(names, 4, layout="seq")
    assert heads == P("data", None, "model", None)
    assert seq == P("data", "model", None, None)
    # MLA latent cache
    assert specs.cache_leaf_spec(["c_kv"], 3, layout="seq") == P("data", "model", None)


def test_mla_absorb_equals_expanded_decode(rng, monkeypatch):
    """Decode picks the absorbed form (one query position against a cache);
    its logits equal those of the same step run in the expanded form."""
    from repro.configs import get_config, reduced
    from repro.models import attention, build_model

    cfg = reduced(get_config("deepseek-v2-236b"))
    m = build_model(cfg)
    params = m.init(jax.random.PRNGKey(0))
    batch = m.make_batch(jax.random.PRNGKey(1), 2, 8)
    cache = m.init_cache(2, 12)
    _, cache = m.prefill(params, batch, cache)
    tok = jnp.asarray([3, 5], jnp.int32)
    pos = jnp.full((2,), 8, jnp.int32)

    used = []
    absorbed = attention.mla_context_absorbed
    monkeypatch.setattr(attention, "mla_context_absorbed",
                        lambda *a: used.append("absorbed") or absorbed(*a))
    logits1, _ = m.decode_step(params, tok, pos, cache)
    assert used, "decode did not take the absorbed form"
    monkeypatch.setattr(attention, "mla_context_absorbed", attention.mla_context_expanded)
    logits2, _ = m.decode_step(params, tok, pos, cache)
    np.testing.assert_allclose(np.asarray(logits1), np.asarray(logits2), atol=2e-3, rtol=2e-3)


def test_sliding_window_ring_buffer_decode(rng):
    """Window-cache decode == full-cache decode when history fits the window,
    and stays finite/correct beyond it."""
    from repro.configs import get_config, reduced
    from repro.models import build_model

    base = reduced(get_config("minitron-8b"))
    win = base.replace(attention=dataclasses.replace(base.attention, sliding_window=8))
    m_full, m_win = build_model(base), build_model(win)
    params = m_full.init(jax.random.PRNGKey(0))
    batch = m_full.make_batch(jax.random.PRNGKey(1), 1, 6)

    cache_f = m_full.init_cache(1, 32)
    cache_w = m_win.init_cache(1, 32)
    assert jax.tree.leaves(cache_w)[0].shape[2] == 8  # ring buffer = window
    lf, cache_f = m_full.prefill(params, batch, cache_f)
    lw, cache_w = m_win.prefill(params, batch, cache_w)
    np.testing.assert_allclose(np.asarray(lf), np.asarray(lw), atol=2e-3, rtol=2e-3)

    tok = jnp.asarray([1], jnp.int32)
    for i in range(12):  # run decode past the window size
        pos = jnp.full((1,), 6 + i, jnp.int32)
        lgf, cache_f = m_full.decode_step(params, tok, pos, cache_f)
        lgw, cache_w = m_win.decode_step(params, tok, pos, cache_w)
        assert np.isfinite(np.asarray(lgw)).all()
        if 6 + i < 8:  # history still inside the window: exact match
            np.testing.assert_allclose(np.asarray(lgf), np.asarray(lgw), atol=2e-3, rtol=2e-3)
        tok = jnp.argmax(lgw[:, -1], -1).astype(jnp.int32)


def test_int8_kv_cache_close_to_fp(rng):
    """int8 cache decode logits ~= fp cache decode logits."""
    from repro.configs import get_config, reduced
    from repro.models import build_model

    base = reduced(get_config("minitron-8b"))
    q8 = base.replace(attention=dataclasses.replace(base.attention, cache_quant="int8"))
    m_fp, m_q8 = build_model(base), build_model(q8)
    params = m_fp.init(jax.random.PRNGKey(0))
    batch = m_fp.make_batch(jax.random.PRNGKey(1), 2, 8)
    c_fp = m_fp.init_cache(2, 12)
    c_q8 = m_q8.init_cache(2, 12)
    assert jax.tree.leaves({"k": c_q8["main"]["k"]})[0].dtype == jnp.int8
    l_fp, c_fp = m_fp.prefill(params, batch, c_fp)
    l_q8, c_q8 = m_q8.prefill(params, batch, c_q8)
    np.testing.assert_allclose(np.asarray(l_fp), np.asarray(l_q8), atol=0.05, rtol=0.05)
    tok = jnp.asarray([1, 2], jnp.int32)
    pos = jnp.full((2,), 8, jnp.int32)
    d_fp, _ = m_fp.decode_step(params, tok, pos, c_fp)
    d_q8, _ = m_q8.decode_step(params, tok, pos, c_q8)
    np.testing.assert_allclose(np.asarray(d_fp), np.asarray(d_q8), atol=0.05, rtol=0.05)
    # halved cache bytes
    bytes_fp = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(c_fp))
    bytes_q8 = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(c_q8))
    assert bytes_q8 < 0.65 * bytes_fp
