"""MoE training dispatch: grouped-capacity path vs dense oracle; capacity
semantics.  The serving path's dropless dispatch: tests/test_deepseek_v2_lite.py."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import MoEConfig
from repro.models import moe


def _setup(rng_seed=0, e=4, k=2, d=16, f=32, shared=0, groups=1, cf=8.0):
    cfg = MoEConfig(num_experts=e, top_k=k, expert_ff=f, num_shared=shared,
                    capacity_factor=cf, router_groups=groups)
    params = moe.init(jax.random.PRNGKey(rng_seed), d, cfg, jnp.float32)
    return cfg, params


@pytest.mark.parametrize("groups", [1, 2])
@pytest.mark.parametrize("shared", [0, 1])
def test_grouped_matches_dense_reference_when_no_drops(groups, shared):
    """With a huge capacity factor nothing is dropped: exact match."""
    cfg, params = _setup(shared=shared, groups=groups, cf=64.0)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 8, 16), jnp.float32)
    out_g, aux_g = moe.apply_capacity(params, cfg, x)
    out_d, aux_d = moe.apply_dense_reference(params, cfg, x)
    np.testing.assert_allclose(np.asarray(out_g), np.asarray(out_d), atol=1e-4)
    if groups == 1:
        np.testing.assert_allclose(float(aux_g), float(aux_d), rtol=1e-4)
    else:
        # per-group load-balance stats differ slightly from global ones
        np.testing.assert_allclose(float(aux_g), float(aux_d), rtol=0.05)


def test_capacity_drops_tokens():
    """Tiny capacity: output is a (strictly) partial version of the dense one."""
    cfg, params = _setup(cf=0.25)
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 16, 16), jnp.float32)
    out_small, _ = moe.apply_capacity(params, cfg, x)
    out_full, _ = moe.apply_capacity(params, cfg.__class__(**{**cfg.__dict__, "capacity_factor": 64.0}), x)
    # some tokens dropped -> outputs differ; but finite and same shape
    assert out_small.shape == out_full.shape
    assert np.isfinite(np.asarray(out_small)).all()
    assert not np.allclose(np.asarray(out_small), np.asarray(out_full))


def test_capacity_value():
    cfg = MoEConfig(num_experts=8, top_k=2, expert_ff=4, capacity_factor=1.25)
    c = moe.capacity(cfg, 1024)
    assert c >= 1024 * 2 * 1.25 / 8
    assert c % 8 == 0


def test_aux_loss_uniform_router_is_one():
    """With perfectly uniform routing, E * sum f_e P_e / k -> ~1."""
    cfg, params = _setup(e=4, k=1, cf=64.0)
    # force uniform router
    params["router"] = jnp.zeros_like(params["router"])
    x = jax.random.normal(jax.random.PRNGKey(3), (4, 64, 16), jnp.float32)
    _, aux = moe.apply_capacity(params, cfg, x)
    assert 0.8 <= float(aux) <= 1.3


def test_group_count_divisibility_fallback():
    cfg = MoEConfig(num_experts=4, top_k=2, expert_ff=8, router_groups=16)
    assert moe._num_groups(cfg, 1) == 1  # long_500k decode: N=1
    assert moe._num_groups(cfg, 24) == 8  # gcd(16, 24)
    assert moe._num_groups(cfg, 32) == 16


def test_gradients_flow_through_dispatch():
    cfg, params = _setup(cf=8.0)
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 8, 16), jnp.float32)

    def loss(p):
        out, aux = moe.apply_capacity(p, cfg, x)
        return jnp.sum(out**2) + 0.01 * aux

    grads = jax.grad(loss)(params)
    for name in ("router", "w_gate", "w_up", "w_down"):
        g = np.asarray(grads[name])
        assert np.isfinite(g).all()
        assert np.abs(g).sum() > 0, f"no gradient through {name}"
