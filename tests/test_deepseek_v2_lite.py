"""DeepSeek-V2-Lite on the serving path against a plain float32 reference
(``tests/deepseek_v2_lite_reference.py``), on seeded random weights at a
small DeepSeek-V2-Lite-shaped size: MLA with a latent cache and YaRN, a
leading dense layer, then expert layers with un-normalised top-k gates, a
routed scaling factor and 2 shared experts, served from a share of the
experts (expert parallelism without its exchange).
"""
import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepseek_v2_lite_reference as ref
from repro.configs import get_config
from repro.configs.base import AttentionConfig, ModelConfig, MoEConfig, RopeScaling
from repro.launch import serve as serve_lib
from repro.launch import steps as steps_lib
from repro.models import attention, build_model, common, moe

YARN = RopeScaling(factor=40.0, original_max_position=4096, beta_fast=32.0, beta_slow=1.0,
                   mscale=0.707, mscale_all_dim=0.707)


def small_config(held: int = 0, offset: int = 0) -> ModelConfig:
    """3 layers (1 dense + 2 expert), d 64, 4 MLA heads of 16 + 16 (rope 16,
    so YaRN's ramp runs inside the head), 16 experts of 32 top-4, 2 shared."""
    return ModelConfig(
        name="dsv2-lite-small", family="moe", num_layers=3, d_model=64, d_ff=96,
        vocab_size=256,
        attention=AttentionConfig(
            num_heads=4, num_kv_heads=4, head_dim=16, kind="mla", rope_theta=10000.0,
            rope_scaling=YARN, kv_lora_rank=32, q_lora_rank=0, qk_nope_dim=16,
            qk_rope_dim=16, v_head_dim=16),
        moe=MoEConfig(num_experts=16, top_k=4, expert_ff=32, num_shared=2,
                      first_dense_layers=1, dense_ff=96, norm_topk_prob=False,
                      routed_scaling_factor=2.5, experts_held=held, expert_offset=offset),
        tie_embeddings=False, norm_eps=1e-6, dtype="float32", remat=False)


def ref_config(cfg: ModelConfig) -> dict:
    """The published config.json's keys for ``cfg``."""
    a, m, rs = cfg.attention, cfg.moe, cfg.attention.rope_scaling
    return {
        "num_attention_heads": a.num_heads, "kv_lora_rank": a.kv_lora_rank,
        "qk_nope_head_dim": a.qk_nope_dim, "qk_rope_head_dim": a.qk_rope_dim,
        "v_head_dim": a.v_head_dim, "rms_norm_eps": cfg.norm_eps, "rope_theta": a.rope_theta,
        "rope_scaling": {"factor": rs.factor, "original_max_position_embeddings":
                         rs.original_max_position, "beta_fast": rs.beta_fast,
                         "beta_slow": rs.beta_slow, "mscale": rs.mscale,
                         "mscale_all_dim": rs.mscale_all_dim},
        "num_experts_per_tok": m.top_k, "norm_topk_prob": m.norm_topk_prob,
        "routed_scaling_factor": m.routed_scaling_factor,
    }


def seeded_weights(model, seed: int):
    """The program's init with every leaf scaled by 1 + 0.1 u, so norm scales
    differ from 1 and every weight is used as given."""
    params = model.init(jax.random.PRNGKey(seed))
    leaves, treedef = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(seed + 1), len(leaves))
    return treedef.unflatten([
        x * (1.0 + 0.1 * jax.random.uniform(k, x.shape, x.dtype, -1.0, 1.0))
        for k, x in zip(keys, leaves)])


# moe.DENSE_DISPATCH_MAX_TOKENS for each of the expert layer's two dispatches
DISPATCH = {"grouped": 0, "dense": 1 << 30}


@pytest.mark.parametrize("dispatch", sorted(DISPATCH))
@pytest.mark.parametrize("held,offset", [(0, 0), (8, 4)])
def test_prefill_then_decode_matches_the_reference_forward(held, offset, dispatch, monkeypatch):
    """Prefill of 12 tokens, then 6 decode steps through the latent cache
    (the absorbed form), each step's logits against the reference's full
    forward over the same tokens, with the experts run as a sorted grouped
    product or every held expert on every token.  Both sides are float32 at
    full precision, so what differs is the order of the sums: the absorbed
    form folds W_uk into the query.  Measured
    gaps are about 1e-6 of logits of order 1; the tolerance, 2e-5, leaves
    10x and is far below what one bfloat16 rounding of the activations
    moves (about 1e-2)."""
    monkeypatch.setattr(moe, "DENSE_DISPATCH_MAX_TOKENS", DISPATCH[dispatch])
    cfg = small_config(held, offset)
    model = build_model(cfg)
    params = seeded_weights(model, 0)
    prompt_len, steps = 12, 6
    seq = np.asarray(jax.random.randint(jax.random.PRNGKey(2), (prompt_len + steps,), 0,
                                        cfg.vocab_size), np.int32)
    want = np.asarray(ref.make_forward(ref_config(cfg), offset=offset)(params, jnp.asarray(seq)))

    cache = model.init_cache(1, prompt_len + steps)
    logits, cache = jax.jit(model.prefill)(params, {"tokens": jnp.asarray(seq[None, :prompt_len])},
                                           cache)
    got = [np.asarray(logits[0, -1])]
    decode = jax.jit(model.decode_step)
    for i in range(steps - 1):
        pos = prompt_len + i
        logits, cache = decode(params, jnp.asarray(seq[pos:pos + 1]), jnp.asarray([pos]), cache)
        got.append(np.asarray(logits[0, -1]))
    np.testing.assert_allclose(np.stack(got), want[prompt_len - 1:prompt_len + steps - 1],
                               rtol=0, atol=2e-5)


@pytest.mark.parametrize("dispatch", sorted(DISPATCH))
def test_expert_shares_add_up_to_the_uncut_layer(dispatch, monkeypatch):
    """64 experts top-6 split into 8 shares of 8: each share's dropless layer
    (the program, told which 8 it holds) without the shared experts, summed,
    plus the shared experts once, equals the reference's whole layer.  The
    tolerance, 1e-5 against outputs of order 0.1, covers float32 sums taken
    in another order (the shares add in a different sequence)."""
    monkeypatch.setattr(moe, "DENSE_DISPATCH_MAX_TOKENS", DISPATCH[dispatch])
    d = 32
    full = MoEConfig(num_experts=64, top_k=6, expert_ff=16, num_shared=2,
                     norm_topk_prob=False, routed_scaling_factor=1.0)
    params = moe.init(jax.random.PRNGKey(0), d, full, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 20, d), jnp.float32)

    total = jnp.zeros_like(x)
    for offset in range(0, 64, 8):
        share = dataclasses.replace(full, experts_held=8, expert_offset=offset)
        p = {"router": params["router"],
             **{n: params[n][offset:offset + 8] for n in ("w_gate", "w_up", "w_down")}}
        out, _ = moe.apply(p, share, x)
        total = total + out
    total = total + common.mlp_apply(params["shared"], x)

    cfg = {"num_experts_per_tok": 6, "norm_topk_prob": False, "routed_scaling_factor": 1.0}
    want = ref.expert_layer(x.reshape(-1, d), params, cfg).reshape(x.shape)
    np.testing.assert_allclose(np.asarray(total), np.asarray(want), rtol=0, atol=1e-5)


@pytest.mark.parametrize("dispatch", sorted(DISPATCH))
def test_every_token_to_one_expert_drops_nothing(dispatch, monkeypatch):
    """A router that sends every token to expert 3 (and 3 others): the
    dropless layer, in either dispatch, equals the dense oracle, where the
    capacity dispatch at its training factor drops most of expert 3's
    tokens."""
    monkeypatch.setattr(moe, "DENSE_DISPATCH_MAX_TOKENS", DISPATCH[dispatch])
    d = 16
    cfg = MoEConfig(num_experts=8, top_k=4, expert_ff=8, num_shared=2, norm_topk_prob=False,
                    routed_scaling_factor=2.5, capacity_factor=1.25)
    params = moe.init(jax.random.PRNGKey(0), d, cfg, jnp.float32)
    params["router"] = params["router"].at[:, 3].set(10.0)
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(1), (4, 16, d), jnp.float32)) + 1.0
    _, _, experts = moe.route(params, cfg, x)
    assert bool(jnp.all(experts[..., 0] == 3))

    out, _ = moe.apply(params, cfg, x)
    oracle, _ = moe.apply_dense_reference(params, cfg, x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(oracle), rtol=1e-5, atol=1e-5)
    dropped, _ = moe.apply_capacity(params, cfg, x)
    assert not np.allclose(np.asarray(dropped), np.asarray(oracle), atol=1e-3)


def test_yarn_ramp_and_mla_softmax_scale_match_the_formulas():
    """DeepSeek-V2-Lite's rope part (64 dims, theta 1e4, original 4096, beta
    32/1): the ramp runs over frequency indices 10..23; indices up to 10
    keep theta^(-2i/64), indices from 23 are divided by 40; the softmax scale
    is 192^-0.5 (1 + 0.1 * 0.707 * ln 40)^2."""
    a = get_config("deepseek-v2-lite").attention
    assert common.yarn_ramp_range(a.rope_scaling, 64, 10000.0) == (10, 23)
    got = np.asarray(common.rope_frequencies(64, 10000.0, a.rope_scaling), np.float64)
    plain = 10000.0 ** (-np.arange(0, 64, 2) / 64)
    np.testing.assert_allclose(got[:11], plain[:11], rtol=1e-6)
    np.testing.assert_allclose(got[23:], plain[23:] / 40, rtol=1e-6)
    ramp = (np.arange(11, 23) - 10) / 13
    np.testing.assert_allclose(got[11:23], plain[11:23] * (1 - ramp + ramp / 40), rtol=1e-6)
    want = 192**-0.5 * (1 + 0.1 * 0.707 * math.log(40)) ** 2
    assert attention.mla_softmax_scale(a) == pytest.approx(want, rel=1e-12)
    assert want == pytest.approx(0.11472, abs=5e-6)
    # mscale == mscale_all_dim: cos and sin are not rescaled
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 5, 64))
    pos = jnp.arange(5)[None]
    norms = jnp.linalg.norm(common.apply_rope(x, pos, 10000.0, a.rope_scaling), axis=-1)
    np.testing.assert_allclose(np.asarray(norms), np.asarray(jnp.linalg.norm(x, axis=-1)),
                               rtol=1e-5)


@pytest.mark.parametrize("dispatch", sorted(DISPATCH))
def test_fleet_of_two_models_equals_each_served_alone(dispatch, monkeypatch):
    """Two such models (each holding experts 4-11) stacked and served as a
    fleet, the two request groups routed crosswise (one group after another,
    as for every expert model), give the tokens each model gives served
    alone."""
    monkeypatch.setattr(moe, "DENSE_DISPATCH_MAX_TOKENS", DISPATCH[dispatch])
    cfg = small_config(8, 4)
    model = build_model(cfg)
    stacked = jax.tree.map(lambda a, b: jnp.stack([a, b]), seeded_weights(model, 0),
                           seeded_weights(model, 10))
    b, prompt_len, gen = 2, 8, 6
    prompts = jax.random.randint(jax.random.PRNGKey(3), (2, b, prompt_len), 0, cfg.vocab_size)
    peer_ids = jnp.asarray([1, 0], jnp.int32)
    caches = serve_lib.stack_request_caches(model.init_cache(b, prompt_len + gen), 2)
    fleet = jax.jit(serve_lib.make_fleet_generate_fn(model, gen), donate_argnums=(2,))
    tokens, _ = fleet(stacked, {"tokens": prompts}, caches, peer_ids)

    generate = jax.jit(steps_lib.make_generate_fn(model, gen))
    for g in range(2):
        alone = jax.tree.map(lambda x: x[int(peer_ids[g])], stacked)
        want, _ = generate(alone, {"tokens": prompts[g]}, model.init_cache(b, prompt_len + gen))
        np.testing.assert_array_equal(np.asarray(tokens[g]), np.asarray(want))


def test_published_config():
    """The registered config at published widths: 15.7B parameters, 2.4B of
    them active per token, the embedding lookup not counted (the model
    card's 15.7B-A2.4B)."""
    cfg = get_config("deepseek-v2-lite")
    assert (cfg.num_layers, cfg.d_model, cfg.vocab_size, cfg.moe.num_experts) == (
        27, 2048, 102400, 64)
    assert cfg.param_count() == pytest.approx(15.7e9, rel=0.01)
    embedding = cfg.vocab_size * cfg.d_model
    assert cfg.active_param_count() - embedding == pytest.approx(2.4e9, rel=0.03)


def test_fleet_groups_one_after_another_equal_all_at_once():
    """The fleet serves an expert model's groups one after another; with the
    dispatch a vmap can batch (every held expert on every token, which these
    few tokens take), the tokens are those of serving every group at once,
    vmapped over the routed weights."""
    cfg = small_config(8, 4)
    model = build_model(cfg)
    stacked = jax.tree.map(lambda a, b: jnp.stack([a, b]), seeded_weights(model, 0),
                           seeded_weights(model, 10))
    b, prompt_len, gen = 2, 8, 6
    assert b * prompt_len <= moe.DENSE_DISPATCH_MAX_TOKENS
    prompts = {"tokens": jax.random.randint(jax.random.PRNGKey(4), (3, b, prompt_len), 0,
                                            cfg.vocab_size)}
    peer_ids = jnp.asarray([1, 0, 1], jnp.int32)
    caches = lambda: serve_lib.stack_request_caches(model.init_cache(b, prompt_len + gen), 3)

    fleet = jax.jit(serve_lib.make_fleet_generate_fn(model, gen))
    got = np.asarray(fleet(stacked, prompts, caches(), peer_ids)[0])
    at_once = jax.jit(jax.vmap(steps_lib.make_generate_fn(model, gen)))
    want = at_once(serve_lib.route_params(stacked, peer_ids), prompts, caches())[0]
    np.testing.assert_array_equal(got, np.asarray(want))
