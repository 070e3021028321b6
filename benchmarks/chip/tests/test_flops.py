"""FLOP counters against the shapes and against XLA's own count."""
import json
from pathlib import Path

import pytest

import flops

CONFIGS = Path(__file__).resolve().parents[1] / "configs"


def test_the_2nn_counts_six_flops_per_parameter_per_trained_sample():
    widths = (784, 200, 200, 10)
    assert flops.mlp_params(widths) == 199_210
    assert flops.mlp_train_flops_per_sample(widths) == 6 * 199_210
    assert flops.mlp_eval_flops_per_sample(widths) == 2 * 199_210


def test_smollm_weights_per_token_match_the_published_shapes():
    cfg = json.loads((CONFIGS / "smollm-135m-fleet.json").read_text())
    d, ff, layers = 576, 1536, 30
    q, kv = 9 * 64, 3 * 64
    assert flops.decoder_matmul_params(cfg) == layers * (d * q + 2 * d * kv + q * d + 3 * d * ff)
    # with the tied embedding, the whole model is about 135M parameters
    total = flops.decoder_matmul_params(cfg) + 49152 * d + (2 * layers + 1) * d
    assert total == pytest.approx(134.5e6, rel=0.01)


def test_smollm_request_flops_against_xla_cost_analysis():
    """One layer at the published widths, prefill of T tokens on the CPU:
    XLA counts the full T x T attention square where the model needs the
    causal half, and a few elementwise FLOPs more."""
    import jax
    import jax.numpy as jnp

    from repro.configs.base import AttentionConfig, ModelConfig
    from repro.models import build_model

    cfg = json.loads((CONFIGS / "smollm-135m-fleet.json").read_text())
    cfg["num_hidden_layers"] = 1
    t = 128
    model = build_model(ModelConfig(
        name="one-layer", family="dense", num_layers=1, d_model=576, d_ff=1536,
        vocab_size=49152, attention=AttentionConfig(num_heads=9, num_kv_heads=3, head_dim=64),
        dtype="float32", remat=False))
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    cache = jax.eval_shape(lambda: model.init_cache(1, t))
    tokens = {"tokens": jax.ShapeDtypeStruct((1, t), jnp.int32)}
    cost = jax.jit(model.prefill).lower(params, tokens, cache).compile().cost_analysis()
    if isinstance(cost, list):
        cost = cost[0]
    ours = flops.decoder_request_flops(cfg, t, 1)
    square_minus_causal = 4 * 9 * 64 * (t * t - t * (t + 1) // 2)
    assert cost["flops"] == pytest.approx(ours + square_minus_causal, rel=0.03)
