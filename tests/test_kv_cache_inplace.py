"""The decoder's KV cache rides in the layer scan's carry and each layer
writes only its new positions into the stacked cache, in place.

Every cache kind that ``transformer._decoder_trunk`` serves is checked
against a plain per-layer loop kept here: each layer's cache sliced out of
the stack, the block applied to that slab alone, the slabs stacked again.
Generated tokens and final caches must be bit-identical after a prefill and
several decode steps, through ``steps.make_generate_fn`` and through the fleet's two routes (vmap
over groups; ``lax.map`` for a model with an expert layer).  The counter
``decode.cache_write_bytes`` must count one token of every row of every layer
for a decode step.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import telemetry
from repro.configs import get_config, reduced
from repro.launch import serve as serve_lib
from repro.launch import steps as steps_lib
from repro.models import build_model, common, transformer

BATCH, PROMPT, GEN, GROUPS = 2, 6, 7, 2


def per_layer_trunk(params, cfg, x, positions, caches):
    """The reference: each layer's cache is its own slab, sliced out of the
    stack as the layer scan's input and stacked again as its output."""

    def layer(x, params_and_cache):
        p, c = params_and_cache
        x, c, _ = transformer._block_apply(p, cfg, x, positions, c)
        return x, c

    new_caches = {}
    for name, layers in (("first", "first_layers"), ("main", "layers")):
        if layers in params:
            x, new_caches[name] = jax.lax.scan(layer, x, (params[layers], caches[name]))
    return common.rmsnorm(params["final_norm"], x, cfg.norm_eps), new_caches, None


def _smollm(**attention):
    cfg = reduced(get_config("smollm-135m"))
    return cfg.replace(attention=dataclasses.replace(cfg.attention, **attention))


CASES = {
    "gqa_bf16": lambda: reduced(get_config("smollm-135m")).replace(dtype="bfloat16"),
    "int8_kv": lambda: _smollm(cache_quant="int8"),
    # 6 prompt slots, then decode positions 6..11 wrap round a ring of 8
    "window_wraps": lambda: _smollm(sliding_window=8),
    "mla_absorbed": lambda: reduced(get_config("deepseek-v2-lite")),
    "vlm_prefix": lambda: reduced(get_config("internvl2-2b")),
}
ROUTES = {"generate": ("gqa_bf16", "int8_kv", "window_wraps", "mla_absorbed", "vlm_prefix"),
          "fleet_vmap": ("gqa_bf16",), "fleet_map": ("mla_absorbed",)}


def entry_bytes(cfg) -> int:
    """Bytes of one cache entry (one position of one row of one layer),
    from the configuration: K and V (or MLA's latent and rope key), int8
    scales where quantised, and the position id."""
    a, item = cfg.attention, jnp.dtype(cfg.dtype).itemsize
    if a.kind == "mla":
        return (a.kv_lora_rank + a.qk_rope_dim) * item + 4
    if a.cache_quant == "int8":
        return 2 * a.num_kv_heads * a.head_dim + 2 * a.num_kv_heads * 2 + 4
    return 2 * a.num_kv_heads * a.head_dim * item + 4


def _run(model, route):
    """(tokens, final caches) of one prefill and GEN - 1 decode steps."""
    prompt_of = lambda key: model.make_batch(key, BATCH, PROMPT)
    max_len = steps_lib.prompt_dec_len(prompt_of(jax.random.PRNGKey(1))) + GEN + 2
    if route == "generate":
        params = model.init(jax.random.PRNGKey(0))
        fn = jax.jit(steps_lib.make_generate_fn(model, GEN), donate_argnums=(2,))
        return fn(params, prompt_of(jax.random.PRNGKey(1)), model.init_cache(BATCH, max_len))
    stacked = jax.vmap(model.init)(jax.random.split(jax.random.PRNGKey(0), 3))
    prompts = jax.vmap(prompt_of)(jax.random.split(jax.random.PRNGKey(1), GROUPS))
    caches = serve_lib.stack_request_caches(model.init_cache(BATCH, max_len), GROUPS)
    fleet = jax.jit(serve_lib.make_fleet_generate_fn(model, GEN), donate_argnums=(2,))
    return fleet(stacked, prompts, caches, jnp.array([2, 0], jnp.int32))


@pytest.mark.parametrize("route,case", [(r, c) for r, cs in ROUTES.items() for c in cs])
def test_in_place_cache_matches_per_layer_loop(route, case, monkeypatch):
    cfg = CASES[case]()
    model = build_model(cfg)
    assert (route == "fleet_map") == (cfg.moe is not None) or route == "generate"
    tokens, caches = _run(model, route)
    monkeypatch.setattr(transformer, "_decoder_trunk", per_layer_trunk)
    want_tokens, want_caches = _run(model, route)
    assert np.array_equal(np.asarray(tokens), np.asarray(want_tokens))
    assert jax.tree.structure(caches) == jax.tree.structure(want_caches)
    for got, want in zip(jax.tree.leaves(caches), jax.tree.leaves(want_caches)):
        assert got.dtype == want.dtype and got.shape == want.shape
        assert np.array_equal(np.asarray(got), np.asarray(want))
    if case == "window_wraps":  # the ring really wrapped: 12 positions, 8 slots
        pos_ids = np.asarray(caches["main"]["pos_ids"])
        assert pos_ids.shape[-1] == 8 and pos_ids.max() == PROMPT + GEN - 2 > 8


@pytest.mark.parametrize("case", list(CASES))
def test_decode_step_counts_one_token_per_row_and_layer(case):
    cfg = CASES[case]()
    model = build_model(cfg)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    cache = jax.eval_shape(lambda: model.init_cache(BATCH, 16))
    token = jax.ShapeDtypeStruct((BATCH,), jnp.int32)
    telemetry.reset()
    jax.eval_shape(model.decode_step, params, token, token, cache)
    want = BATCH * cfg.num_layers * entry_bytes(cfg)
    assert telemetry.counters()["decode.cache_write_bytes"] == want
    telemetry.reset()
