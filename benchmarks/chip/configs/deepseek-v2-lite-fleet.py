"""A fleet of personalized DeepSeek-V2-Lite models served by the program, at
this chip's share of an expert-parallel deployment.

The cell is the SmolLM fleet cell (``smollm-135m-fleet.py``: its closed loop,
its calls and its check, inherited) on another model: it drives
``serve.make_fleet_generate_fn``, jitted with the caches donated, exactly as
``serve.serve_fleet`` does; one call serves G request groups of B prompts,
group g under the model ``peer_ids[g]`` of the stacked (K, ...) fleet,
prefill then greedy decode through the MLA latent cache.

The configuration is the published ``config.json`` cut to one chip of eight
(``deployment``): the first 5 layers, the 8 experts of each expert layer held
here (the router keeps its 64 outputs) and a vocabulary slice of 12,800.
The benchmark makes the K models' weights on the device from the seed, in
one jitted call, in the program's parameter layout and in bfloat16.
Correctness: after the window, a seeded sample of finished requests is run
through the plain reference (``deepseek-v2-lite-fleet.reference.py``), one
model at a time, over prompt and served tokens, and the widest gap by which
a served token's logit lies below the reference's best is compared with its
limit.
"""
from __future__ import annotations

import importlib.util
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

import moe_flops
import traffic

HERE = Path(__file__).parent


def _load(name: str, file: str):
    spec = importlib.util.spec_from_file_location(name, HERE / file)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


reference = _load("deepseek_v2_lite_fleet_reference", "deepseek-v2-lite-fleet.reference.py")
# a copy of the SmolLM cell's module of this cell's own, whose inherited
# ``reference_gaps`` reads the module's ``reference``: this model's
fleet_cell = _load("smollm_fleet_cell", "smollm-135m-fleet.py")
fleet_cell.reference = reference

# the router's weights are this many times a kernel's: router logits of
# spread 2, so a token's top-6 gates sum to about 0.68 and the first is about
# 0.26, peaked as a trained router's are; at spread 1 (0.36 and 0.10) the held
# experts are too small a part of the layer for ``logit_gap`` to see a
# dispatch that drops some of them
ROUTER_SPREAD = 2.0


def make_weights(key, shapes, num_models: int):
    """K models' weights in the layout of ``shapes`` (one model's
    ShapeDtypeStructs), stacked on a leading axis, from unit-variance uniform
    draws u: norm scales 1 + 0.1 u, the embedding u/sqrt(hidden), every
    kernel u/sqrt(fan_in), the router ROUTER_SPREAD u/sqrt(hidden).
    Layer-stacked kernels are
    (layers, fan_in, ...), expert kernels (layers, experts, fan_in, ...), the
    head (hidden, vocabulary)."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    half_width = 3.0**0.5

    def one(i, path, s):
        name = jax.tree_util.keystr(path)
        shape = (num_models,) + s.shape
        u = jax.random.uniform(jax.random.fold_in(key, i), shape, jnp.float32,
                               -half_width, half_width)
        if name.endswith("['scale']"):
            x = 1.0 + 0.1 * u
        elif name.endswith("['moe']['router']"):
            x = ROUTER_SPREAD * u * s.shape[1] ** -0.5
        elif "['moe']['w_" in name:
            x = u * s.shape[2] ** -0.5
        elif name == "['lm_head']":
            x = u * s.shape[0] ** -0.5
        elif name == "['embed']" or name.startswith(("['layers']", "['first_layers']")):
            x = u * s.shape[1] ** -0.5
        else:
            raise ValueError(f"no weight rule for {name} {s.shape}")
        return x.astype(s.dtype)

    @jax.jit
    def build(key):
        return jax.tree_util.tree_unflatten(
            treedef, [one(i, p, s) for i, (p, s) in enumerate(flat)])

    return build(key)


def model_config(c: dict):
    """The program's ModelConfig of the configuration file ``c``; refuses
    what the program does not implement."""
    from repro.configs.base import AttentionConfig, ModelConfig, MoEConfig, RopeScaling

    unsupported = {k: c[k] for k, v in (("topk_method", "greedy"), ("scoring_func", "softmax"),
                                        ("n_group", 1), ("topk_group", 1), ("moe_layer_freq", 1))
                   if c[k] != v}
    if unsupported or c["rope_scaling"]["type"] != "yarn":
        raise ValueError(f"not implemented: {unsupported or c['rope_scaling']}")
    rs, dep = c["rope_scaling"], c["deployment"]
    return ModelConfig(
        name=c["name"], family="moe", num_layers=c["num_hidden_layers"],
        d_model=c["hidden_size"], d_ff=c["intermediate_size"], vocab_size=c["vocab_size"],
        attention=AttentionConfig(
            num_heads=c["num_attention_heads"], num_kv_heads=c["num_key_value_heads"],
            head_dim=c["v_head_dim"], kind="mla", rope_theta=c["rope_theta"],
            rope_scaling=RopeScaling(
                factor=rs["factor"], original_max_position=rs["original_max_position_embeddings"],
                beta_fast=rs["beta_fast"], beta_slow=rs["beta_slow"], mscale=rs["mscale"],
                mscale_all_dim=rs["mscale_all_dim"]),
            kv_lora_rank=c["kv_lora_rank"], q_lora_rank=c["q_lora_rank"] or 0,
            qk_nope_dim=c["qk_nope_head_dim"], qk_rope_dim=c["qk_rope_head_dim"],
            v_head_dim=c["v_head_dim"]),
        moe=MoEConfig(
            num_experts=dep["router_outputs"], top_k=c["num_experts_per_tok"],
            expert_ff=c["moe_intermediate_size"], num_shared=c["n_shared_experts"],
            first_dense_layers=c["first_k_dense_replace"], dense_ff=c["intermediate_size"],
            norm_topk_prob=c["norm_topk_prob"],
            routed_scaling_factor=c["routed_scaling_factor"],
            experts_held=c["n_routed_experts"], expert_offset=dep["expert_offset"]),
        tie_embeddings=c["tie_word_embeddings"], norm_eps=c["rms_norm_eps"],
        act=c["hidden_act"], dtype=c["torch_dtype"],
    )


class Cell(fleet_cell.Cell):
    def setup(self):
        from repro.launch import serve
        from repro.models import build_model

        c, tr = self.config, self.traffic
        self.cfg = model_config(c)
        self.model = model = build_model(self.cfg)
        self.info = {"config": c, "moe_passes_per_call": moe_flops.fleet_call_moe_passes(
            c, self.g, self.b, self.prompt_len, self.gen)}
        with self.spans.phase("weights"):
            shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
            self.params = make_weights(fleet_cell.weight_key(self.seed), shapes,
                                       c["fleet_models"])
            jax.block_until_ready(self.params)

        with self.spans.phase("traffic"):
            rng = np.random.default_rng(self.seed)
            n = tr["distinct_calls"]
            self.peer_ids = traffic.zipf_choice(rng, c["fleet_models"], tr["zipf_s"],
                                                (n, self.g))
            self.prompts = rng.integers(0, c["vocab_size"],
                                        (n, self.g, self.b, self.prompt_len), dtype=np.int32)
        self.fleet = jax.jit(serve.make_fleet_generate_fn(model, self.gen), donate_argnums=(2,))
        g, b, max_len = self.g, self.b, self.prompt_len + self.gen

        def fresh_caches():
            return serve.stack_request_caches(model.init_cache(b, max_len), g)

        self.fresh_caches = jax.jit(fresh_caches)
        self.calls = 0
        with self.spans.phase("first call"):
            self._call()
        self.served.clear()

    def model_flops(self, counts: dict) -> float:
        per_request = moe_flops.request_flops(self.config, self.prompt_len, self.gen)
        return counts.get("requests", 0) * per_request
