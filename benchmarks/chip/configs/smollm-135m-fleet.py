"""A fleet of personalized SmolLM-135M models served by the program.

The cell drives ``serve.make_fleet_generate_fn``, jitted with the cache
donated, exactly as ``serve.serve_fleet`` does: one call serves G request
groups of B prompts, group g under the model ``peer_ids[g]`` of the stacked
(K, ...) fleet, prefill then greedy decode through the KV cache.  The loop is
closed: the next call is sent when the last returns, with fresh caches.

The benchmark makes the K models' weights on the device from the seed, in
one jitted call, in the program's parameter layout and in bfloat16 (the
dtype they are served in); it draws each call's prompts and routing from the
seed before the window.  Correctness: after the window, a seeded sample of
finished requests is run through the plain reference
(``smollm-135m-fleet.reference.py``) over prompt and served tokens, and the
widest gap by which a served token's logit lies below the reference's best
is compared with its limit.
"""
from __future__ import annotations

import importlib.util
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

import checks
import flops
import traffic

_REF_PATH = Path(__file__).with_name("smollm-135m-fleet.reference.py")


def _load_reference():
    spec = importlib.util.spec_from_file_location("smollm_fleet_reference", _REF_PATH)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


reference = _load_reference()


def weight_key(seed: int):
    """The key the weights are drawn from: the TPU's own bit generator
    (``rbg``), seeded with all 64 bits of the seed."""
    key = jax.random.key(seed % 2**32, impl="rbg")
    return jax.random.fold_in(key, seed // 2**32)


def make_weights(key, shapes, num_models: int):
    """K models' weights in the layout of ``shapes`` (one model's
    ShapeDtypeStructs), stacked on a leading axis, from unit-variance uniform
    draws u: norm scales 1 + 0.1 u, the embedding u/sqrt(hidden), every
    kernel u/sqrt(fan_in) (layer-stacked kernels are (layers, fan_in, ...))."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    half_width = 3.0**0.5

    def one(i, path, s):
        name = jax.tree_util.keystr(path)
        shape = (num_models,) + s.shape
        u = jax.random.uniform(jax.random.fold_in(key, i), shape, jnp.float32,
                               -half_width, half_width)
        if name.endswith("['scale']"):
            x = 1.0 + 0.1 * u
        elif name == "['embed']" or name.startswith("['layers']"):
            x = u * s.shape[1] ** -0.5
        else:
            raise ValueError(f"no weight rule for {name} {s.shape}")
        return x.astype(s.dtype)

    @jax.jit
    def build(key):
        return jax.tree_util.tree_unflatten(
            treedef, [one(i, p, s) for i, (p, s) in enumerate(flat)])

    return build(key)


class Cell:
    def __init__(self, config: dict, workload: dict, *, seed: int, rehearse: bool, spans):
        self.config = dict(config)
        self.traffic = dict(workload["traffic"])
        if rehearse:
            self.config.update(workload["rehearsal"]["config"])
            self.traffic.update(workload["rehearsal"]["traffic"])
        self.check_requests = workload["check_requests"]
        self.seed = seed
        self.spans = spans
        tr = self.traffic
        self.g, self.b = tr["groups"], tr["batch"]
        self.prompt_len, self.gen = tr["prompt_len"], tr["gen_tokens"]
        self.info = {}
        self.served: list[tuple[int, np.ndarray]] = []

    def setup(self):
        from repro.configs.base import AttentionConfig, ModelConfig
        from repro.launch import serve
        from repro.models import build_model

        c, tr = self.config, self.traffic
        self.cfg = ModelConfig(
            name=c["name"], family="dense", num_layers=c["num_hidden_layers"],
            d_model=c["hidden_size"], d_ff=c["intermediate_size"], vocab_size=c["vocab_size"],
            attention=AttentionConfig(num_heads=c["num_attention_heads"],
                                      num_kv_heads=c["num_key_value_heads"],
                                      head_dim=c["head_dim"], rope_theta=c["rope_theta"]),
            tie_embeddings=c["tie_word_embeddings"], norm_eps=c["rms_norm_eps"],
            act=c["hidden_act"], dtype=c["torch_dtype"],
        )
        model = build_model(self.cfg)
        with self.spans.phase("weights"):
            shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
            self.params = make_weights(weight_key(self.seed), shapes, c["fleet_models"])
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

    def _call(self) -> dict:
        i = self.calls % len(self.prompts)
        self.calls += 1
        t0 = time.perf_counter()
        with self.spans.span("fleet_call"):
            caches = self.fresh_caches()
            tokens, _ = self.fleet(self.params, {"tokens": jnp.asarray(self.prompts[i])}, caches,
                                   jnp.asarray(self.peer_ids[i]))
            tokens = np.asarray(tokens)
        latency = time.perf_counter() - t0
        self.served.append((i, tokens))
        requests = self.g * self.b
        bad = int(np.any((tokens < 0) | (tokens >= self.config["vocab_size"]), axis=-1).sum())
        return {"tokens": requests * self.gen, "requests": requests, "attempted": requests,
                "failed": bad, "latencies_s": [latency] * requests}

    def call(self) -> dict:
        return self._call()

    def model_flops(self, counts: dict) -> float:
        per_request = flops.decoder_request_flops(self.config, self.prompt_len, self.gen)
        return counts.get("requests", 0) * per_request

    def free(self):
        self.fleet = self.fresh_caches = None

    # -- correctness -------------------------------------------------------
    def sample(self) -> list[tuple[int, int, int]]:
        """(served index, group, row) of the requests the check compares: a
        seeded draw from every request the window finished."""
        rng = np.random.default_rng([self.seed, 1])
        total = len(self.served) * self.g * self.b
        picks = rng.choice(total, size=min(self.check_requests, total), replace=False)
        return [(int(p) // (self.g * self.b), (int(p) // self.b) % self.g, int(p) % self.b)
                for p in sorted(picks)]

    def sequences(self):
        """Per sampled request: (model, prompt and served tokens but the last,
        served tokens)."""
        out = []
        for s, g, r in self.sample():
            i, tokens = self.served[s]
            served = tokens[g, r]
            seq = np.concatenate([self.prompts[i, g, r], served[:-1]])
            out.append((int(self.peer_ids[i, g]), seq, served))
        return out

    def model_weights(self, k: int):
        return jax.tree.map(lambda x: x[k].astype(jnp.float32), self.params)

    def reference_gaps(self, mode: str = "f32") -> float:
        """Widest reference-logit gap of the served tokens (``mode="f32"``),
        or of the tokens a control arithmetic puts first at the same
        positions (``"fp8"``, ``"int8"``)."""
        ref = reference.make_forward(self.config, "f32")
        ctl = None if mode == "f32" else reference.make_forward(self.config, mode)
        widest = 0.0
        seqs = self.sequences()
        for k in sorted({m for m, _, _ in seqs}):
            w = self.model_weights(k)
            for m, seq, served in seqs:
                if m != k:
                    continue
                logits = np.asarray(ref(w, jnp.asarray(seq)))[self.prompt_len - 1:]
                picked = served if ctl is None else np.asarray(
                    ctl(w, jnp.asarray(seq)))[self.prompt_len - 1:].argmax(axis=-1)
                widest = max(widest, checks.widest_logit_gap(logits, picked))
            del w
        return widest

    def readings(self, control: str | None = None) -> dict:
        """The compared number of the served tokens (``control=None``), or of
        the tokens the control arithmetic puts first at the same positions."""
        return {"logit_gap": self.reference_gaps(control or "f32")}
