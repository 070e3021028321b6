"""Serving: single-model batched decode and the stacked K-model fleet.

``serve_batch`` serves ONE model: prefill a prompt batch, then greedy-decode
with the generation collapsed into a single ``lax.scan`` dispatch
(``launch/steps.py:make_decode_scan``; ``decode_impl="python"`` keeps the
legacy per-token loop as the parity baseline).

``serve_fleet`` is the personalized-fleet path — P2PL's product is K
*divergent* models, and the trainer already emits them stacked
(``core/p2p.py:P2PState.params``, leading K axis).  The fleet server keeps
that exact layout: ``make_fleet_generate_fn`` routes each request group to
its peer's weights via a TRACED ``peer_ids`` gather and vmaps the fused
generate over the group axis, so ONE compile serves any request routing (the
one-compile rule of docs/ARCHITECTURE.md, applied to serving).  With
``peer_axis="pod"`` the same jitted function runs with the K parameter rows
sharded over the mesh (``sharding/specs.py:shard_peer_tree`` — the identical
placement the sharded trainer uses), so serving and training share the
stacked-parameter layout.

CLI:  python -m repro.launch.serve --arch smollm-135m --batch 4 --gen 8
      python -m repro.launch.serve --peers 8          # the stacked fleet
"""
from __future__ import annotations

import argparse
import sys
from typing import Any, Callable

import jax
import jax.numpy as jnp

from repro import telemetry
from repro.configs import get_config, reduced
from repro.launch import compile_cache
from repro.launch import steps as steps_lib
from repro.models import build_model

PyTree = Any


@telemetry.scoped("repro.route")
def route_params(stacked_params: PyTree, peer_ids: jax.Array) -> PyTree:
    """Gather each request group's parameter rows: (K, ...) -> (G, ...), or
    one model's (K, ...) -> (...) for a scalar ``peer_ids``.

    ``peer_ids`` (G,) int32 is a TRACED value — routing changes never
    recompile (``jnp.take`` with a traced index, not python indexing).
    """
    return jax.tree.map(lambda p: jnp.take(p, peer_ids, axis=0), stacked_params)


def make_fleet_generate_fn(model, gen_tokens: int) -> Callable:
    """The stacked K-model serving step.

    (stacked_params (K, ...), prompts (G, B, ...), caches (G, ...),
    peer_ids (G,)) -> (tokens (G, B, gen_tokens), caches)

    Request group g decodes under peer ``peer_ids[g]``'s weights: a traced
    gather routes the parameter rows, then the fused prefill+scan generate
    (``steps.make_generate_fn``) runs on each group: all at once, vmapped
    over the group axis, or, for a model with an expert layer, one group
    after another (``lax.map``), each routing only its own model's rows,
    since the TPU's grouped product (``moe.apply``) takes no batch axis.
    Jit with ``donate_argnums=(2,)`` to reuse the cache buffers in place; the
    program is ``jit_fleet``, registered with ``repro.telemetry`` as ``fleet``.
    """
    generate = steps_lib.make_generate_fn(model, gen_tokens)

    @telemetry.program("fleet", donate_argnums=(2,))
    def fleet(stacked_params, prompts, caches, peer_ids):
        if model.cfg.moe is None:
            routed = route_params(stacked_params, peer_ids)
            return jax.vmap(generate)(routed, prompts, caches)

        def one_group(group):
            prompt, cache, peer_id = group
            return generate(route_params(stacked_params, peer_id), prompt, cache)

        return jax.lax.map(one_group, (prompts, caches, peer_ids))

    return fleet


def make_fleet_classify_fn(apply_fn: Callable) -> Callable:
    """Stacked fleet serving for classifier models (the paper's 2NN MLP).

    (stacked_params (K, ...), inputs (G, N, ...), peer_ids (G,)) ->
    logits (G, N, C) — the same traced-gather + vmap routing as the LLM
    fleet, over a single forward instead of a generate loop.
    """

    def fleet(stacked_params, inputs, peer_ids):
        routed = route_params(stacked_params, peer_ids)
        return jax.vmap(apply_fn)(routed, inputs)

    return fleet


def stack_request_caches(cache: PyTree, num_groups: int) -> PyTree:
    """Replicate one fresh decode cache into the (G, ...) group layout."""
    return jax.tree.map(
        lambda x: jnp.tile(x[None], (num_groups,) + (1,) * x.ndim), cache
    )


def serve_batch(
    arch: str = "smollm-135m",
    *,
    batch: int = 4,
    prompt_len: int = 16,
    gen_tokens: int = 8,
    use_reduced: bool = True,
    seed: int = 0,
    verbose: bool = False,
    decode_impl: str = "scan",
) -> dict:
    """Single-model serving: prefill, then greedy-decode ``gen_tokens - 1``.

    The times are the spans ``serve.prefill`` and ``serve.decode``
    (``repro.telemetry``), on benchmarks/timing.py's discipline: jax
    dispatches asynchronously, so inputs are blocked on before a span opens
    and outputs before it closes — a bare clock around a jit call measures
    enqueue time, not execution time (and the reported times
    here still include compile, since each jit runs once; steady-state
    numbers live in benchmarks/serving.py).

    ``gen_tokens=1`` is the EXPLICIT empty decode: zero serve steps run, the
    prefill-sampled token is the only output (``tokens`` is (B, 1)),
    ``decode_steps`` is 0 and ``decode_s_per_token`` is None — not a rate
    divided out of a region in which nothing executed.
    """
    if gen_tokens < 1:
        raise ValueError(f"need gen_tokens >= 1, got {gen_tokens}")
    if decode_impl not in ("scan", "python"):
        raise ValueError(
            f"decode_impl must be 'scan' or 'python', got {decode_impl!r}"
        )
    cfg = get_config(arch)
    if use_reduced:
        cfg = reduced(cfg)
    model = build_model(cfg)
    rng = jax.random.PRNGKey(seed)
    params = model.init(rng)

    prompt = model.make_batch(rng, batch, prompt_len)
    max_len = prompt_len + gen_tokens
    cache = model.init_cache(batch, max_len)

    prefill = jax.jit(steps_lib.make_prefill_step(model))

    jax.block_until_ready((params, prompt, cache))
    with telemetry.span("serve.prefill") as prefill_span:
        tok, cache = prefill(params, prompt, cache)
        jax.block_until_ready((tok, cache))
    prefill_s = prefill_span.seconds

    decode_steps = gen_tokens - 1
    if decode_steps == 0:
        out = tok[:, None]
        decode_s_per_token = None
    else:
        # decode positions continue after the prompt's *decoder-side* length
        pos = jnp.full((batch,), steps_lib.prompt_dec_len(prompt), jnp.int32)
        if decode_impl == "scan":
            decode = jax.jit(
                steps_lib.make_decode_scan(model, decode_steps),
                donate_argnums=(1,),
            )
            with telemetry.span("serve.decode") as decode_span:
                gen, cache = decode(params, cache, tok, pos)
                jax.block_until_ready((gen, cache))
        else:
            serve = jax.jit(steps_lib.make_serve_step(model))
            first, toks = tok, []
            with telemetry.span("serve.decode") as decode_span:
                for _ in range(decode_steps):
                    tok, pos, cache = serve(params, cache, tok, pos)
                    toks.append(tok)
                jax.block_until_ready((toks, cache))
            gen, tok = jnp.stack(toks, axis=1), first
        out = jnp.concatenate([tok[:, None], gen], axis=1)
        decode_s_per_token = decode_span.seconds / decode_steps

    result = {
        "tokens": out,  # (B, gen_tokens)
        "cache": cache,
        "prefill_s": prefill_s,
        "decode_steps": decode_steps,
        "decode_s_per_token": decode_s_per_token,
    }
    if verbose:
        print(f"arch={arch} batch={batch} prompt={prompt_len} gen={gen_tokens} "
              f"decode_impl={decode_impl}")
        decode_msg = (
            "decode: (empty — gen_tokens=1 samples only the prefill token)"
            if decode_s_per_token is None
            else f"decode: {decode_s_per_token*1e3:.2f} ms/token"
        )
        print(f"prefill: {prefill_s*1e3:.1f} ms; {decode_msg}")
        print("sample tokens:", out[0].tolist())
    return result


def fleet_inputs(model, num_peers: int, batch: int, prompt_len: int, seed: int = 0):
    """The (K, ...) parameter stack and (K, B, prompt_len) prompts that
    ``serve_fleet`` serves for ``seed``."""
    stacked_params = jax.vmap(model.init)(
        jax.random.split(jax.random.PRNGKey(seed), num_peers)
    )
    prompts = jax.vmap(lambda k: model.make_batch(k, batch, prompt_len))(
        jax.random.split(jax.random.PRNGKey(seed + 1), num_peers)
    )
    return stacked_params, prompts


def serve_fleet(
    arch: str = "smollm-135m",
    *,
    num_peers: int = 8,
    batch: int = 4,
    prompt_len: int = 16,
    gen_tokens: int = 8,
    use_reduced: bool = True,
    seed: int = 0,
    peer_axis: str = "vmap",
    verbose: bool = False,
) -> dict:
    """Serve ``num_peers`` personalized models from ONE stacked process.

    Builds K per-peer parameter sets (independent seeds standing in for a
    trained ``P2PState.params`` stack), one request group per peer, and runs
    the whole fleet through a single jitted call with cache donation.
    ``peer_axis="pod"`` places the K rows (and the request groups) over the
    mesh — one device per peer, same layout as the sharded trainer; it
    needs ``num_peers`` visible devices (``launch/mesh.py:make_peer_mesh``
    fails fast with the CPU incantation otherwise).
    """
    if peer_axis not in ("vmap", "pod"):
        raise ValueError(f"peer_axis must be 'vmap' or 'pod', got {peer_axis!r}")
    cfg = get_config(arch)
    if use_reduced:
        cfg = reduced(cfg)
    model = build_model(cfg)
    stacked_params, prompts = fleet_inputs(model, num_peers, batch, prompt_len, seed)
    caches = stack_request_caches(
        model.init_cache(batch, prompt_len + gen_tokens), num_peers
    )
    peer_ids = jnp.arange(num_peers, dtype=jnp.int32)

    fleet = jax.jit(make_fleet_generate_fn(model, gen_tokens), donate_argnums=(2,))
    if peer_axis == "pod":
        from repro.launch import mesh as mesh_lib
        from repro.sharding import specs as specs_lib

        mesh = mesh_lib.make_peer_mesh(num_peers)
        stacked_params = specs_lib.shard_peer_tree(stacked_params, mesh)
        prompts = specs_lib.shard_peer_tree(prompts, mesh)
        caches = specs_lib.shard_peer_tree(caches, mesh)
        peer_ids = specs_lib.shard_peer_tree(peer_ids, mesh)

    jax.block_until_ready((stacked_params, prompts, caches, peer_ids))
    with telemetry.span("serve.fleet") as fleet_span:
        tokens, caches = fleet(stacked_params, prompts, caches, peer_ids)
        jax.block_until_ready(tokens)
    serve_s = fleet_span.seconds

    total_tokens = int(tokens.shape[0] * tokens.shape[1] * tokens.shape[2])
    result = {
        "tokens": tokens,  # (K, B, gen_tokens)
        "serve_s": serve_s,
        "tokens_per_s": total_tokens / serve_s,
    }
    if verbose:
        print(f"arch={arch} fleet: {num_peers} personalized models x "
              f"{batch} requests x {gen_tokens} tokens, peer_axis={peer_axis}")
        print(f"one stacked call: {serve_s*1e3:.1f} ms "
              f"({result['tokens_per_s']:.0f} tokens/s, includes compile; "
              "steady-state numbers: benchmarks/serving.py)")
        print("peer 0 tokens:", tokens[0, 0].tolist())
    return result


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-135m")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--gen", type=int, default=8)
    ap.add_argument("--peers", type=int, default=0,
                    help="serve this many personalized models from one "
                         "stacked process (0 = single-model serve_batch)")
    ap.add_argument("--peer-axis", default="vmap", choices=["vmap", "pod"],
                    help="with --peers: 'vmap' stacks the fleet on one "
                         "device; 'pod' shards one model replica per device "
                         "(needs --peers visible devices — on CPU set "
                         "XLA_FLAGS=--xla_force_host_platform_device_count=K)")
    ap.add_argument("--decode-impl", default="scan", choices=["scan", "python"],
                    help="single-model decode driver: 'scan' is one fused "
                         "lax.scan dispatch, 'python' the legacy per-token "
                         "loop (parity baseline)")
    ap.add_argument("--full", action="store_true", help="use the full (non-reduced) config")
    args = ap.parse_args(argv)
    if args.peers:
        serve_fleet(
            args.arch,
            num_peers=args.peers,
            batch=args.batch,
            prompt_len=args.prompt_len,
            gen_tokens=args.gen,
            use_reduced=not args.full,
            peer_axis=args.peer_axis,
            verbose=True,
        )
    else:
        serve_batch(
            args.arch,
            batch=args.batch,
            prompt_len=args.prompt_len,
            gen_tokens=args.gen,
            use_reduced=not args.full,
            verbose=True,
            decode_impl=args.decode_impl,
        )
    print(telemetry.summary(), file=sys.stderr)


if __name__ == "__main__":
    compile_cache.enable()
    main()
