"""Compile the main path for a described TPU v5e, without a chip.

The TPU compiler is installed wherever jax's TPU support is, and it compiles
for a ``v5e:2x2`` topology that is described rather than attached.  That
catches what interpret mode cannot: blocks off the (8, 128) tile, operations
Pallas TPU cannot lower, collectives a mesh cannot place.  Nothing runs, so
these tests say nothing about results or times.

The topology is described inside a module-scoped fixture (never at import):
only one process at a time may load the TPU library, so every test that
needs it lives in this one file, and every kernel entry point is given
``interpret=False`` explicitly (the platform default resolves against the
CPU backend the tests run on).
"""
import functools
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding

from repro.configs.p2pl_mnist import noniid_k2, seqmnist_k8, sharded_k8
from repro.core import p2p
from repro.core import task as task_lib
from repro.data import partition, synthetic
from repro.sharding import specs as specs_lib


@pytest.fixture(scope="module")
def topo(tmp_path_factory):
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # a compile for a described chip can be written to the persistent cache
    # but never read back without one: keep these out of it
    cache_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    # the TPU library logs under /tmp unless given a directory ("disabled"
    # still leaves its driver log there): keep them in the test's temp tree
    os.environ.setdefault("TPU_LOG_DIR", str(tmp_path_factory.mktemp("tpu_logs")))
    try:
        desc = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        desc = None
        reason = f"no v5e:2x2 topology can be described here: {e}"
    if desc is not None:
        yield desc
    jax.config.update("jax_enable_compilation_cache", cache_on)
    compilation_cache.reset_cache()
    if desc is None:
        pytest.skip(reason)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def data():
    return synthetic.mnist_like(num_train=2000, num_test=100)


def _shapes(tree, sharding):
    """ShapeDtypeStructs of ``tree`` placed by ``sharding`` (one sharding, or
    a matching tree of them)."""
    if not isinstance(sharding, (SingleDeviceSharding, NamedSharding)):
        return jax.tree.map(
            lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s), tree, sharding
        )
    return jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding), tree)


def _experiment_inputs(exp, data, rounds_per_call=None):
    """(task, cfg, data sizes, state shapes, batch arrays) of an experiment,
    built through the same task, partition and batcher as the trainer."""
    task = task_lib.get_task(exp.p2p.model)
    x_tr, y_tr, _, _ = data
    parts = partition.pathological_partition(
        x_tr, y_tr, list(exp.peer_classes), samples_per_class=exp.samples_per_class
    )
    sizes = partition.data_sizes(parts)
    state = jax.eval_shape(
        lambda: p2p.init_state(jax.random.PRNGKey(0), task, exp.p2p, data_sizes=sizes)
    )
    t = exp.p2p.local_steps
    bx, by = task.make_peer_batches(parts, exp.batch_size, seed=0).round_batches(t)
    if rounds_per_call is not None:  # scan-driver chunk layout (C, T, K, ...)
        bx = np.broadcast_to(bx, (rounds_per_call,) + bx.shape)
        by = np.broadcast_to(by, (rounds_per_call,) + by.shape)
    return task, exp.p2p, sizes, state, (bx, by)


def test_mlp_vmap_round_step_compiles(data, one_chip):
    """The paper's 2NN (784-200-200-10) round step, K=2, vmap runtime."""
    exp = noniid_k2(algorithm="p2pl_affinity")
    task, cfg, sizes, state, batches = _experiment_inputs(exp, data)
    round_fn = p2p.make_round_fn(task, cfg, data_sizes=sizes)
    compiled = round_fn.lower(_shapes(state, one_chip), _shapes(batches, one_chip)).compile()
    assert compiled.memory_analysis() is not None


def test_seqmnist_scan_driver_compiles(data, one_chip):
    """RWKV6 sequential-MNIST, K=8, two rounds per scanned call."""
    exp = seqmnist_k8()
    task, cfg, sizes, state, batches = _experiment_inputs(exp, data, rounds_per_call=2)
    drive = p2p.make_scan_driver(task, cfg, data_sizes=sizes)
    compiled = drive.lower(_shapes(state, one_chip), _shapes(batches, one_chip)).compile()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 16e9  # fits one v5e chip's HBM


def test_pod_round_compiles_on_four_chips(data, topo):
    """K=4 pod runtime, one peer per chip: consensus lowers to ppermutes."""
    exp = sharded_k8(num_peers=4)
    task, cfg, sizes, state, batches = _experiment_inputs(exp, data)
    mesh = Mesh(np.asarray(topo.devices[:4]), ("pod",))
    state_sh = jax.tree.map(
        lambda s: NamedSharding(mesh, s), specs_lib.peer_stacked_pspecs(state)
    )
    batch_sh = jax.tree.map(
        lambda s: NamedSharding(mesh, s), specs_lib.peer_batch_pspecs(batches)
    )
    round_fn = p2p.make_sharded_round_fn(task, cfg, mesh, data_sizes=sizes)
    compiled = round_fn.lower(_shapes(state, state_sh), _shapes(batches, batch_sh)).compile()
    assert "collective-permute" in compiled.as_text()


def _mlp_stack(k):
    """(K, N) f32 flat stack of K 2NN parameter sets (199,210 each)."""
    task = task_lib.get_task("mnist_mlp")
    params = jax.eval_shape(jax.vmap(task.init_params), jax.random.split(jax.random.PRNGKey(0), k))
    n = sum(int(np.prod(leaf.shape[1:])) for leaf in jax.tree.leaves(params))
    assert n == 199_210
    return params, n


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


def test_consensus_mix_stacked_compiles_at_mlp_size(one_chip):
    from repro.kernels.consensus_mix import ops

    k, d = 2, 1
    params, _ = _mlp_stack(k)
    args = (
        _shapes(params, one_chip),
        jax.ShapeDtypeStruct((k,), jnp.float32, sharding=one_chip),
        jax.ShapeDtypeStruct((k, d), jnp.int32, sharding=one_chip),
        jax.ShapeDtypeStruct((k, d), jnp.float32, sharding=one_chip),
        jax.ShapeDtypeStruct((k, d), jnp.float32, sharding=one_chip),
    )
    fn = functools.partial(ops.consensus_mix_stacked, local_steps=10, interpret=False)
    _assert_kernel(jax.jit(fn).lower(*args).compile())


def test_dequant_mix_compiles_at_mlp_size(one_chip):
    from repro.kernels.consensus_mix import dequant

    k, d = 8, 2
    params, n = _mlp_stack(k)

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    args = (
        _shapes(params, one_chip),
        sds((k, n), jnp.float32),
        sds((k, n), jnp.int8),
        sds((k,), jnp.float32),
        sds((k,), jnp.float32),
        sds((k, d), jnp.int32),
        sds((k, d), jnp.float32),
        sds((k, d), jnp.float32),
    )
    fn = functools.partial(dequant.dequant_consensus_mix_stacked, local_steps=10, interpret=False)
    _assert_kernel(jax.jit(fn).lower(*args).compile())


def test_segment_mix_compiles_at_mlp_size(one_chip):
    from repro.kernels.consensus_mix import segment

    k, d = 8, 2
    params, _ = _mlp_stack(k)
    args = (
        _shapes(params, one_chip),
        jax.ShapeDtypeStruct((k,), jnp.float32, sharding=one_chip),
        jax.ShapeDtypeStruct((k, d), jnp.int32, sharding=one_chip),
        jax.ShapeDtypeStruct((k, d), jnp.float32, sharding=one_chip),
        jax.ShapeDtypeStruct((k, d), jnp.float32, sharding=one_chip),
    )
    fn = functools.partial(segment.segment_mix_stacked, local_steps=10, interpret=False)
    _assert_kernel(jax.jit(fn).lower(*args).compile())


def test_gqa_flash_attention_compiles_at_smollm_heads(one_chip):
    """smollm-135m attention: 9 query heads over 3 KV heads of width 64."""
    from repro.configs import get_config
    from repro.kernels.flash_attention.ops import gqa_flash_attention

    att = get_config("smollm-135m").attention
    b, s = 2, 1024

    def sds(heads):
        return jax.ShapeDtypeStruct((b, s, heads, att.head_dim), jnp.bfloat16, sharding=one_chip)

    fn = functools.partial(gqa_flash_attention, causal=True, interpret=False)
    compiled = jax.jit(fn).lower(sds(att.num_heads), sds(att.num_kv_heads), sds(att.num_kv_heads))
    _assert_kernel(compiled.compile())


def test_grouped_product_counts_with_the_expert_layer(one_chip):
    """Above ``DENSE_DISPATCH_MAX_TOKENS`` tokens the dropless expert layer
    runs the TPU's grouped product, a custom call without op metadata: every
    one of its instructions maps to ``repro.moe`` by its operands."""
    from repro import telemetry
    from repro.configs.base import MoEConfig
    from repro.models import moe

    cfg = MoEConfig(num_experts=64, top_k=6, expert_ff=128, num_shared=2, experts_held=8,
                    norm_topk_prob=False)
    params = jax.eval_shape(lambda k: moe.init(k, 256, cfg, jnp.bfloat16),
                            jax.random.PRNGKey(0))
    x = jax.ShapeDtypeStruct((2, moe.DENSE_DISPATCH_MAX_TOKENS, 256), jnp.bfloat16,
                             sharding=one_chip)
    fn = lambda p, x: moe.apply(p, cfg, x)[0]
    text = jax.jit(fn).lower(_shapes(params, one_chip), x).compile().as_text()
    scopes = telemetry.hlo_scopes(text)
    grouped = [name for name in scopes if name.startswith("ragged-dot")]
    assert len(grouped) >= 3  # gate, up and down products
    assert {scopes[name] for name in grouped} == {"repro.moe"}


_HLO_INSTRUCTION = re.compile(
    r"^\s+(?:ROOT\s+)?%?([^\s=]+)\s*=\s*(\w+)\[([\d,]*)\]\S*\s+([\w\-]+)\(([^)]*)\)(.*)$"
)
_HLO_CALLEE = re.compile(r"\b(?:calls|body|condition|to_apply)=%?([\w.\-]+)")


def _hlo_computations(text):
    """{computation: {"ops": {name: (dtype, dims, opcode, operands, attrs)},
    "root": name, "callees": [...], "whiles": [(op_name, body)]}} of an HLO
    module's text; instructions of tuple shape are left out of "ops"."""
    comps, comp = {}, None
    for line in text.splitlines():
        if line and not line[0].isspace() and line.rstrip().endswith("{"):
            comp = comps.setdefault(re.match(r"^(?:ENTRY\s+)?%?([^\s(]+)", line).group(1),
                                    {"ops": {}, "root": None, "callees": [], "whiles": []})
            continue
        if comp is None or not line.startswith(" "):
            continue
        comp["callees"] += _HLO_CALLEE.findall(line)
        if " while(" in line:
            op_name = re.search(r'op_name="([^"]*)"', line)
            body = re.search(r"body=%?([\w.\-]+)", line).group(1)
            comp["whiles"].append((op_name.group(1) if op_name else "", body))
        m = _HLO_INSTRUCTION.match(line)
        if m:
            name, dtype, dims, opcode, operands, attrs = m.groups()
            dims = tuple(int(d) for d in dims.split(",") if d)
            comp["ops"][name] = (dtype, dims, opcode, re.findall(r"%([\w.\-]+)", operands), attrs)
            if line.lstrip().startswith("ROOT"):
                comp["root"] = name
    return comps


def _cache_sized_ops_in_loops(text, scope, dims_of_stacks):
    """(opcode, update dims) of every instruction, in the loops traced under
    ``scope`` and all they call, whose output has the dims of a whole stacked
    cache.  An update is a scatter's or dynamic-update-slice's new values,
    also as the root of a fusion; other opcodes give None."""
    comps = _hlo_computations(text)
    todo = [body for c in comps.values() for op_name, body in c["whiles"] if scope in op_name]
    seen, found = set(), []
    while todo:
        name = todo.pop()
        if name in seen or name not in comps:
            continue
        seen.add(name)
        todo += comps[name]["callees"]
        for _, (_, dims, opcode, operands, attrs) in comps[name]["ops"].items():
            if dims not in dims_of_stacks or opcode in ("parameter", "get-tuple-element",
                                                        "bitcast"):
                continue
            ops = comps[name]["ops"]
            if opcode == "fusion":
                fused = comps[_HLO_CALLEE.search(attrs).group(1)]
                ops, root = fused["ops"], fused["ops"][fused["root"]]
                opcode, operands = f"fusion:{root[2]}", root[3]
            update = {"scatter": 2, "dynamic-update-slice": 1}.get(opcode.split(":")[-1])
            found.append((opcode, None if update is None else ops[operands[update]][1]))
    return found


def test_fleet_decode_writes_one_token_per_row_in_place(one_chip):
    """SmolLM's attention (3 KV heads of 64) at small depth and width, as
    the fleet serves it: groups vmapped, the cache donated.  Inside the
    decode loop nothing the size of a whole stacked cache is copied,
    transposed or allocated; the only such ops update it in place, with one
    token of every row of every group."""
    from repro.configs.base import AttentionConfig, ModelConfig
    from repro.launch import serve
    from repro.models import build_model

    groups, rows, prompt, gen = 2, 4, 12, 5
    cfg = ModelConfig(name="smollm-small", family="dense", num_layers=3, d_model=128, d_ff=256,
                      vocab_size=512, attention=AttentionConfig(num_heads=9, num_kv_heads=3,
                                                                head_dim=64),
                      tie_embeddings=True, dtype="bfloat16")
    model = build_model(cfg)
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    params = jax.eval_shape(jax.vmap(model.init), keys)
    caches = jax.eval_shape(
        lambda: serve.stack_request_caches(model.init_cache(rows, prompt + gen), groups))
    prompts = {"tokens": jax.ShapeDtypeStruct((groups, rows, prompt), jnp.int32)}
    peer_ids = jax.ShapeDtypeStruct((groups,), jnp.int32)
    fleet = jax.jit(serve.make_fleet_generate_fn(model, gen), donate_argnums=(2,))
    text = fleet.lower(*_shapes((params, prompts, caches, peer_ids), one_chip)).compile().as_text()

    stacks = {leaf.shape for leaf in jax.tree.leaves(caches)}
    assert {(groups, 3, rows, prompt + gen, 3, 64), (groups, 3, rows, prompt + gen)} == stacks
    found = _cache_sized_ops_in_loops(text, "repro.decode", stacks)
    assert {opcode for opcode, _ in found} <= {"scatter", "fusion:scatter"}, found
    # K or V, and the position ids: one token of each row of each group
    assert {update for _, update in found} == {(groups, rows, 3, 64), (groups, rows)}
