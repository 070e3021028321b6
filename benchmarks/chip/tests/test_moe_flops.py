"""The expert layer's and the DeepSeek-V2-Lite request's counts
(``moe_flops.py``) against the shapes, against XLA's own count, and the
readers of ``moe_device_ms.serve``, ``mla_device_ms.serve`` and
``moe_roofline.serve`` on a made-up trace."""
import json
import types
from pathlib import Path

import pytest

import moe_flops
import peaks
import run as harness
import scopes
import trace_reduce as tr

CHIP = Path(__file__).resolve().parents[1]
CFG = json.loads((CHIP / "configs" / "deepseek-v2-lite-fleet.json").read_text())
BENCH = json.loads((CHIP.parents[1] / "BENCHMARK.json").read_text())
V5E = peaks.peak("TPU v5 lite")
D, F = 2048, 1408
EXPERT = 3 * D * F  # parameters of one routed expert


def test_expected_load_of_the_held_experts():
    # 6 of 64 picked, 8 held: 0.75 passes a token; 8 tokens reach 4.36 of 8
    assert moe_flops.expected_routed_passes(CFG) == 0.75
    assert moe_flops.expected_experts_hit(CFG, 1) == pytest.approx(0.75)
    assert moe_flops.expected_experts_hit(CFG, 8) == pytest.approx(4.36, abs=0.005)
    assert moe_flops.expected_experts_hit(CFG, 8160) == pytest.approx(8.0)


def test_a_decode_pass_by_hand():
    n = 8
    flops = 2 * n * D * 64 + 2 * n * 2 * EXPERT + 2 * n * 0.75 * EXPERT
    assert moe_flops.moe_pass_flops(CFG, n) == pytest.approx(flops)
    hit = 8 * (1 - (58 / 64) ** n)
    nbytes = 2 * 2 * EXPERT + 4 * D * 64 + 2 * hit * EXPERT
    assert moe_flops.moe_pass_bytes(CFG, n) == pytest.approx(nbytes)
    # decode is bound by the weight bytes, a prefill pass by the FLOPs
    assert moe_flops.moe_pass_roofline_s(CFG, n, V5E) == pytest.approx(nbytes / 819e9)
    big = 8 * 1020
    assert moe_flops.moe_pass_roofline_s(CFG, big, V5E) == pytest.approx(
        moe_flops.moe_pass_flops(CFG, big) / 197e12)


def test_a_call_makes_every_groups_prefill_and_decode_passes():
    passes = moe_flops.fleet_call_moe_passes(CFG, groups=8, batch=8, prompt_len=1020,
                                             gen_tokens=129)
    assert passes == [(8160, 32), (8, 32 * 128)]


def test_request_flops_against_xla_cost_analysis():
    """The leading dense layer at the published widths and the head over the
    slice, prefill of T tokens on the CPU: XLA counts the full T x T
    attention square where the model needs the causal half, and a few
    elementwise FLOPs more."""
    import jax
    import jax.numpy as jnp

    cell = harness.load_module(CHIP / "configs" / "deepseek-v2-lite-fleet.py")
    from repro.models import build_model

    cfg = dict(CFG, num_hidden_layers=1, torch_dtype="float32")
    dense_layer = cell.model_config(dict(cfg, num_hidden_layers=2)).replace(
        family="dense", moe=None, num_layers=1, remat=False)
    model = build_model(dense_layer)
    t = 128
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    cache = jax.eval_shape(lambda: model.init_cache(1, t))
    tokens = {"tokens": jax.ShapeDtypeStruct((1, t), jnp.int32)}
    cost = jax.jit(model.prefill).lower(params, tokens, cache).compile().cost_analysis()
    if isinstance(cost, list):
        cost = cost[0]
    ours = moe_flops.request_flops(cfg, t, 1)
    square_minus_causal = 2 * 16 * (192 + 128) * (t * t - t * (t + 1) // 2)
    assert cost["flops"] == pytest.approx(ours + square_minus_causal, rel=0.03)


# -- the readers --------------------------------------------------------------

DEV = "/device:TPU:0"
FLEET_SCOPES = {"fusion.1": "repro.route", "fusion.2": "repro.mla", "fusion.3": "repro.moe",
                "fusion.4": "repro.prefill", "while.5": "repro.decode",
                "fusion.6": "repro.mla", "fusion.7": "repro.moe"}


@pytest.fixture
def program(monkeypatch):
    tel = types.SimpleNamespace(op_scopes=lambda name: FLEET_SCOPES if name == "fleet" else {},
                                events=lambda: [])
    monkeypatch.setattr(scopes, "telemetry", lambda: tel)


def serve_run(passes):
    # two calls of 2 s: route 0.1, prefill's MLA 0.2 and experts 0.3, the rest
    # of prefill 0.1, decode 1.3 s of which MLA 0.4 and experts 0.6
    ops, modules = [], []
    for a in (1.0, 4.0):
        ops += [("fusion.1", a, a + 0.1), ("fusion.2", a + 0.1, a + 0.3),
                ("fusion.3", a + 0.3, a + 0.6), ("fusion.4", a + 0.6, a + 0.7),
                ("while.5", a + 0.7, a + 2.0), ("fusion.6", a + 0.7, a + 1.1),
                ("fusion.7", a + 1.1, a + 1.7)]
        modules.append(("jit_fleet", a, a + 2.0))
    trace = tr.Trace(ops={DEV: ops}, modules={DEV: modules}, spans=[], window=(0.0, 10.0))
    return harness.Run(trace=trace, spans=types.SimpleNamespace(events=[]),
                       counts={"tokens": 100, "requests": 10}, peak=V5E,
                       info={"config": CFG, "moe_passes_per_call": passes})


def reader(name):
    return harness.load_module(CHIP / "metrics" / f"{name}.py").read


def test_moe_and_mla_device_time_per_call(program):
    run = serve_run([(8, 1)])
    assert reader("moe_device_ms.serve")(run) == pytest.approx(900.0)
    assert reader("mla_device_ms.serve")(run) == pytest.approx(600.0)


def test_the_roofline_share_is_the_passes_least_time_over_the_experts_time(program):
    passes = [(8160, 32), (8, 4096)]
    least = sum(c * moe_flops.moe_pass_roofline_s(CFG, n, V5E) for n, c in passes)
    # 0.9 s of expert ops a call
    assert reader("moe_roofline.serve")(serve_run(passes)) == pytest.approx(100 * least / 0.9)


def test_nothing_is_read_without_the_programs_scopes(monkeypatch):
    monkeypatch.setattr(scopes, "telemetry", lambda: None)
    run = serve_run([(8, 1)])
    for name in ("moe_device_ms.serve", "mla_device_ms.serve", "moe_roofline.serve"):
        assert reader(name)(run) is None


@pytest.mark.parametrize("name", ["moe_device_ms.serve", "mla_device_ms.serve",
                                  "moe_roofline.serve"])
def test_metric_files_match_their_entries(name):
    entry = {m["name"]: m for m in BENCH["per_layer"]}[name]
    module = harness.load_module(CHIP / "metrics" / f"{name}.py")
    assert (module.UNIT, module.SOURCE, module.LAYER, module.MOVES) == (
        entry["unit"], entry["source"], entry["layer"], entry["moves"])
