"""The program's telemetry (``repro.telemetry``).

* **Spans** nest per thread (each records its parent), land in a bounded
  ring, and under the profiler appear as ``repro.<name>`` on the host plane
  with the duration the ring holds.
* **Counters** and the one-line ``summary``.
* **Named scopes** reach the compiled HLO of the round program
  (``repro.local``, ``repro.consensus``) and of the fleet program
  (``repro.route``, ``repro.prefill``, ``repro.decode``); ``op_scopes``
  maps their instructions, a scoped loop's unscoped copies included.
* **Trace counts**: ``trace.drive`` counts traces, not calls.
* **Where the work happens**: the data pipeline, eval, the training loop and
  the serving entry points record their spans.
"""
import glob
import os
import subprocess
import sys
import textwrap
import threading
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import telemetry
from repro.configs import get_config, reduced
from repro.configs.p2pl_mnist import noniid_k2
from repro.core import consensus as consensus_lib
from repro.core import p2p
from repro.data import synthetic
from repro.data.pipeline import PeerBatcher
from repro.launch import serve as serve_lib
from repro.launch.train import run_paper_experiment
from repro.models import build_model

K, T, CHUNK = 4, 3, 2


@pytest.fixture(autouse=True)
def clean():
    telemetry.reset()
    yield
    telemetry.reset()


def _parts(k=K, n=20, feat=6):
    rng = np.random.default_rng(0)
    return [(rng.normal(size=(n, feat)).astype(np.float32), rng.integers(0, 4, n))
            for _ in range(k)]


# ---------------------------------------------------------------- spans


def test_spans_nest_and_record_their_parent():
    with telemetry.span("outer") as outer:
        with telemetry.span("inner") as inner:
            pass
        with telemetry.span("inner"):
            pass
    evs = telemetry.events()
    assert [(n, p) for n, p, _, _ in evs] == [
        ("inner", "outer"), ("inner", "outer"), ("outer", None)]
    assert outer.t0 <= inner.t0 <= inner.t1 <= outer.t1
    assert outer.seconds == pytest.approx(evs[-1][3] - evs[-1][2])


def test_span_parents_are_per_thread():
    seen = {}

    def worker():
        with telemetry.span("in_thread") as s:
            seen["parent"] = s.parent

    with telemetry.span("main"):
        t = threading.Thread(target=worker)
        t.start()
        t.join(timeout=10)
    assert not t.is_alive()
    assert seen["parent"] is None


def test_span_records_when_the_block_raises():
    with pytest.raises(ValueError):
        with telemetry.span("failing"):
            raise ValueError("boom")
    with telemetry.span("after"):
        pass
    assert [(n, p) for n, p, _, _ in telemetry.events()] == [
        ("failing", None), ("after", None)]


def test_ring_keeps_the_newest_spans():
    extra = 10
    for i in range(telemetry.RING_SIZE + extra):
        with telemetry.span("s"):
            pass
    evs = telemetry.events()
    assert len(evs) == telemetry.RING_SIZE
    assert all(a[3] <= b[2] for a, b in zip(evs, evs[1:]))


# ------------------------------------------------------- counters, summary


def test_counters_and_summary():
    telemetry.count("data.samples", 40)
    telemetry.count("data.samples", 2)
    telemetry.count("trace.x")
    with telemetry.span("a"):
        pass
    with telemetry.span("a"):
        pass
    assert telemetry.counters() == {"data.samples": 42, "trace.x": 1}
    line = telemetry.summary()
    assert "\n" not in line
    assert line.startswith("telemetry: spans [a 2x ")
    assert "data.samples=42" in line and "trace.x=1" in line
    telemetry.reset()
    assert telemetry.counters() == {} and telemetry.events() == []


def test_counters_lose_no_update_across_threads():
    def add():
        for _ in range(2000):
            telemetry.count("n")

    threads = [threading.Thread(target=add) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=30)
    assert not any(t.is_alive() for t in threads)
    assert telemetry.counters()["n"] == 16000


# ------------------------------------------------------ profiler trace


def test_profiler_trace_holds_the_pipeline_span(tmp_path):
    from jax.profiler import ProfileData

    batcher = PeerBatcher(_parts(), 5, seed=0)
    jax.profiler.start_trace(str(tmp_path))
    try:
        batcher.round_batches(T)
    finally:
        jax.profiler.stop_trace()
    (name, parent, t0, t1), = telemetry.events()
    assert (name, parent) == ("data.round_batches", None)
    path, = glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True)
    found = [ev.duration_ns * 1e-9
             for plane in ProfileData.from_file(path).planes
             if plane.name.startswith("/host:")
             for line in plane.lines for ev in line.events
             if ev.name == "repro.data.round_batches"]
    assert len(found) == 1
    assert abs(found[0] - (t1 - t0)) < 1e-3


# ------------------------------------------------- scopes and programs


def _drive_and_batches():
    def init(key):
        k1, k2 = jax.random.split(key)
        return {"w1": jax.random.normal(k1, (6, 16)), "w2": jax.random.normal(k2, (16, 4))}

    def loss(p, batch):
        x, y = batch
        return jnp.mean(jnp.square(jnp.tanh(x @ p["w1"]) @ p["w2"] - y))

    cfg = p2p.P2PConfig(algorithm="p2pl_affinity", num_peers=K, local_steps=T,
                        consensus_steps=1, lr=0.1, momentum=0.3, eta_d=0.5,
                        topology="ring")
    state = p2p.init_state(jax.random.PRNGKey(0), init, cfg)
    rng = np.random.default_rng(0)

    def batches():
        return (jnp.asarray(rng.normal(size=(CHUNK, T, K, 5, 6)), jnp.float32),
                jnp.asarray(rng.normal(size=(CHUNK, T, K, 5, 4)), jnp.float32))

    return p2p.make_scan_driver(loss, cfg), state, batches


@pytest.fixture(scope="module")
def fleet_parts():
    model = build_model(reduced(get_config("smollm-135m")))
    params = jax.jit(jax.vmap(model.init))(jax.random.split(jax.random.PRNGKey(0), 3))
    g, b, prompt, gen = 2, 2, 8, 5

    def run():
        fleet = jax.jit(serve_lib.make_fleet_generate_fn(model, gen), donate_argnums=(2,))
        caches = serve_lib.stack_request_caches(model.init_cache(b, prompt + gen), g)
        prompts = {"tokens": jnp.zeros((g, b, prompt), jnp.int32)}
        peer_ids = jnp.asarray([0, 2], jnp.int32)
        fleet(params, prompts, caches, peer_ids)
        return fleet.lower(params, prompts, caches, peer_ids).compile().as_text()

    return run


def _whiles(scopes):
    return {n: s for n, s in scopes.items() if n.split(".")[0] == "while"}


def test_round_program_carries_local_and_consensus_scopes():
    drive, state, batches = _drive_and_batches()
    feed = batches()
    text = drive.lower(state, feed).compile().as_text()
    assert 'repro.local' in text and 'repro.consensus' in text
    drive(state, feed)
    scopes = telemetry.op_scopes("drive")
    assert scopes == telemetry.hlo_scopes(text)  # the executed program's compile
    assert {"repro.local", "repro.consensus"} <= set(scopes.values())
    whiles = _whiles(scopes)
    # the round scan holds both phases (no scope); the local scan is local
    assert None in whiles.values() and "repro.local" in whiles.values()
    assert set(whiles.values()) <= {None, "repro.local"}


def test_fleet_program_carries_route_prefill_and_decode_scopes(fleet_parts):
    text = fleet_parts()
    for scope in ("repro.route", "repro.prefill", "repro.decode"):
        assert scope in text
    scopes = telemetry.op_scopes("fleet")
    assert scopes == telemetry.hlo_scopes(text)
    assert {"repro.route", "repro.prefill", "repro.decode"} <= set(scopes.values())
    whiles = _whiles(scopes)
    # every loop of the fleet is a layer scan of prefill or decode, or the
    # decode scan itself
    assert whiles and set(whiles.values()) <= {"repro.prefill", "repro.decode"}
    assert "repro.decode" in whiles.values()


def test_unscoped_ops_inherit_the_scope_of_the_loop_that_runs_them():
    text = """HloModule m

%body (p: (s32[], f32[4])) -> (s32[], f32[4]) {
  %p = (s32[], f32[4]{0}) parameter(0)
  %copy.1 = f32[4]{0} copy(%gte), metadata={op_name="jit(f)/while/body"}
  %fusion.2 = f32[4]{0} fusion(%copy.1), kind=kLoop, calls=%fused, metadata={op_name="jit(f)/repro.decode/transpose(jvp(repro.inner))/mul"}
  ROOT %tuple = (s32[], f32[4]{0}) tuple(%c, %fusion.2)
}

%cond (p: (s32[], f32[4])) -> pred[] {
  ROOT %lt = pred[] compare(%a, %b), direction=LT
}

%branch (q: f32[4]) -> f32[4] {
  ROOT %copy.3 = f32[4]{0} copy(%q)
}

ENTRY %main (x: f32[4]) -> f32[4] {
  %x = f32[4]{0} parameter(0)
  %while.7 = (s32[], f32[4]{0}) while(%t), condition=%cond, body=%body, metadata={op_name="jit(f)/repro.decode/while"}
  %conditional.1 = f32[4]{0} conditional(%i, %x), branch_computations={%branch}, metadata={op_name="jit(f)/repro.route/cond"}
  ROOT %copy.9 = f32[4]{0} copy(%x)
}
"""
    assert telemetry.hlo_scopes(text) == {
        "p": "repro.decode", "copy.1": "repro.decode", "fusion.2": "repro.inner",
        "tuple": "repro.decode", "lt": "repro.decode", "copy.3": "repro.route",
        "x": None, "while.7": "repro.decode", "conditional.1": "repro.route",
        "copy.9": None,
    }


def test_unscoped_custom_calls_take_the_scope_of_their_operands():
    text = """HloModule m

%body (p: (s32[], bf16[8,4])) -> (s32[], bf16[8,4]) {
  %p = (s32[], bf16[8,4]{1,0}) parameter(0)
  %gte = bf16[8,4]{1,0} get-tuple-element(%p), index=1
  %sizes = s32[2]{0} fusion(%gte), kind=kLoop, calls=%f1, metadata={op_name="jit(f)/repro.prefill/repro.moe/reduce_sum"}
  %meta = (s32[2]{0}, s32[1]{0}) custom-call(%sizes), custom_call_target="tpu_custom_call", metadata={op_name="ragged-dot-metadata"}
  %gte.1 = s32[2]{0} get-tuple-element(%meta), index=0
  %rows = bf16[8,4]{1,0} fusion(%gte), kind=kLoop, calls=%f2, metadata={op_name="jit(f)/repro.prefill/repro.moe/gather"}
  %ragged-dot-none = bf16[8,4]{1,0} custom-call(%gte.1, %rows, %gte), custom_call_target="tpu_custom_call"
  %other = bf16[8,4]{1,0} custom-call(%gte), custom_call_target="tpu_custom_call"
  %copy.2 = bf16[8,4]{1,0} copy(%rows)
  ROOT %tuple = (s32[], bf16[8,4]{1,0}) tuple(%c, %ragged-dot-none)
}

ENTRY %main (x: bf16[8,4]) -> bf16[8,4] {
  %x = bf16[8,4]{1,0} parameter(0)
  ROOT %while.3 = (s32[], bf16[8,4]{1,0}) while(%t), condition=%cond, body=%body, metadata={op_name="jit(f)/repro.prefill/while"}
}
"""
    scopes = telemetry.hlo_scopes(text)
    # a custom call takes its first scoped operand's scope
    assert scopes["meta"] == scopes["ragged-dot-none"] == "repro.moe"
    # without one, and for any other op, the loop's
    assert scopes["other"] == scopes["copy.2"] == scopes["gte.1"] == "repro.prefill"


def test_a_program_traces_once_for_many_same_shape_calls():
    drive, state, batches = _drive_and_batches()
    for _ in range(3):
        _, state, _ = drive(state, batches())
    assert telemetry.counters()["trace.drive"] == 1
    assert drive._cache_size() == 1
    telemetry.op_scopes("drive")  # compiles again without counting a trace
    assert telemetry.counters()["trace.drive"] == 1
    assert telemetry.op_scopes("no_such_program") == {}


STALE_CACHE_SCRIPT = textwrap.dedent("""
    import contextlib, sys
    import jax, jax.numpy as jnp
    jax.config.update("jax_compilation_cache_dir", sys.argv[2])
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    if sys.argv[1] == "unscoped":
        jax.named_scope = lambda name: contextlib.nullcontext()
    from repro import telemetry
    from repro.core import p2p
    cfg = p2p.P2PConfig(algorithm="p2pl", num_peers=2, local_steps=2,
                        consensus_steps=1, lr=0.1, topology="complete")
    init = lambda key: {"w": jax.random.normal(key, (3, 2))}
    loss = lambda p, b: jnp.mean(jnp.square(b[0] @ p["w"] - b[1]))
    drive = p2p.make_scan_driver(loss, cfg)
    state = p2p.init_state(jax.random.PRNGKey(0), init, cfg)
    feed = (jnp.ones((1, 2, 2, 4, 3)), jnp.ones((1, 2, 2, 4, 2)))
    drive(state, feed)
    print(sorted({str(s) for s in telemetry.op_scopes("drive").values()}))
""")


def test_op_scopes_read_the_scopes_when_the_cache_holds_an_unscoped_compile(tmp_path):
    """A persistent cache keys programs without metadata: the round program
    run from an entry that a scope-less compile wrote still maps its scopes."""
    script = tmp_path / "stale.py"
    script.write_text(STALE_CACHE_SCRIPT)
    src = str(Path(__file__).resolve().parents[1] / "src")
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=src)
    out = {}
    for phase in ("unscoped", "scoped"):
        proc = subprocess.run([sys.executable, str(script), phase, str(tmp_path / "cache")],
                              env=env, capture_output=True, text=True, timeout=300)
        assert proc.returncode == 0, proc.stderr[-3000:]
        out[phase] = proc.stdout.strip().splitlines()[-1]
    assert out["unscoped"] == "['None']"
    assert out["scoped"] == "['None', 'repro.consensus', 'repro.local']"


# ---------------------------------------------- spans where the work is


def test_pipeline_counts_samples():
    batcher = PeerBatcher(_parts(), 5, seed=0)
    batcher.round_batches(T)
    batcher.round_batches(T)
    assert telemetry.counters()["data.samples"] == 2 * T * K * 5
    assert [n for n, _, _, _ in telemetry.events()] == ["data.round_batches"] * 2


def test_pipeline_sends_the_shards_once_then_only_indices():
    parts = _parts()
    shard_bytes = sum(x.nbytes + y.astype(np.int32).nbytes for x, y in parts)
    index_bytes = T * K * 5 * 4
    batcher = PeerBatcher(parts, 5, seed=0)
    sent = []
    for _ in range(3):
        batcher.round_batches(T)
        sent.append(telemetry.counters()["data.h2d_bytes"])
    assert sent == [shard_bytes + index_bytes * i for i in (1, 2, 3)]
    assert telemetry.counters()["data.samples"] == 3 * T * K * 5
    assert [n for n, _, _, _ in telemetry.events()] == ["data.round_batches"] * 3


def test_drift_and_error_record_spans():
    stacked = {"w": jnp.arange(12.0).reshape(3, 4)}
    float(consensus_lib.pairwise_drift(stacked))
    float(consensus_lib.consensus_error(stacked))
    assert [n for n, _, _, _ in telemetry.events()] == [
        "consensus.pairwise_drift", "consensus.consensus_error"]


@pytest.mark.parametrize("driver", ["scan", "python"])
def test_training_loop_spans(driver):
    data = synthetic.mnist_like(600, 200)
    run_paper_experiment(noniid_k2(algorithm="p2pl_affinity", local_steps=2),
                         rounds=2, data=data, driver=driver)
    evs = telemetry.events()
    parents = {(n, p) for n, p, _, _ in evs}
    assert ("data.round_batches", "train.batches") in parents
    assert ("consensus.pairwise_drift", "train.eval") in parents
    names = [n for n, p, _, _ in evs if p is None]
    per_round = ["train.batches", "train.dispatch", "train.eval"]
    assert names == per_round * 2
    if driver == "scan":
        assert telemetry.counters()["trace.drive"] == 1


@pytest.mark.parametrize("decode_impl", ["scan", "python"])
def test_serve_batch_times_are_its_spans(decode_impl):
    out = serve_lib.serve_batch(batch=2, prompt_len=4, gen_tokens=3, decode_impl=decode_impl)
    spans = {n: t1 - t0 for n, _, t0, t1 in telemetry.events()}
    assert set(spans) == {"serve.prefill", "serve.decode"}
    assert out["prefill_s"] == spans["serve.prefill"]
    assert out["decode_s_per_token"] == spans["serve.decode"] / 2


def test_serve_fleet_time_is_its_span():
    out = serve_lib.serve_fleet(num_peers=2, batch=2, prompt_len=4, gen_tokens=3)
    (name, _, t0, t1), = telemetry.events()
    assert name == "serve.fleet"
    assert out["serve_s"] == t1 - t0
    assert telemetry.counters()["trace.fleet"] == 1
