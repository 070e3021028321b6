"""The readers of the program's telemetry (``scopes.py`` and the six metrics
that use it), on a made-up trace, scope map and span list with known
answers: per-scope device time inside one program's executions, the
coverage gate, host spans clipped to the window, and nothing read where the
program has no telemetry."""
import json
import types
from pathlib import Path

import pytest

import run as harness
import scopes
import trace_reduce as tr

CHIP = Path(__file__).resolve().parents[1]
BENCH = json.loads((CHIP.parents[1] / "BENCHMARK.json").read_text())
METRICS = ("local_device_ms.train", "consensus_device_ms.train", "round_batches_ms.train",
           "eval_drift_ms.train", "prefill_device_ms.serve", "decode_step_device_ms.serve")
DEV = "/device:TPU:0"

DRIVE_SCOPES = {"while.30": None, "while.29": "repro.local", "fusion.1": "repro.local",
                "copy.5": "repro.local", "fusion.2": "repro.consensus", "fusion.3": None}
FLEET_SCOPES = {"fusion.10": "repro.route", "fusion.11": "repro.prefill",
                "while.46": "repro.decode", "fusion.12": "repro.decode",
                "copy.2965": "repro.decode"}


def reader(name):
    return harness.load_module(CHIP / "metrics" / f"{name}.py").read


def drive_ops(a, extra=()):
    """One round program at [a, a + 1): the round loop, the local phase (a
    loop, its fusion and an XLA copy), the mix and an unscoped tail."""
    return [("while.30", a, a + 1.0), ("while.29", a + 0.1, a + 0.7),
            ("fusion.1", a + 0.1, a + 0.7), ("copy.5", a + 0.7, a + 0.75),
            ("fusion.2", a + 0.75, a + 0.95), ("fusion.3", a + 0.95, a + 1.0),
            *((n, a + t0, a + t1) for n, t0, t1 in extra)]


def fake_run(ops, modules, *, counts, info=None, events=(), window=(0.0, 10.0)):
    trace = tr.Trace(ops={DEV: ops}, modules={DEV: modules}, spans=[], window=window)
    spans = types.SimpleNamespace(events=[("window", *window), ("round", 1.0, 2.0)])
    return harness.Run(trace=trace, spans=spans, counts=counts, info=info or {}), list(events)


@pytest.fixture
def program(monkeypatch):
    """Stands in for ``repro.telemetry``: its scope maps and spans."""
    tel = types.SimpleNamespace(maps={"drive": DRIVE_SCOPES, "fleet": FLEET_SCOPES}, spans=[])
    tel.op_scopes = lambda name: tel.maps.get(name, {})
    tel.events = lambda: tel.spans
    monkeypatch.setattr(scopes, "telemetry", lambda: tel)
    return tel


def train_run(extra=()):
    ops = drive_ops(1.0, extra) + drive_ops(4.0, extra) + [("fusion.1", 6.0, 7.0)]
    modules = [("jit_drive", 1.0, 2.0), ("jit_drive", 4.0, 5.0), ("jit_other", 6.0, 7.0)]
    events = [("data.round_batches", "train.batches", -1.0, 0.5),
              ("data.round_batches", None, 2.0, 2.25),
              ("consensus.pairwise_drift", None, 3.0, 3.1),
              ("consensus.consensus_error", None, 3.1, 3.15),
              ("data.round_batches", None, 9.9, 10.5)]
    return fake_run(ops, modules, counts={"rounds": 2, "evals": 2},
                    info={"rounds_per_call": 1}, events=events)


def serve_run():
    ops = [("fusion.10", 1.0, 1.1), ("fusion.11", 1.1, 1.5), ("while.46", 1.5, 3.0),
           ("fusion.12", 1.5, 2.9), ("copy.2965", 2.9, 3.0)]
    return fake_run(ops, [("jit_fleet", 1.0, 3.0)], counts={"tokens": 50, "requests": 10})


def test_device_scopes_of_the_round_program(program):
    run, _ = train_run()
    # per round: the local loop 0.1-0.7 and its copy 0.7-0.75; the mix 0.75-0.95;
    # the other program's fusion.1 at 6-7 is not the round program's
    assert reader("local_device_ms.train")(run) == pytest.approx(650.0)
    assert reader("consensus_device_ms.train")(run) == pytest.approx(200.0)
    assert reader("round_device_ms.train")(run) == pytest.approx(1000.0)


def test_device_scopes_of_the_fleet_program(program):
    run, _ = serve_run()
    assert reader("prefill_device_ms.serve")(run) == pytest.approx(400.0)
    # decode 1.5-3.0 over one call of 50 / 10 - 1 = 4 steps
    assert reader("decode_step_device_ms.serve")(run) == pytest.approx(375.0)


def test_coverage_below_the_gate_reads_nothing(program):
    # an op the map does not name: 0.02 s a call beside 0.9 s of named ops
    run, _ = train_run(extra=[("fusion.99", 0.05, 0.07)])
    times, coverage, calls, unscoped = scopes.scope_times(run.trace, "jit_drive", DRIVE_SCOPES)
    assert calls == 2 and coverage == pytest.approx(1 - 0.02 / 0.92)
    # the unscoped tail, 0.05 s a call; the unnamed op is in no list
    assert unscoped == {"fusion.3": pytest.approx(0.1)}
    assert reader("local_device_ms.train")(run) is None
    assert reader("consensus_device_ms.train")(run) is None
    # an unnamed op of 1e-5 s a call (0.001 %) still reads
    run, _ = train_run(extra=[("fusion.99", 0.05, 0.05001)])
    assert reader("local_device_ms.train")(run) == pytest.approx(650.0)


def test_a_scope_the_program_lacks_reads_nothing(program):
    program.maps["drive"] = {k: (None if v == "repro.consensus" else v)
                             for k, v in DRIVE_SCOPES.items()}
    run, _ = train_run()
    assert reader("consensus_device_ms.train")(run) is None
    assert reader("local_device_ms.train")(run) == pytest.approx(650.0)


def test_host_spans_are_clipped_to_the_window(program):
    run, program.spans = train_run()
    # (0.5 + 0.25 + 0.1) s of batching over 2 rounds; (0.1 + 0.05) s over 2 evals
    assert reader("round_batches_ms.train")(run) == pytest.approx(425.0)
    assert reader("eval_drift_ms.train")(run) == pytest.approx(75.0)


def test_nothing_is_read_without_the_programs_telemetry(monkeypatch):
    monkeypatch.setattr(scopes, "telemetry", lambda: None)
    for make in (train_run, serve_run):
        run, _ = make()
        for name in METRICS:
            assert reader(name)(run) is None
    run, _ = train_run()
    run.trace = None
    assert reader("local_device_ms.train")(run) is None


def test_intersect():
    assert scopes.intersect([(0, 2), (3, 5)], [(1, 4)]) == [(1, 2), (3, 4)]
    assert scopes.intersect([(0, 1)], [(1, 2)]) == []


@pytest.mark.parametrize("name", METRICS)
def test_metric_files_match_their_entries(name):
    entry = {m["name"]: m for m in BENCH["per_layer"]}[name]
    module = harness.load_module(CHIP / "metrics" / f"{name}.py")
    assert (module.UNIT, module.SOURCE, module.LAYER, module.MOVES) == (
        entry["unit"], entry["source"], entry["layer"], entry["moves"])
