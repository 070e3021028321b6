"""The trace reduction, on a trace recorded on a TPU v5e
(``record_trace.py``: three rounds of a jitted 8-step matmul scan, a 2 ms
host-only gap and a small second program, under harness spans) and on
made-up intervals."""
from pathlib import Path

import pytest

import trace_reduce as tr

SAMPLE = Path(__file__).parent / "data" / "tpu_v5e_sample.xplane.pb"


@pytest.fixture(scope="module")
def sample():
    return tr.load(str(SAMPLE))


def test_layout_of_the_recorded_trace(sample):
    assert list(sample.ops) == ["/device:TPU:0"]
    assert {n for n, _, _ in sample.modules["/device:TPU:0"]} == {"jit_work", "jit_other"}
    assert [n for n, _, _ in sample.spans] == ["dispatch", "host_batch", "eval"] * 3
    assert sample.window_s > 0


def test_module_time_counts_each_execution(sample):
    secs, calls = tr.module_time(sample, "jit_work")
    assert calls == 3
    assert secs == pytest.approx(3 * 109e-6, rel=0.01)
    assert tr.module_time(sample, "jit_missing") == (0.0, 0)


def test_busy_is_the_union_of_ops_inside_the_window(sample):
    busy = tr.busy_s(sample)
    modules = sum(t1 - t0 for _, t0, t1 in sample.modules["/device:TPU:0"])
    # ops run inside their programs: busy is at most the programs' time
    assert 0.9 * modules < busy <= modules * 1.0001
    assert busy < sample.window_s


def test_idle_gaps_follow_the_host_spans(sample):
    gaps = dict(tr.idle_gaps(sample))
    # the sleep inside bench.host_batch is the longest stretch with no op
    assert max(gaps, key=gaps.get) == "host_batch"
    assert gaps["host_batch"] >= 3 * 0.002
    total = sum(gaps.values())
    assert total == pytest.approx(sample.window_s - tr.busy_s(sample), rel=1e-6)


def test_top_ops_leave_out_control_flow(sample):
    ops = dict(tr.top_ops(sample, 10))
    assert "while" not in ops
    assert max(ops, key=ops.get) == "convolution_tanh_fusion.2"
    assert tr.exposed_collective_s(sample) is None


def test_op_and_module_names():
    assert tr.op_name("%fusion.3 = f32[2]{0} fusion(...)") == "fusion.3"
    assert tr.module_name("jit_drive(123456)") == "jit_drive"
    assert tr.is_collective("all-gather-start.2") and not tr.is_collective("fusion.1")
    assert tr.is_container("while.4") and not tr.is_container("fusion")


def test_exposed_collective_time_on_made_up_intervals():
    ops = [("fusion.1", 0.0, 1.0), ("all-gather.1", 0.5, 2.0), ("fusion.2", 1.5, 3.0),
           ("collective-permute-done", 3.5, 4.0), ("while", 0.0, 4.0)]
    t = tr.Trace(ops={"/device:TPU:0": ops}, modules={}, spans=[("eval", 3.0, 4.0)],
                 window=(0.0, 4.0))
    # all-gather 0.5-2.0 minus compute (0-1, 1.5-3) leaves 1.0-1.5; the permute 3.5-4
    assert tr.exposed_collective_s(t) == pytest.approx(0.5 + 0.5)
    assert tr.busy_s(t) == pytest.approx(4.0)
    t.ops["/device:TPU:0"] = [o for o in ops if o[0] != "while"]
    assert tr.busy_s(t) == pytest.approx(3.5)
    assert tr.idle_gaps(t) == [["eval", pytest.approx(0.5)]]


def test_interval_algebra():
    assert tr.union([(2, 3), (0, 1), (0.5, 1.5)]) == [(0, 1.5), (2, 3)]
    assert tr.subtract([(0, 10)], [(1, 2), (5, 6)]) == [(0, 1), (2, 5), (6, 10)]
    assert tr.clip([(0, 5), (6, 7)], 1, 6.5) == [(1, 5), (6, 6.5)]
