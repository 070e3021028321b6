"""Whole harness runs on the CPU at the workloads' tiny rehearsal sizes, each
in a process of its own: every cell rehearses and comes out correct; every
fault a cell can have, planted underneath the unchanged harness, makes it
come out not correct; and the measurement path refuses to run without a TPU
or without the program."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

CHIP = Path(__file__).resolve().parents[1]
ROOT = CHIP.parents[1]
CHIPS = {w["name"]: w["chips"]
         for w in json.loads((ROOT / "BENCHMARK.json").read_text())["workloads"]}
WORKLOADS = {name: json.loads((CHIP / "workloads" / f"{name}.json").read_text())
             for name in CHIPS}


def run(args, *, chips=1, cwd=ROOT, script=CHIP / "run.py", rehearse=True):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={chips}"
    cmd = [sys.executable, str(script), *args]
    if rehearse:
        cmd.append("--rehearse-on-cpu")
    return subprocess.run(cmd, cwd=cwd, env=env, capture_output=True, text=True, timeout=600)


def last_line(proc):
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("cell", sorted(WORKLOADS))
def test_rehearsal_is_correct_and_reports_no_device_metric(cell):
    proc = run(["--workload", cell, "--seed", str(2**31 + 11), "--seconds", "1",
                "--trace", "1"], chips=CHIPS[cell])
    out = last_line(proc)
    assert out["correct"] is True, proc.stderr[-3000:]
    assert "metrics" not in out and "device" not in out
    assert out["attempted"] > 0
    assert "compilations inside the window: 0" in proc.stderr
    assert list(out)[-1] == "checks"


@pytest.mark.parametrize("cell,fault", sorted(
    (cell, f) for cell, w in WORKLOADS.items() for f in w["faults"]))
def test_a_planted_fault_comes_out_not_correct(cell, fault):
    proc = run([fault, "--workload", cell, "--seed", "7", "--seconds", "0.5", "--trace", "0"],
               chips=CHIPS[cell], script=CHIP / "tests" / "run_with_fault.py")
    assert last_line(proc)["correct"] is False, proc.stderr[-3000:]


def test_without_a_tpu_the_measurement_path_exits_nonzero():
    proc = run(["--workload", "p2pl-2nn.iid-k100-t60", "--seed", "1", "--seconds", "1",
                "--trace", "0"], rehearse=False)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "no TPU" in proc.stderr


def test_the_benchmark_alone_without_the_program_exits_nonzero(tmp_path):
    shutil.copytree(CHIP, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    proc = run(["--workload", "p2pl-2nn.iid-k100-t60", "--seed", "1", "--seconds", "1",
                "--trace", "0"], cwd=tmp_path, script=tmp_path / "benchmarks/chip/run.py",
               rehearse=False)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
