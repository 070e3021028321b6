"""The control of every cell in ``BENCHMARK.json`` comes out not correct
under the cell's limits: the plain reference, put in the program's place and
computed in the first arithmetic its configuration lists under ``controls``
(the step below the configuration's own precision), at the size its
workload gives under ``control.test``.  (At the cells' own sizes on the chip
the same readings set the limits' upper ends: ``control.py``, ``PERF.md``.)

A configuration whose control arithmetic the CPU cannot reproduce names
``off_tpu_separation``: off a TPU the control then has to read that many
times the program's reading on some compared number, the separation step 4
of the limits asks for, and on a TPU (``python -m pytest
benchmarks/chip/tests/test_control.py`` on the chip) it has to cross a limit
as everywhere else."""
import json
from pathlib import Path

import pytest

import control
import run as harness

CHIP = Path(__file__).resolve().parents[1]
BENCH = json.loads((CHIP.parents[1] / "BENCHMARK.json").read_text())
CONFIG_FILES = {c["name"]: c["file"] for c in BENCH["configs"]}


def build(cell_name: str, seed: int):
    workload = harness.load_json(CHIP / "workloads" / f"{cell_name}.json")
    workload["rehearsal"] = workload["control"]["test"]
    config = harness.load_json(CHIP.parents[1] / CONFIG_FILES[workload["config"]])
    cell = control.build(config, workload, seed, True, workload["control"]["calls"])
    return config, workload, cell


@pytest.mark.parametrize("cell_name", sorted(w["name"] for w in BENCH["workloads"]))
@pytest.mark.parametrize("seed", [1, 2, 3])
def test_the_control_fails_a_number(cell_name, seed):
    import jax

    config, workload, cell = build(cell_name, seed)
    vals = cell.readings(config["controls"][0])
    separation = config.get("off_tpu_separation")
    if separation and jax.default_backend() != "tpu":
        program = cell.readings()
        apart = [k for k in vals if vals[k] >= separation * program[k] > 0]
        assert apart, (vals, program)
        return
    failed = [k for k, limit in workload["limits"].items() if vals[k] > limit]
    assert failed, vals
