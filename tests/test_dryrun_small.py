"""Sharding/dry-run machinery on a small 8-device mesh (subprocess: the
device-count override must not leak into other tests)."""
import json
import subprocess
import sys

import pytest

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax
from repro.launch import dryrun_lib
from repro.launch.mesh import make_test_mesh

mesh = make_test_mesh({shape}, {axes})
res = dryrun_lib.run_case(
    "{arch}", "{shape_name}", mesh,
    multi_pod={multi}, mesh_name="test", with_consensus={multi},
)
print(json.dumps({{
    "ok": res.ok,
    "error": res.error[-2000:] if res.error else "",
    "dominant": res.report.dominant if res.report else "",
    "coll": res.report.coll_wire_bytes_per_chip if res.report else 0,
    "consensus": bool(res.consensus_report),
}}))
"""


def _run(arch, shape_name, shape, axes, multi):
    code = SCRIPT.format(
        arch=arch, shape_name=shape_name, shape=shape, axes=axes,
        n=len(axes), multi=multi,
    )
    p = subprocess.run(
        [sys.executable, "-c", code],
        capture_output=True, text=True, timeout=560,
        # the child runs on the CPU: it must never contend for a chip that
        # this process (or another worker) holds
        env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin:/usr/local/bin", "JAX_PLATFORMS": "cpu"},
    )
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["ok"], out["error"]
    return out


@pytest.mark.slow
def test_single_pod_train_lowers_on_small_mesh():
    out = _run("smollm-135m", "train_4k", (2, 4), ("data", "model"), False)
    assert out["dominant"] in ("compute", "memory", "collective")


@pytest.mark.slow
def test_multi_pod_train_and_consensus_lower():
    out = _run("smollm-135m", "train_4k", (2, 2, 2), ("pod", "data", "model"), True)
    assert out["consensus"], "consensus step must lower on the pod axis"
    assert out["coll"] > 0


@pytest.mark.slow
def test_decode_lowers_on_small_mesh():
    out = _run("rwkv6-7b", "decode_32k", (2, 4), ("data", "model"), False)
    assert out["dominant"] in ("compute", "memory", "collective")
