"""Production mesh construction (TPU v5e pods; host-device placeholders here).

``make_production_mesh`` is a FUNCTION (never a module constant) so importing
this module never touches jax device state.  The dry-run entry point sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any jax
import; everything else (smoke tests, benches) sees the 1 real CPU device.

Axis semantics:
  pod   — the P2P *peer* axis at production scale: each pod is one paper
          "device"; consensus collectives run only across this axis.
  data  — intra-peer batch/FSDP axis.
  model — intra-peer tensor/expert-parallel axis.

Running sharded locally
-----------------------
The sharded peer-axis runtime (``--peer-axis pod``,
``repro.core.p2p.make_sharded_round_fn``) needs one device per peer.  On a
CPU-only machine, force XLA to expose K host devices BEFORE the first jax
import (an env var, not a runtime switch)::

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python -m repro.launch.train --experiment sharded_k8 --peer-axis pod

The same incantation drives the ``mesh``-marked test suite
(``python -m pytest -m mesh``) and CI's multi-device job; results are
bit-identical to the vmap runtime, so the forced-host mesh is a faithful
stand-in for real hardware.  ``make_peer_mesh`` fails fast with this hint
when too few devices are visible.
"""
from __future__ import annotations

import jax
import numpy as np

# TPU v5e roofline constants (per chip), per the assignment.
PEAK_FLOPS_BF16 = 197e12  # FLOP/s
HBM_BW = 819e9  # B/s
ICI_BW = 50e9  # B/s per link


def _mesh(shape, axes):
    return jax.make_mesh(shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_test_mesh(shape=(2, 2), axes=("data", "model")):
    """Small mesh for CI-scale sharding tests (requires >= prod(shape) devices)."""
    return _mesh(shape, axes)


def make_peer_mesh(num_peers: int, *, axis_name: str = "pod"):
    """1-D mesh for the sharded peer-axis runtime: one device per peer.

    Fails fast (with the CPU incantation) when fewer than ``num_peers``
    devices are visible — the alternative is an opaque XLA sharding error
    deep inside the first jitted round.
    """
    if num_peers < 1:
        raise ValueError("need at least one peer")
    devices = jax.devices()
    if len(devices) < num_peers:
        raise RuntimeError(
            f"peer_axis={axis_name!r} needs one device per peer: "
            f"num_peers={num_peers} but only {len(devices)} jax device(s) "
            "visible. On CPU, relaunch with XLA_FLAGS="
            f"--xla_force_host_platform_device_count={num_peers} set before "
            "the first jax import (see repro/launch/mesh.py)."
        )
    # jax.sharding.Mesh (not jax.make_mesh): it takes an explicit device subset
    return jax.sharding.Mesh(np.asarray(devices[:num_peers]), (axis_name,))


def num_chips(mesh) -> int:
    n = 1
    for v in mesh.shape.values():
        n *= v
    return n
