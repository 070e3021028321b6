"""The data pipeline against its per-step sampler.

``PeerBatcher.round_batches`` draws a call's row indices on the host and
gathers the rows from shards held on the device.  ``OracleBatcher`` below is
the per-step numpy sampler it replaced, kept as the oracle: over consecutive
calls the two give bit-identical batches, so the same rows reach the same
peers in the same order.  The last tests run the training entry point with
the device-made batches on the pod and hierarchical runtimes.
"""
import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.configs.p2pl_mnist import sharded_k8
from repro.data import pipeline, synthetic
from repro.launch.train import run_paper_experiment


class OracleBatcher:
    """The per-step sampler: one numpy gather per peer and step, then stacks."""

    def __init__(self, parts, batch_size, *, seed=0, reshuffle=True):
        self.parts = parts
        self.b = batch_size
        self.reshuffle = reshuffle
        self.rngs = [np.random.default_rng(seed + 7 * k) for k in range(len(parts))]
        self.orders = [rng.permutation(len(p[0])) for rng, p in zip(self.rngs, parts)]
        self.cursors = [0] * len(parts)

    def _next_indices(self, k):
        n = len(self.parts[k][0])
        if n < self.b:
            return self.rngs[k].integers(0, n, size=self.b)
        if self.cursors[k] + self.b > n:
            self.cursors[k] = 0
            if self.reshuffle:
                self.orders[k] = self.rngs[k].permutation(n)
        sel = self.orders[k][self.cursors[k] : self.cursors[k] + self.b]
        self.cursors[k] += self.b
        return sel

    def round_batches(self, local_steps):
        xs, ys = [], []
        for _t in range(local_steps):
            bx, by = [], []
            for k in range(len(self.parts)):
                sel = self._next_indices(k)
                bx.append(self.parts[k][0][sel])
                by.append(self.parts[k][1][sel])
            xs.append(np.stack(bx))
            ys.append(np.stack(by))
        return np.stack(xs), np.stack(ys)


def _parts(sizes, feat=6, seed=0):
    rng = np.random.default_rng(seed)
    return [(rng.normal(size=(n, feat)).astype(np.float32),
             rng.integers(0, 10, n).astype(np.int32)) for n in sizes]


# name: (shard sizes, batch size, reshuffle, steps per call)
CASES = {
    "n_divisible_by_b": ([20, 20, 20], 5, True, 3),
    "n_not_divisible_by_b": ([23, 23, 23], 5, True, 4),
    "n_below_b": ([3, 7, 4], 8, True, 3),
    "no_reshuffle": ([23, 17, 30], 5, False, 4),
    "unequal_shards": ([12, 31, 6, 50], 5, True, 5),
    "long_call_over_many_epochs": ([9, 14, 25], 4, True, 17),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_round_batches_match_the_per_step_sampler(case):
    sizes, b, reshuffle, steps = CASES[case]
    parts = _parts(sizes)
    got = pipeline.PeerBatcher(parts, b, seed=3, reshuffle=reshuffle)
    want = OracleBatcher(parts, b, seed=3, reshuffle=reshuffle)
    for call in range(6):
        gx, gy = got.round_batches(steps)
        wx, wy = want.round_batches(steps)
        assert isinstance(gx, jax.Array) and isinstance(gy, jax.Array)
        assert gx.shape == wx.shape and gy.shape == wy.shape, call
        np.testing.assert_array_equal(np.asarray(gx), wx, err_msg=f"x, call {call}")
        np.testing.assert_array_equal(np.asarray(gy), wy, err_msg=f"y, call {call}")


def test_chunked_call_reshaped_as_the_scan_driver_feeds_it():
    """One call of n·T steps, reshaped to (n, T, K, B, ...), holds the rounds
    that n calls of T steps would give."""
    n, t, b = 3, 4, 5
    parts = _parts([23, 40, 18, 26])
    got = pipeline.PeerBatcher(parts, b, seed=11)
    want = OracleBatcher(parts, b, seed=11)
    for _call in range(3):
        gx, gy = got.round_batches(n * t)
        gx = gx.reshape((n, t) + gx.shape[1:])
        gy = gy.reshape((n, t) + gy.shape[1:])
        for r in range(n):
            wx, wy = want.round_batches(t)
            np.testing.assert_array_equal(np.asarray(gx[r]), wx)
            np.testing.assert_array_equal(np.asarray(gy[r]), wy)


@pytest.mark.parametrize("batch_size", [4, 64])
def test_token_sequence_batcher_matches_the_per_step_sampler(batch_size):
    x, y, _, _ = synthetic.mnist_like(400, 10)
    parts = [(x[i::3][: 40 + 7 * i], y[i::3][: 40 + 7 * i]) for i in range(3)]
    got = pipeline.TokenSequenceBatcher(parts, batch_size, seed=5)
    tok_parts = [(pipeline.images_to_tokens(px), np.asarray(py, np.int32)) for px, py in parts]
    want = OracleBatcher(tok_parts, batch_size, seed=5)
    for _call in range(3):
        gx, gy = got.round_batches(4)
        wx, wy = want.round_batches(4)
        assert gx.dtype == np.int32 and gy.dtype == np.int32
        np.testing.assert_array_equal(np.asarray(gx), wx)
        np.testing.assert_array_equal(np.asarray(gy), wy)


# ----------------------------------------- the training entry on a peer mesh


def test_hierarchical_one_slice_run_matches_vmap(mnist_small):
    """All K peers on one mesh slice (the hierarchical runtime on one device)
    take the device-made batches and match the vmap runtime."""
    exp = sharded_k8(num_peers=4, local_steps=2)
    log_v = run_paper_experiment(exp, rounds=2, data=mnist_small)
    log_h = run_paper_experiment(exp, rounds=2, data=mnist_small, peer_axis="pod",
                                 peers_per_device=4, mix_mode="bridge")
    for attr in ("after_local", "after_consensus"):
        want, got = getattr(log_v, attr), getattr(log_h, attr)
        for group in want:
            assert np.array_equal(np.stack(want[group]), np.stack(got[group])), (attr, group)
    assert log_v.train_loss == log_h.train_loss


FOUR_DEVICE_SCRIPT = textwrap.dedent("""
    import json, sys
    import numpy as np
    from repro.configs.p2pl_mnist import sharded_k8
    from repro.data import synthetic
    from repro.launch.train import run_paper_experiment

    data = synthetic.mnist_like(1000, 200)
    exp = sharded_k8(num_peers=4, local_steps=2)
    runs = {
        "vmap": dict(),
        "pod_scan": dict(peer_axis="pod"),
        "pod_python": dict(peer_axis="pod", driver="python"),
        "hier_2x2": dict(peer_axis="pod", peers_per_device=2, mix_mode="bridge"),
    }
    out = {}
    for name, kw in runs.items():
        log = run_paper_experiment(exp, rounds=2, data=data, **kw)
        out[name] = {"loss": log.train_loss,
                     "acc": {g: np.stack(v).tolist() for g, v in log.after_consensus.items()}}
    print(json.dumps(out))
""")


def test_pod_and_hierarchical_runs_on_four_devices_match_vmap(tmp_path):
    """K=4 on four forced CPU devices: the pod runtime (one peer a device,
    both drivers) and the hierarchical one (two peers a device) reshard the
    single-device batches and match the vmap runtime."""
    script = tmp_path / "four.py"
    script.write_text(FOUR_DEVICE_SCRIPT)
    src = str(Path(__file__).resolve().parents[1] / "src")
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=src,
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run([sys.executable, str(script)], env=env, capture_output=True,
                          text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    for name in ("pod_scan", "pod_python", "hier_2x2"):
        assert out[name] == out["vmap"], name
