"""Bring-up smoke: P2P training and fleet serving on one TPU chip.

    python chip_smoke.py              # one chip: train + serve, each vs the host CPU
    python chip_smoke.py --chips 4    # four chips: pod and hierarchical runtimes
                                      # vs the vmap runtime on one chip
    JAX_PLATFORMS=cpu python chip_smoke.py --rehearse-on-cpu   # control flow only

Everything runs in this one process and it starts no other: a chip belongs to
one process at a time.  The phases go through the entry points a user calls
(``repro.launch.train.main``, ``run_paper_experiment``, ``serve_fleet``), at
published widths, with random weights and synthetic data made from fixed
seeds.  Each phase runs again on the host CPU of the same process
(``jax.devices("cpu")``) or on the one-chip vmap runtime, and compares.  Both
sides run under ``jax.default_matmul_precision("highest")``; the stated
tolerances cover the chip's f32 matmul passes and transcendental functions
not being bitwise the CPU's.  Fp32 bit parity between runtimes is a CPU
property, gated by the CPU test suite.

Without a TPU the script exits non-zero and prints no result, unless
``--rehearse-on-cpu`` asks for a rehearsal: the same phases on the CPU at
reduced serving width.  Any failed phase fails the run.  Wall-clock times it
prints include compilation and are not device metrics.  The last line of
standard output is ``{"ok": true, "device": {...}}``.
"""
from __future__ import annotations

import argparse
import collections
import json
import os
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

# the host CPU backend is the reference: keep it next to the accelerator
_platforms = os.environ.get("JAX_PLATFORMS", "")
if _platforms and "cpu" not in _platforms.split(","):
    os.environ["JAX_PLATFORMS"] = _platforms + ",cpu"

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_config, reduced  # noqa: E402
from repro.configs.p2pl_mnist import sharded_k8  # noqa: E402
from repro.kernels import lowering  # noqa: E402
from repro.launch import compile_cache  # noqa: E402
from repro.launch import serve as serve_lib  # noqa: E402
from repro.launch import train as train_lib  # noqa: E402
from repro.models import build_model  # noqa: E402

# Parameters after a few rounds, chip vs reference: |a - b| <= ATOL + RTOL*|b|
# elementwise.  The chip's f32 matmuls at "highest" are multi-pass bf16 and
# its exp/log differ from the CPU's by ulps, so the two agree to ~1e-7
# relative per operation; tens of SGD steps amplify that to ~1e-5.  A wrong
# layout, kernel or collective gives O(1) differences.  On a TPU v5e, the
# training phase with the chip at "default" (one bf16 pass) misses this limit
# about 20x in both models; at "high" (three passes) it passes, so the limit
# tells single-pass bf16 from f32, nothing finer.
PARAM_RTOL, PARAM_ATOL = 1e-3, 1e-5
# Prefill logits of the bf16 smollm-135m: bf16 keeps 8 bits of mantissa
# (relative rounding 2^-9 ~ 2e-3) and 30 layers round activations a few
# hundred times, a random walk of ~sqrt(300) * 2e-3 ~ 3.5e-2 relative.
LOGITS_REL_L2 = 5e-2

SERVE_ARCH = "smollm-135m"


def _numpy_leaves(tree):
    return [np.asarray(x, np.float64) for x in jax.tree.leaves(jax.device_get(tree))]


def check_params(name: str, got, ref) -> None:
    """Elementwise |got - ref| <= ATOL + RTOL*|ref| over every parameter."""
    worst, max_abs = 0.0, 0.0
    for g, r in zip(_numpy_leaves(got), _numpy_leaves(ref), strict=True):
        assert g.shape == r.shape, f"{name}: shape {g.shape} vs {r.shape}"
        assert np.isfinite(g).all(), f"{name}: non-finite parameters"
        diff = np.abs(g - r)
        max_abs = max(max_abs, float(diff.max()))
        worst = max(worst, float((diff / (PARAM_ATOL + PARAM_RTOL * np.abs(r))).max()))
    print(f"  {name}: max|diff| {max_abs:.3e}, worst diff/tolerance {worst:.3f} "
          f"(rtol {PARAM_RTOL:g}, atol {PARAM_ATOL:g})")
    assert worst <= 1.0, f"{name}: parameters differ beyond tolerance"


def check_log(name: str, log) -> None:
    losses = np.asarray(log.train_loss)
    assert losses.size and np.isfinite(losses).all(), f"{name}: loss {losses}"
    acc = np.asarray(log.series("all")).mean(axis=-1)
    print(f"  {name}: loss first {losses[0]:.6f} last {losses[-1]:.6f}; "
          f"accuracy after consensus first {acc[0]:.4f} last {acc[-1]:.4f}")


def report_memory(devices) -> None:
    for d in devices:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            print(f"  {d}: peak_bytes_in_use {stats['peak_bytes_in_use']}")


def phase_train(cpu) -> None:
    """The paper's 2NN (scan driver) and RWKV6 sequential MNIST (vmap
    runtime) through the training CLI, chip vs host CPU."""
    for name, argv in (
        ("noniid_affinity", ["--experiment", "noniid_affinity", "--rounds", "4",
                             "--eval-every", "2"]),
        ("seqmnist_k8", ["--experiment", "seqmnist_k8", "--rounds", "2"]),
    ):
        log, state = train_lib.main(argv)
        with jax.default_device(cpu):
            log_ref, state_ref = train_lib.main(argv)
        check_log(f"{name} chip", log)
        check_log(f"{name} cpu", log_ref)
        check_params(f"{name} params, chip vs cpu", state.params, state_ref.params)


def _prefill_logits(model, num_peers: int, batch: int, prompt_len: int, gen_tokens: int):
    """Last-position prefill logits (K, B, V) of the models and prompts that
    ``serve_fleet`` serves, rebuilt from its seed on the default device."""
    stacked_params, prompts = serve_lib.fleet_inputs(model, num_peers, batch, prompt_len)
    cache = model.init_cache(batch, prompt_len + gen_tokens)

    def one(params, prompt):
        logits, _ = model.prefill(params, prompt, cache)
        return logits[:, -1].astype(jnp.float32)

    return np.asarray(jax.jit(jax.vmap(one))(stacked_params, prompts))


def phase_serve(cpu, *, use_reduced: bool) -> None:
    """Two personalized smollm-135m models served as one stacked call."""
    cfg = get_config(SERVE_ARCH)
    if use_reduced:
        cfg = reduced(cfg)
    model = build_model(cfg)
    kw = dict(num_peers=2, batch=4, prompt_len=16, gen_tokens=8, use_reduced=use_reduced)
    res = serve_lib.serve_fleet(SERVE_ARCH, verbose=True, **kw)
    with jax.default_device(cpu):
        res_ref = serve_lib.serve_fleet(SERVE_ARCH, **kw)
    tokens = np.asarray(res["tokens"])
    assert tokens.shape == (2, 4, 8), tokens.shape
    assert ((tokens >= 0) & (tokens < cfg.vocab_size)).all(), "token out of range"

    shape = (kw["num_peers"], kw["batch"], kw["prompt_len"], kw["gen_tokens"])
    logits = _prefill_logits(model, *shape)
    with jax.default_device(cpu):
        ref = _prefill_logits(model, *shape)
    assert np.isfinite(logits).all(), "non-finite prefill logits"
    rel = float(np.linalg.norm(logits - ref) / np.linalg.norm(ref))
    max_abs = float(np.abs(logits - ref).max())
    print(f"  prefill logits, chip vs cpu: relative L2 {rel:.3e}, max|diff| "
          f"{max_abs:.3e} (tolerance: relative L2 {LOGITS_REL_L2:g})")
    assert rel <= LOGITS_REL_L2, "prefill logits differ beyond tolerance"

    # the first served token is the prefill argmax; where the reference's
    # top two logits are further apart than the largest logit difference,
    # both sides must pick the same token
    top2 = np.sort(ref, axis=-1)[..., -2:]
    clear = (top2[..., 1] - top2[..., 0]) > 2 * max_abs
    first, first_ref = tokens[..., 0], np.asarray(res_ref["tokens"])[..., 0]
    agree = int((first == first_ref)[clear].sum())
    print(f"  first token, chip vs cpu: {agree}/{int(clear.sum())} rows agree where "
          f"the margin is clear ({clear.size} rows)")
    assert agree == int(clear.sum()), "first served token differs from the CPU's"


def phase_four_chips() -> None:
    """The runtimes that span chips, each vs the vmap runtime on one chip."""
    exp = sharded_k8(num_peers=4)
    run = train_lib.run_paper_experiment
    log_pod, pod = run(exp, rounds=3, eval_every=3, peer_axis="pod", return_state=True)
    log_vmap, vmap = run(exp, rounds=3, eval_every=3, peer_axis="vmap", return_state=True)
    check_log("pod K=4 (one peer per chip)", log_pod)
    check_log("vmap K=4 (one chip)", log_vmap)
    check_params("pod vs vmap params", pod.params, vmap.params)

    argv = ["--experiment", "sharded_k8", "--rounds", "3", "--eval-every", "3"]
    log_hier, hier = train_lib.main(argv + ["--peer-axis", "pod", "--peers-per-device", "2"])
    log_vmap, vmap = train_lib.main(argv)
    check_log("hierarchical K=8 (2 peers per chip)", log_hier)
    check_log("vmap K=8 (one chip)", log_vmap)
    check_params("hierarchical vs vmap params", hier.params, vmap.params)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: train and serve on one chip vs the host CPU; 4: only "
                         "the pod and hierarchical runtimes vs vmap on one chip")
    ap.add_argument("--rehearse-on-cpu", action="store_true",
                    help="run the phases on the CPU backend (reduced serving "
                         "width) to rehearse the control flow; not a chip run")
    args = ap.parse_args(argv)

    devices = jax.devices()
    dev = devices[0]
    if args.rehearse_on_cpu:
        if dev.platform != "cpu":
            ap.error("--rehearse-on-cpu needs JAX_PLATFORMS=cpu")
    elif dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {dev.platform}: {dev.device_kind}); "
              "refusing to report a chip run.  Use --rehearse-on-cpu for a CPU "
              "rehearsal.", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} devices, "
              f"JAX sees {len(devices)}", file=sys.stderr)
        return 2
    if dev.platform != "cpu" and lowering.default_interpret():
        print(f"chip_smoke: Pallas kernels would run in interpret mode on "
              f"{dev.platform} ({lowering.ENV_VAR} is set); unset it",
              file=sys.stderr)
        return 2

    cache_dir = compile_cache.enable()
    cache_events = collections.Counter()
    jax.monitoring.register_event_listener(
        lambda event, **_: cache_events.update([event.rsplit("/", 1)[-1]])
    )
    cpu = jax.devices("cpu")[0]
    mode = "REHEARSAL on the CPU, not a chip run" if args.rehearse_on_cpu else "chip run"
    print(f"chip_smoke: {mode}; jax {jax.__version__}; {dev.platform} "
          f"{dev.device_kind} x{len(devices)}; reference {cpu.device_kind}")

    if args.chips == 4:
        phases = [("four chips", phase_four_chips)]
    else:
        phases = [
            ("train", lambda: phase_train(cpu)),
            ("serve", lambda: phase_serve(cpu, use_reduced=args.rehearse_on_cpu)),
        ]
    with jax.default_matmul_precision("highest"):
        for name, phase in phases:
            t0 = time.perf_counter()
            print(f"phase {name}:", flush=True)
            phase()
            report_memory(devices[: args.chips])
            print(f"phase {name}: passed, {time.perf_counter() - t0:.1f} s wall "
                  "clock (includes compile; not a device metric)", flush=True)

    print(f"compile cache {cache_dir}: {cache_events['cache_hits']} hits, "
          f"{cache_events['cache_misses']} entries written")
    result = {"ok": True, "device": {"platform": dev.platform, "kind": dev.device_kind,
                                     "count": len(devices)}}
    if args.rehearse_on_cpu:
        result["rehearsal"] = True
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
