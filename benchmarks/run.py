"""Benchmark orchestrator.  One function per paper figure + kernel micro-
benches.  Prints ``name,us_per_call,derived`` CSV (see figures.py/kernels.py)
and serializes the consensus-protocol rows to ``BENCH_protocols.json``, the
round-loop driver rows to ``BENCH_roundloop.json``, the adaptive
partner-selection rows to ``BENCH_adaptive.json``, the K-scaling rows to
``BENCH_scaling.json``, the compression Pareto rows to
``BENCH_compression.json``, the sync-vs-async straggler rows to
``BENCH_straggler.json``, the stacked-fleet serving rows to
``BENCH_serving.json``, and the TrainTask real-model rows to
``BENCH_models.json`` so the perf trajectories (spectral gap, consensus
error, wall-clock per round, scan-vs-python speedup, oscillation damping,
sub-quadratic K-scaling, bytes-vs-accuracy compression, async
wall-clock-to-accuracy, stacked-vs-sequential serving throughput, the
personalized-vs-consensus accuracy A/B, and the real-model per-round cost
and loss trajectory) accumulate across PRs.  See benchmarks/README.md for the
file contract.  ``--only`` with an unknown name errors out listing the
registry (a typo used to silently run nothing).

    PYTHONPATH=src python -m benchmarks.run              # reduced (CI) scale
    PYTHONPATH=src python -m benchmarks.run --full       # paper scale
    PYTHONPATH=src python -m benchmarks.run --only fig3,consensus
"""
from __future__ import annotations

import argparse
import json
import sys
import traceback


def _write_rows(path: str, rows: list[dict], what: str) -> None:
    if rows:
        with open(path, "w") as f:
            json.dump({"rows": rows}, f, indent=2)
        print(f"wrote {path} ({len(rows)} rows)", file=sys.stderr)
    else:
        print(f"NOT writing {path}: only {what} benchmarks serialize these "
              "rows and none were selected", file=sys.stderr)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true", help="paper-scale rounds/data")
    ap.add_argument("--only", default="", help="comma-separated subset")
    ap.add_argument("--json-out", default="BENCH_protocols.json",
                    help="where to write the protocol benchmark rows "
                         "('' disables)")
    ap.add_argument("--roundloop-json-out", default="BENCH_roundloop.json",
                    help="where to write the round-loop driver benchmark rows "
                         "('' disables)")
    ap.add_argument("--adaptive-json-out", default="BENCH_adaptive.json",
                    help="where to write the adaptive partner-selection "
                         "benchmark rows ('' disables)")
    ap.add_argument("--scaling-json-out", default="BENCH_scaling.json",
                    help="where to write the K-scaling benchmark rows "
                         "('' disables)")
    ap.add_argument("--compression-json-out", default="BENCH_compression.json",
                    help="where to write the compression Pareto benchmark "
                         "rows ('' disables)")
    ap.add_argument("--straggler-json-out", default="BENCH_straggler.json",
                    help="where to write the sync-vs-async straggler "
                         "benchmark rows ('' disables)")
    ap.add_argument("--serving-json-out", default="BENCH_serving.json",
                    help="where to write the stacked-fleet serving "
                         "benchmark rows ('' disables)")
    ap.add_argument("--models-json-out", default="BENCH_models.json",
                    help="where to write the TrainTask real-model "
                         "benchmark rows ('' disables)")
    args = ap.parse_args(argv)

    from repro.launch import compile_cache

    compile_cache.enable()

    from benchmarks.adaptive import ALL_ADAPTIVE
    from benchmarks.figures import ALL_FIGURES
    from benchmarks.kernels import ALL_KERNELS
    from benchmarks.models import ALL_MODELS
    from benchmarks.peer_axis import ALL_PEER_AXIS
    from benchmarks.protocols import ALL_COMPRESSION, ALL_PROTOCOLS
    from benchmarks.roundloop import ALL_ROUNDLOOP, ALL_SCALING
    from benchmarks.schedules import ALL_SCHEDULES
    from benchmarks.serving import ALL_SERVING
    from benchmarks.straggler import ALL_STRAGGLER

    benches = {**ALL_KERNELS, **ALL_FIGURES, **ALL_SCHEDULES, **ALL_PROTOCOLS,
               **ALL_PEER_AXIS, **ALL_ROUNDLOOP, **ALL_ADAPTIVE,
               **ALL_SCALING, **ALL_COMPRESSION, **ALL_STRAGGLER,
               **ALL_SERVING, **ALL_MODELS}
    only = set(args.only.split(",")) if args.only else None
    if only:
        # a typo'd --only used to silently run NOTHING (and exit 0) — fail
        # loudly with the registry instead
        unknown = sorted(only - set(benches))
        if unknown:
            ap.error(
                f"unknown benchmark name(s) {', '.join(unknown)}; "
                f"known: {', '.join(sorted(benches))}"
            )
    failures = 0
    protocol_rows = []
    roundloop_rows = []
    adaptive_rows = []
    scaling_rows = []
    compression_rows = []
    straggler_rows = []
    serving_rows = []
    models_rows = []
    print("name,us_per_call,derived")
    for name, fn in benches.items():
        if only and name not in only:
            continue
        try:
            out = fn(args.full) if name not in ALL_KERNELS else fn()
            for row_name, us, derived in out:
                print(f"{row_name},{us:.1f},{derived}", flush=True)
            rows = [
                {"name": row_name, "us_per_call": round(us, 1), "derived": derived}
                for row_name, us, derived in out
            ]
            if name in ALL_PROTOCOLS:
                protocol_rows += rows
            if name in ALL_ROUNDLOOP:
                roundloop_rows += rows
            if name in ALL_ADAPTIVE:
                adaptive_rows += rows
            if name in ALL_SCALING:
                scaling_rows += rows
            if name in ALL_COMPRESSION:
                compression_rows += rows
            if name in ALL_STRAGGLER:
                straggler_rows += rows
            if name in ALL_SERVING:
                serving_rows += rows
            if name in ALL_MODELS:
                models_rows += rows
        except Exception:  # noqa: BLE001
            failures += 1
            print(f"{name},ERROR,0", flush=True)
            traceback.print_exc(limit=5, file=sys.stderr)
    if args.json_out:
        _write_rows(args.json_out, protocol_rows, "proto_*")
    if args.roundloop_json_out:
        if any("SKIPPED" in row["name"] for row in roundloop_rows):
            # a <8-device run has no pod rows: writing it would clobber a
            # committed baseline with a file the CI gate can never match
            print(f"NOT writing {args.roundloop_json_out}: pod rows were "
                  "SKIPPED (need 8 devices — set XLA_FLAGS="
                  "--xla_force_host_platform_device_count=8)", file=sys.stderr)
        else:
            _write_rows(args.roundloop_json_out, roundloop_rows, "roundloop")
    if args.adaptive_json_out:
        _write_rows(args.adaptive_json_out, adaptive_rows, "adaptive")
    if args.scaling_json_out:
        if any("SKIPPED" in row["name"] for row in scaling_rows):
            # a <8-device run has no scaling cells: writing it would clobber
            # a committed baseline with a file the CI gate can never match
            print(f"NOT writing {args.scaling_json_out}: scaling rows were "
                  "SKIPPED (need 8 devices — set XLA_FLAGS="
                  "--xla_force_host_platform_device_count=8)", file=sys.stderr)
        else:
            _write_rows(args.scaling_json_out, scaling_rows, "scaling")
    if args.compression_json_out:
        _write_rows(args.compression_json_out, compression_rows, "compression")
    if args.straggler_json_out:
        _write_rows(args.straggler_json_out, straggler_rows, "straggler")
    if args.serving_json_out:
        if any("SKIPPED" in row["name"] for row in serving_rows):
            # a <8-device run has no pod rows: writing it would clobber a
            # committed baseline with a file the CI gate can never match
            print(f"NOT writing {args.serving_json_out}: pod rows were "
                  "SKIPPED (need 8 devices — set XLA_FLAGS="
                  "--xla_force_host_platform_device_count=8)", file=sys.stderr)
        else:
            _write_rows(args.serving_json_out, serving_rows, "serving")
    if args.models_json_out:
        if any("SKIPPED" in row["name"] for row in models_rows):
            # a <8-device run has no pod rows: writing it would clobber a
            # committed baseline with a file the CI gate can never match
            print(f"NOT writing {args.models_json_out}: the rwkv6 pod row was "
                  "SKIPPED (need 8 devices — set XLA_FLAGS="
                  "--xla_force_host_platform_device_count=8)", file=sys.stderr)
        else:
            _write_rows(args.models_json_out, models_rows, "models")
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
