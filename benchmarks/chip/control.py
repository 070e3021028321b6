"""Readings that the limits of ``correct`` are set from, at a cell's own size.

    python benchmarks/chip/control.py --workload <cell> --seeds 1-12 \
        [--control-seeds 1-3] [--faults 1-3] [--fault-names a,b] [--out FILE]

In one process, for each seed: the program's readings (the cell's set-up,
which drives its first calls, compared with the reference: the lower
readings); for each control seed, the readings of each arithmetic the
configuration lists under ``controls`` (the reference in the next precision
below the configuration's, put in the program's place); for each fault seed,
the readings of each fault the workload lists, planted in the program
(``faults.py``).  Before reading, each cell drives ``control.calls`` more
calls at its own load (``workloads/<cell>.json``), so that as many requests
are finished as a run compares.  Prints one JSON line per reading;
the benchmark's own runs never run this.  On the CPU, ``--rehearse-on-cpu``
uses the workload's tiny sizes.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import run as harness  # noqa: E402


def seeds(spec: str) -> list[int]:
    out = []
    for part in spec.split(","):
        if "-" in part:
            a, b = part.split("-")
            out.extend(range(int(a), int(b) + 1))
        elif part:
            out.append(int(part))
    return out


def build(config, workload, seed, rehearse, calls):
    config_module = harness.load_module(HERE / "configs" / f"{workload['config']}.py")
    cell = config_module.Cell(config, workload, seed=seed, rehearse=rehearse, spans=harness.Spans())
    cell.setup()
    for _ in range(calls):
        cell.call()
    cell.free()
    return cell


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--faults", default="", help="seeds to read each listed fault on")
    ap.add_argument("--fault-names", default="",
                    help="comma-separated subset of the workload's faults (default: all)")
    ap.add_argument("--rehearse-on-cpu", action="store_true")
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)

    bench = harness.load_json(harness.ROOT / "BENCHMARK.json")
    entry = {w["name"]: w for w in bench["workloads"]}[args.workload]
    workload = harness.load_json(HERE / "workloads" / f"{args.workload}.json")
    config = harness.load_json(harness.ROOT / {c["name"]: c for c in bench["configs"]}
                               [entry["config"]]["file"])
    import jax

    why_not = harness.device_guard(jax, entry["chips"], args.rehearse_on_cpu)
    if why_not:
        print(f"control.py: {why_not}", file=sys.stderr)
        return 2
    harness.enable_compile_cache(jax)
    import faults

    calls = workload["control"]["calls"]
    out = open(args.out, "a") if args.out else None

    def emit(kind, seed, vals):
        line = json.dumps({"workload": args.workload, "kind": kind, "seed": seed, **vals})
        print(line, flush=True)
        if out:
            out.write(line + "\n")
            out.flush()

    ctl_seeds = set(seeds(args.control_seeds))
    for seed in sorted(set(seeds(args.seeds)) | ctl_seeds):
        cell = build(config, workload, seed, args.rehearse_on_cpu, calls)
        if seed in seeds(args.seeds):
            emit("program", seed, cell.readings())
        if seed in ctl_seeds:
            for c in config["controls"]:
                emit(f"control:{c}", seed, cell.readings(c))
        del cell
    for seed in seeds(args.faults):
        for name in args.fault_names.split(",") if args.fault_names else workload["faults"]:
            with faults.planted(name):
                cell = build(config, workload, seed, args.rehearse_on_cpu, calls)
            emit(f"fault:{name}", seed, cell.readings())
            del cell
    return 0


if __name__ == "__main__":
    sys.exit(main())
