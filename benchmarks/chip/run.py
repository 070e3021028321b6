"""Run one benchmark cell once on the accelerator and print its result line.

    python benchmarks/chip/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>
    JAX_PLATFORMS=cpu python benchmarks/chip/run.py --workload <cell> --seed 1 \
        --seconds 2 --trace 0 --rehearse-on-cpu      # tiny sizes, control flow only

Everything a cell needs is found by name, so a new cell or metric is new
files only:

- ``BENCHMARK.json`` (root of the checkout) lists the cells, their
  configurations and the metrics, and which cells each metric belongs to;
- ``workloads/<cell>.json`` names the configuration and holds the traffic,
  the limits of ``correct``, the faults the cell can have and the tiny sizes
  of its CPU rehearsal and control test;
- ``configs/<config>.json`` holds the configuration as run (with its
  ``controls``, the arithmetic a step below its own), and
  ``configs/<config>.py`` builds it on the program (class ``Cell``: ``setup``,
  ``call``, ``free``, and ``readings(control=None)``, the numbers compared
  with the limits), with its plain reference in
  ``configs/<config>.reference.py``;
- ``metrics/<metric>.py`` reads one number from the run record (``read``).

A run: refuse anything but a TPU (or, with ``--rehearse-on-cpu``, anything
but the CPU); build the cell, warm up exactly its own shapes and drive its
first calls for the correctness check (all of it ``setup_s``); measure for
``--seconds`` with no compilation inside the window (counted and printed);
read the device's peak memory; free the program's state; compare with the
reference; print the compared numbers with their limits on standard error
and, as the last line of standard output, one JSON object.  With
``--trace 1`` the window runs under the profiler and the line carries the
per-layer metrics and a breakdown instead of the end-to-end metrics.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import resource  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path.insert(0, str(HERE))
sys.path.insert(1, str(ROOT / "src"))

COMPILE_EVENTS = (
    "/jax/core/compile/backend_compile_duration",
    "/jax/compilation_cache/cache_hits",
)


def load_module(path: Path):
    """Import a file by path (file names carry the cell's and metric's names)."""
    name = "chipbench_" + path.stem.replace("-", "_").replace(".", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None:
        raise FileNotFoundError(path)
    module = importlib.util.module_from_spec(spec)
    sys.modules[name] = module
    spec.loader.exec_module(module)
    return module


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


class Spans:
    """Host spans of the harness, on the host clock; under the profiler each
    is also written into the trace as ``bench.<name>``."""

    def __init__(self):
        self.events: list[tuple[str, float, float]] = []
        self.recording = False
        self.tracing = False

    @contextlib.contextmanager
    def span(self, name: str):
        annotation = None
        if self.tracing:
            import jax

            annotation = jax.profiler.TraceAnnotation(f"bench.{name}")
            annotation.__enter__()
        t0 = time.perf_counter()
        try:
            yield
        finally:
            t1 = time.perf_counter()
            if annotation is not None:
                annotation.__exit__(None, None, None)
            if self.recording:
                self.events.append((name, t0, t1))

    @contextlib.contextmanager
    def phase(self, name: str):
        """A stretch of set-up, timed on standard error."""
        t0 = time.perf_counter()
        yield
        print(f"set-up phase {name}: {time.perf_counter() - t0:.3f} s", file=sys.stderr)

    def total(self, name: str) -> float:
        return sum(t1 - t0 for n, t0, t1 in self.events if n == name)


class CompileCounter:
    """Counts compilations (backend compiles and persistent-cache loads)."""

    def __init__(self):
        import jax

        self.count = 0
        self.armed = False
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, event, **_):
        if self.armed and event in COMPILE_EVENTS:
            self.count += 1

    def _duration(self, event, duration, **_):
        if self.armed and event in COMPILE_EVENTS:
            self.count += 1


class Run:
    """What the metric readers see of one run."""

    def __init__(self, **kw):
        self.__dict__.update(kw)


def applicable(metric: dict, cell: str, e2e_names: set[str]) -> bool:
    """Whether a metric belongs to a cell: listed under its ``workloads``, or,
    for a per-layer metric without that key, moving an end-to-end metric the
    cell reports."""
    if "workloads" in metric:
        return cell in metric["workloads"]
    if "moves" in metric:
        return metric["moves"] in e2e_names
    return True


def device_guard(jax, chips: int, rehearse: bool) -> str | None:
    """Why this process must not measure, or None."""
    devices = jax.devices()
    dev = devices[0]
    if rehearse:
        if dev.platform != "cpu":
            return "--rehearse-on-cpu needs JAX_PLATFORMS=cpu"
        if len(devices) < chips:
            return (f"the rehearsal of a {chips}-chip cell needs "
                    f"XLA_FLAGS=--xla_force_host_platform_device_count={chips}")
        return None
    if dev.platform != "tpu":
        return (f"no TPU (JAX found {dev.platform}: {dev.device_kind}); refusing to "
                "report a chip run")
    if len(devices) < chips:
        return f"the cell needs {chips} chips, JAX sees {len(devices)}"
    from repro.kernels import lowering

    if lowering.default_interpret():
        return f"Pallas kernels would run in interpret mode ({lowering.ENV_VAR} is set)"
    return None


def enable_compile_cache(jax) -> None:
    """The program's persistent compile cache (``.jax_cache`` in the checkout,
    or ``$JAX_COMPILATION_CACHE_DIR``), keeping every program, however small,
    so that only a checkout's first run compiles."""
    from repro.launch import compile_cache

    compile_cache.enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse-on-cpu", action="store_true",
                    help="tiny sizes on the CPU backend: control flow only, "
                         "prints no metric")
    args = ap.parse_args(argv)

    bench = load_json(ROOT / "BENCHMARK.json")
    entry = {w["name"]: w for w in bench["workloads"]}.get(args.workload)
    if entry is None:
        print(f"run.py: no workload {args.workload!r} in BENCHMARK.json", file=sys.stderr)
        return 2
    workload = load_json(HERE / "workloads" / f"{args.workload}.json")
    config_entry = {c["name"]: c for c in bench["configs"]}[entry["config"]]
    config = load_json(ROOT / config_entry["file"])

    import jax

    why_not = device_guard(jax, entry["chips"], args.rehearse_on_cpu)
    if why_not:
        print(f"run.py: {why_not}", file=sys.stderr)
        return 2

    print(f"set-up phase start-up: {time.perf_counter() - T_START:.3f} s", file=sys.stderr)
    enable_compile_cache(jax)

    spans = Spans()
    counter = CompileCounter()
    config_module = load_module(HERE / "configs" / f"{entry['config']}.py")
    cell = config_module.Cell(config, workload, seed=args.seed,
                              rehearse=args.rehearse_on_cpu, spans=spans)
    cell.setup()
    devices = jax.devices()[: entry["chips"]]
    setup_s = time.perf_counter() - T_START
    print(f"set-up {setup_s:.3f} s (process start to the first timed call)", file=sys.stderr)

    trace_dir = tempfile.mkdtemp(prefix="chipbench_trace_") if args.trace else None
    spans.recording = True
    counts: dict[str, float] = {}
    latencies: list[float] = []
    counter.armed = True
    if trace_dir:
        jax.profiler.start_trace(trace_dir)
        spans.tracing = True
    usage0 = resource.getrusage(resource.RUSAGE_SELF)
    with spans.span("window"):
        t0 = time.perf_counter()
        while True:
            out = cell.call()
            latencies.extend(out.pop("latencies_s", ()))
            for k, v in out.items():
                counts[k] = counts.get(k, 0) + v
            if time.perf_counter() - t0 >= args.seconds:
                break
        window_s = time.perf_counter() - t0
    if trace_dir:
        spans.tracing = False
        jax.profiler.stop_trace()
    counter.armed = False
    spans.recording = False
    print(f"compilations inside the window: {counter.count} (expected 0)", file=sys.stderr)
    usage1 = resource.getrusage(resource.RUSAGE_SELF)
    print(f"host CPU in the window: {usage1.ru_utime - usage0.ru_utime:.3f} s user, "
          f"{usage1.ru_stime - usage0.ru_stime:.3f} s system", file=sys.stderr)
    for name in sorted({n for n, _, _ in spans.events}):
        print(f"span {name}: {spans.total(name):.3f} s in the window", file=sys.stderr)

    peak_bytes = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak_bytes = max(peak_bytes, int(stats.get("peak_bytes_in_use", 0)))
    print(f"peak_bytes_in_use {peak_bytes} (fullest of {len(devices)} device(s))",
          file=sys.stderr)

    trace = None
    if trace_dir:
        import trace_reduce

        trace = trace_reduce.load_dir(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
        print(f"trace: {len(trace.ops)} device(s), "
              f"{sum(len(v) for v in trace.ops.values())} op events, {len(trace.spans)} "
              f"host spans, window {trace.window_s:.3f} s", file=sys.stderr)

    dev = devices[0]
    peak = None
    if not args.rehearse_on_cpu:
        import peaks

        peak = peaks.peak(dev.device_kind)
    run = Run(cell=args.workload, chips=entry["chips"], setup_s=setup_s, window_s=window_s,
              counts=counts, latencies_s=latencies, spans=spans, trace=trace, peak=peak,
              model_flops=cell.model_flops(counts), info=cell.info)

    cell.free()
    vals = cell.readings()
    checks = [(name, float(vals[name]), float(limit))
              for name, limit in workload["limits"].items()]
    correct = bool(checks) and all(
        math.isfinite(v) and v <= limit for _, v, limit in checks
    )
    compiles_ok = counter.count == 0
    if not compiles_ok:
        print("run.py: the window compiled; the run is not correct", file=sys.stderr)
    correct = correct and compiles_ok

    result = {"correct": correct, "attempted": int(counts.get("attempted", 0)),
              "failed": int(counts.get("failed", 0))}
    if args.rehearse_on_cpu:
        result["rehearsal"] = "CPU rehearsal at tiny sizes: no metric is reported"
        result["counts"] = counts
    else:
        e2e = [m for m in bench["end_to_end"] if applicable(m, args.workload, set())]
        e2e_names = {m["name"] for m in e2e}
        wanted = e2e if not args.trace else [
            m for m in bench["per_layer"] if applicable(m, args.workload, e2e_names)
        ]
        metrics = {}
        for m in wanted:
            value = load_module(HERE / "metrics" / f"{m['name']}.py").read(run)
            if value is None:
                if not args.trace:
                    print(f"run.py: end-to-end metric {m['name']} read nothing",
                          file=sys.stderr)
                    return 3
                continue
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        result["metrics"] = metrics
        result["device"] = {"platform": dev.platform, "kind": dev.device_kind,
                            "count": len(jax.devices()), "memory_peak_bytes": peak_bytes}
        if trace is not None:
            import trace_reduce

            result["device"]["busy_s"] = trace_reduce.busy_s(trace)
            result["device"]["window_s"] = trace.window_s
            result["breakdown"] = {"device_ops": trace_reduce.top_ops(trace, 10),
                                   "idle_gaps": trace_reduce.idle_gaps(trace, 10)}
    result["checks"] = {name: {"value": v, "limit": limit} for name, v, limit in checks}
    for name, v, limit in checks:
        print(f"check {name}: {v!r} (limit {limit!r})", file=sys.stderr)
    print(f"correct: {correct}", file=sys.stderr)
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
