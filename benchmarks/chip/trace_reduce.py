"""Reduce a JAX profiler trace (``.xplane.pb``) to the benchmark's numbers.

The trace holds, per accelerator, a plane ``/device:TPU:<n>`` whose line
``XLA Ops`` has one event per executed HLO op (named by its HLO text,
``%fusion.3 = ...``; control-flow ops such as ``while`` enclose the ops of
their body) and whose line ``XLA Modules`` has one event per executed
program (``jit_drive(<hash>)``).  The host plane holds the
harness's spans, ``bench.<name>``; ``bench.window`` bounds the measured
window.  Host and device timestamps share the trace's clock to about a
millisecond (on a TPU v5e the device events read about 1.2 ms early), which
is far below the gaps attributed here.

Every number is averaged over the devices that ran something.
"""
from __future__ import annotations

import dataclasses
import glob
import re
from collections import defaultdict

CONTAINER_OPS = ("while", "conditional", "call")
COLLECTIVE_OPS = ("all-gather", "all-reduce", "reduce-scatter", "collective-permute",
                  "all-to-all", "send", "recv")
_OP_NAME = re.compile(r"^%?([^\s=]+)")
_MODULE_NAME = re.compile(r"^(.*?)(\(\d+\))?$")

Interval = tuple[float, float]


@dataclasses.dataclass
class Trace:
    """Device ops and programs per device, host spans, and the window; all
    times in seconds on the trace's clock."""

    ops: dict[str, list[tuple[str, float, float]]]
    modules: dict[str, list[tuple[str, float, float]]]
    spans: list[tuple[str, float, float]]
    window: Interval

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]


def op_name(hlo_text: str) -> str:
    """``%fusion.3 = f32[...] fusion(...)`` -> ``fusion.3``."""
    m = _OP_NAME.match(hlo_text)
    return m.group(1) if m else hlo_text


def module_name(name: str) -> str:
    """``jit_drive(1234)`` -> ``jit_drive``."""
    return _MODULE_NAME.match(name).group(1)


def is_collective(op: str) -> bool:
    return op.startswith(COLLECTIVE_OPS)


def is_container(op: str) -> bool:
    return op.split(".")[0] in CONTAINER_OPS


def load(path: str) -> Trace:
    """Read one ``.xplane.pb`` file."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    ops: dict[str, list] = {}
    modules: dict[str, list] = {}
    spans: list[tuple[str, float, float]] = []
    for plane in data.planes:
        if plane.name.startswith("/device:") and "CUSTOM" not in plane.name:
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops[plane.name] = [
                        (op_name(ev.name), ev.start_ns * 1e-9,
                         (ev.start_ns + ev.duration_ns) * 1e-9)
                        for ev in line.events
                    ]
                elif line.name == "XLA Modules":
                    modules[plane.name] = [
                        (module_name(ev.name), ev.start_ns * 1e-9,
                         (ev.start_ns + ev.duration_ns) * 1e-9)
                        for ev in line.events
                    ]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("bench."):
                        spans.append((ev.name[len("bench."):], ev.start_ns * 1e-9,
                                      (ev.start_ns + ev.duration_ns) * 1e-9))
    windows = [(t0, t1) for n, t0, t1 in spans if n == "window"]
    if windows:
        window = windows[0]
    else:
        times = [t for evs in ops.values() for _, t0, t1 in evs for t in (t0, t1)]
        window = (min(times), max(times)) if times else (0.0, 0.0)
    spans = [s for s in spans if s[0] != "window"]
    return Trace(ops=ops, modules=modules, spans=spans, window=window)


def load_dir(trace_dir: str) -> Trace:
    """Read the one ``.xplane.pb`` that ``jax.profiler`` wrote under a directory."""
    paths = sorted(glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return load(paths[-1])


def union(intervals) -> list[Interval]:
    """Merge intervals into disjoint, sorted ones."""
    out: list[list[float]] = []
    for t0, t1 in sorted(intervals):
        if t1 <= t0:
            continue
        if out and t0 <= out[-1][1]:
            out[-1][1] = max(out[-1][1], t1)
        else:
            out.append([t0, t1])
    return [(a, b) for a, b in out]


def clip(intervals, lo: float, hi: float) -> list[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals if min(b, hi) > max(a, lo)]


def length(intervals) -> float:
    return sum(b - a for a, b in intervals)


def subtract(a: list[Interval], b: list[Interval]) -> list[Interval]:
    """Parts of the disjoint sorted intervals ``a`` not covered by ``b``."""
    out = []
    b = union(b)
    j = 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def _busy(trace: Trace, device: str) -> list[Interval]:
    return clip(union((t0, t1) for _, t0, t1 in trace.ops[device]), *trace.window)


def _active(trace: Trace) -> list[str]:
    return [d for d, evs in trace.ops.items() if evs]


def busy_s(trace: Trace) -> float:
    """Seconds of the window in which some op ran, averaged over devices."""
    devs = _active(trace)
    if not devs:
        return 0.0
    return sum(length(_busy(trace, d)) for d in devs) / len(devs)


def module_time(trace: Trace, name: str) -> tuple[float, int]:
    """(device seconds of the program ``name`` in the window, executions),
    averaged over devices."""
    devs = [d for d, evs in trace.modules.items() if any(n == name for n, _, _ in evs)]
    if not devs:
        return 0.0, 0
    lo, hi = trace.window
    secs = cnt = 0.0
    for d in devs:
        evs = [(t0, t1) for n, t0, t1 in trace.modules[d] if n == name]
        secs += length(clip(evs, lo, hi))
        cnt += sum(1 for t0, t1 in evs if t1 > lo and t0 < hi)
    return secs / len(devs), round(cnt / len(devs))


def exposed_collective_s(trace: Trace) -> float | None:
    """Seconds in which a collective ran with no other op on that device,
    averaged over devices; None where the trace holds no collective."""
    devs = [d for d in _active(trace) if any(is_collective(n) for n, _, _ in trace.ops[d])]
    if not devs:
        return None
    total = 0.0
    for d in devs:
        coll = union((t0, t1) for n, t0, t1 in trace.ops[d] if is_collective(n))
        other = [(t0, t1) for n, t0, t1 in trace.ops[d]
                 if not is_collective(n) and not is_container(n)]
        total += length(clip(subtract(coll, other), *trace.window))
    return total / len(devs)


def top_ops(trace: Trace, n: int = 10) -> list[list]:
    """The ``n`` ops with the most device time in the window (control-flow
    containers left out, as their bodies are listed), averaged over devices."""
    devs = _active(trace)
    acc: dict[str, float] = defaultdict(float)
    lo, hi = trace.window
    for d in devs:
        for name, t0, t1 in trace.ops[d]:
            if not is_container(name):
                acc[name] += max(0.0, min(t1, hi) - max(t0, lo))
    ranked = sorted(acc.items(), key=lambda kv: -kv[1])[:n]
    return [[name, secs / len(devs)] for name, secs in ranked]


def idle_gaps(trace: Trace, n: int = 10) -> list[list]:
    """Idle device time in the window, by the harness span the host was in
    (the span with the largest overlap of each gap, the innermost of nested
    spans that overlap it alike; ``outside spans`` where none overlaps),
    averaged over devices, largest first."""
    devs = _active(trace)
    acc: dict[str, float] = defaultdict(float)
    spans = sorted(trace.spans, key=lambda s: s[1])
    for d in devs:
        gaps = subtract([trace.window], _busy(trace, d))
        for g0, g1 in gaps:
            best, best_overlap = "outside spans", 0.0
            for name, s0, s1 in spans:
                if s0 >= g1:
                    break
                ov = min(g1, s1) - max(g0, s0)
                if ov > 0 and ov >= best_overlap:  # ties: the inner, later span
                    best, best_overlap = name, ov
            acc[best] += g1 - g0
    ranked = sorted(acc.items(), key=lambda kv: -kv[1])[:n]
    return [[name, secs / max(len(devs), 1)] for name, secs in ranked]
