"""Per-layer numbers from the program's own telemetry (``repro.telemetry``).

Device side: a named scope's device time inside one jitted program is the
union of the intervals of that program's ops which ``telemetry.op_scopes``
maps to the scope, taken inside the program's executions in the window
(``XLA Modules`` events of the trace), averaged over the devices that ran it.
It reads None unless the ops the map names cover at least ``COVERAGE`` of the
program's op time: a map of another compile of the program names other ops.
The map comes from the program's compiled HLO, as the trace's events carry
no scope that ``jax.profiler.ProfileData`` exposes.

Host side: the program's spans (``telemetry.events()``, on
``time.perf_counter`` like the harness's own), clipped to the harness's
``window`` span.

Both read None where the program has no telemetry.
"""
from __future__ import annotations

import sys
import traceback

import trace_reduce

COVERAGE = 0.99


def telemetry():
    """The program's ``repro.telemetry`` module, or None where it has none."""
    try:
        from repro import telemetry as tel
    except ImportError:
        return None
    return tel


def intersect(a: list, b: list) -> list:
    """Intersection of two sorted lists of disjoint intervals."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if lo < hi:
            out.append((lo, hi))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def scope_times(trace: trace_reduce.Trace, module: str, op_scopes: dict):
    """``(seconds per scope, coverage, executions, seconds per unscoped op)``
    of the program ``module`` in the window, averaged over the devices that
    ran it; None where it did not run.  ``op_scopes`` maps op names to scopes
    (None: no scope).  A scope's time includes its control-flow ops, whose
    events enclose their bodies; coverage is the share of the program's op
    time (control flow left out) spent in ops the map names."""
    lo, hi = trace.window
    per_scope: dict[str, float] = {}
    per_unscoped: dict[str, float] = {}
    op_s = mapped_s = 0.0
    devs = 0
    for dev, modules in trace.modules.items():
        runs = trace_reduce.union(
            trace_reduce.clip([(t0, t1) for n, t0, t1 in modules if n == module], lo, hi))
        if not runs:
            continue
        devs += 1
        by_scope: dict = {}
        unscoped: dict = {}
        ops, mapped = [], []
        for name, t0, t1 in trace.ops.get(dev, ()):
            if name in op_scopes:
                by_scope.setdefault(op_scopes[name], []).append((t0, t1))
            if not trace_reduce.is_container(name):
                ops.append((t0, t1))
                if name in op_scopes:
                    mapped.append((t0, t1))
                    if op_scopes[name] is None:
                        unscoped.setdefault(name, []).append((t0, t1))
        op_s += trace_reduce.length(intersect(trace_reduce.union(ops), runs))
        mapped_s += trace_reduce.length(intersect(trace_reduce.union(mapped), runs))
        for scope, ivs in by_scope.items():
            if scope is not None:
                got = trace_reduce.length(intersect(trace_reduce.union(ivs), runs))
                per_scope[scope] = per_scope.get(scope, 0.0) + got
        for name, ivs in unscoped.items():
            got = trace_reduce.length(intersect(trace_reduce.union(ivs), runs))
            per_unscoped[name] = per_unscoped.get(name, 0.0) + got
    if not devs or not op_s:
        return None
    _, calls = trace_reduce.module_time(trace, module)
    return ({s: v / devs for s, v in per_scope.items()}, mapped_s / op_s, calls,
            {n: v / devs for n, v in per_unscoped.items()})


def device_scope_s(run, module: str, program: str, scope: str):
    """``(device seconds of scope, executions)`` of ``module`` (jitted from
    the telemetry program ``program``) in the run's window; None where the
    trace, the program's telemetry or the coverage falls short."""
    if run.trace is None:
        return None
    cache = vars(run).setdefault("scope_times", {})  # one map and sweep per run
    key = (module, program)
    if key not in cache:
        tel = telemetry()
        op_scopes = {}
        if tel is not None:
            try:
                op_scopes = tel.op_scopes(program)
            except Exception:  # a metric that cannot be read is left out, not fatal
                traceback.print_exc()
        got = scope_times(run.trace, module, op_scopes) if op_scopes else None
        if got is not None:
            top = sorted(got[3].items(), key=lambda kv: -kv[1])[:8]
            print(f"scopes: {module} ({program}): op_scopes cover {100 * got[1]:.3f} % of "
                  f"its op time; " + ", ".join(
                      f"{s} {v:.6f} s" for s, v in sorted(got[0].items()))
                  + f"; unscoped ops {sum(got[3].values()):.6f} s, most: "
                  + ", ".join(f"{n} {v:.6f} s" for n, v in top), file=sys.stderr)
        cache[key] = got
    got = cache[key]
    if got is None or got[1] < COVERAGE or not got[2] or scope not in got[0]:
        return None
    return got[0][scope], got[2]


def window_span_s(run, names) -> float | None:
    """Seconds of the program's spans named in ``names`` inside the run's
    window; None where the program records no such span."""
    tel = telemetry()
    if tel is None:
        return None
    windows = [(t0, t1) for n, t0, t1 in run.spans.events if n == "window"]
    spans = [(t0, t1) for n, _, t0, t1 in tel.events() if n in names]
    if not windows or not spans:
        return None
    lo, hi = windows[0]
    return sum(max(0.0, min(t1, hi) - max(t0, lo)) for t0, t1 in spans)
