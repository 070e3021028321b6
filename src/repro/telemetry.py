"""The program's own telemetry: host spans, counters and named-scope read-out.

Always on; nothing turns it off.

- ``span(name)`` times a stretch of host code on ``time.perf_counter`` into a
  bounded in-memory ring (``events()``), with the enclosing span of the same
  thread as its parent, and enters ``jax.profiler.TraceAnnotation
  ("repro.<name>")``, so that under the profiler the span lands on the host
  plane of the trace, on the device ops' clock.  Spans belong in code that
  runs eagerly: inside traced code they would time the trace.
- ``count(name, n)`` adds to a named counter (``counters()``).
- ``program(name, donate_argnums=...)`` marks a function that is jitted as
  one program: each trace of its body adds one to ``trace.<name>`` (a
  re-trace is a compile) and records the arguments' shapes, from which
  ``op_scopes(name)`` compiles the program again and maps each of its
  instructions to the innermost ``repro.*`` named scope, read from the HLO's
  ``metadata={op_name=...}``.  An instruction without a scope of its own
  takes the scope of the innermost scoped ``while``, ``call`` or
  ``conditional`` that runs it, so the copies XLA adds inside a scoped loop
  count with that loop; a custom call without one (a kernel the compiler
  put in an op's place, such as the TPU's grouped product) first takes the
  scope of its first operand that has one, so it counts with the op whose
  data it consumes.

Named scopes on the device side (``jax.named_scope``, or ``scoped(scope)``
as a decorator): ``repro.local``, ``repro.consensus`` (``core/p2p.py``),
``repro.eval`` (``p2p.stratified_accuracy``), ``repro.route``,
``repro.prefill`` and ``repro.decode`` (``launch/serve.py``,
``launch/steps.py``), ``repro.mla`` (``models/attention.py``) and
``repro.moe`` (``models/moe.py``).  They add metadata only.
"""
from __future__ import annotations

import collections
import dataclasses
import functools
import re
import threading
import time
from typing import Callable

import jax

RING_SIZE = 65536  # spans kept; the oldest fall out first
SCOPE_PREFIX = "repro."
CONTAINER_OPS = ("while", "call", "conditional")


class _Recorder(threading.local):
    """The enclosing spans of one thread."""

    def __init__(self):
        self.stack: list[Span] = []


_ring: collections.deque = collections.deque(maxlen=RING_SIZE)
_counts: dict[str, int] = {}
_counts_lock = threading.Lock()
_open = _Recorder()
_programs: dict[str, "_Program"] = {}  # by name, the last traced
_replaying = threading.local()


class Span:
    """One timed stretch of host code: ``name``, ``parent`` (the enclosing
    span's name, or None), ``t0`` and ``t1`` in ``time.perf_counter``
    seconds; ``seconds`` once it has ended."""

    __slots__ = ("name", "parent", "t0", "t1", "_annotation")

    def __init__(self, name: str):
        self.name = name

    def __enter__(self) -> "Span":
        stack = _open.stack
        self.parent = stack[-1].name if stack else None
        stack.append(self)
        self._annotation = jax.profiler.TraceAnnotation(SCOPE_PREFIX + self.name)
        self._annotation.__enter__()
        self.t1 = None
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        self.t1 = time.perf_counter()
        self._annotation.__exit__(None, None, None)
        _open.stack.pop()
        _ring.append(self)

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0


def span(name: str) -> Span:
    """Context manager timing its block as the span ``name``."""
    return Span(name)


def scoped(scope: str) -> Callable:
    """Decorator: trace the function inside ``jax.named_scope(scope)``, a
    fresh scope object per call (one shared object is not re-entrant)."""

    def wrap(fn: Callable) -> Callable:
        @functools.wraps(fn)
        def inner(*args, **kwargs):
            with jax.named_scope(scope):
                return fn(*args, **kwargs)

        return inner

    return wrap


def count(name: str, n: int = 1) -> None:
    """Add ``n`` to the counter ``name``."""
    with _counts_lock:
        _counts[name] = _counts.get(name, 0) + n


def events() -> list[tuple[str, str | None, float, float]]:
    """The recorded spans, oldest first: ``(name, parent, t0, t1)``."""
    return [(s.name, s.parent, s.t0, s.t1) for s in list(_ring)]


def counters() -> dict[str, int]:
    with _counts_lock:
        return dict(_counts)


def summary() -> str:
    """One line: per span name its count and total seconds, then the counters."""
    totals: dict[str, list] = {}
    for name, _, t0, t1 in events():
        acc = totals.setdefault(name, [0, 0.0])
        acc[0] += 1
        acc[1] += t1 - t0
    spans = ", ".join(f"{n} {c}x {s:.3f}s" for n, (c, s) in sorted(totals.items()))
    counts = ", ".join(f"{n}={v}" for n, v in sorted(counters().items()))
    return f"telemetry: spans [{spans}] counters [{counts}]"


def reset() -> None:
    """Forget every span, counter and traced program."""
    _ring.clear()
    with _counts_lock:
        _counts.clear()
    _programs.clear()


# ---------------------------------------------------------------------------
# Programs: trace counts and the map from instruction to named scope
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class _Program:
    fn: Callable  # the traced function, as jitted
    donate_argnums: tuple[int, ...]
    shapes: tuple  # of the arguments of its last trace
    scopes: dict | None = None  # op_scopes, once computed


def _shape_of(x) -> jax.ShapeDtypeStruct:
    aval = jax.typeof(x)
    return jax.ShapeDtypeStruct(aval.shape, aval.dtype, weak_type=aval.weak_type)


def program(name: str, *, donate_argnums: tuple[int, ...] = ()) -> Callable:
    """Decorator for a function the caller jits as the program ``name`` (with
    these ``donate_argnums``).  The function's name is kept, so the jitted
    module is still ``jit_<function name>``."""

    def wrap(fn: Callable) -> Callable:
        @functools.wraps(fn)
        def traced(*args):
            if not getattr(_replaying, "on", False):
                count(f"trace.{name}")
                shapes = jax.tree.map(_shape_of, args)
                _programs[name] = _Program(traced, tuple(donate_argnums), shapes)
            return fn(*args)

        return traced

    return wrap


_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([^\s(]+)")
_INSTRUCTION = re.compile(r"^\s+(?:ROOT\s+)?%?([^\s=]+)\s*=\s*(.*)$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_SCOPE = re.compile(re.escape(SCOPE_PREFIX) + r"\w+")
_CONTAINER = re.compile(r"\s(?:" + "|".join(CONTAINER_OPS) + r")\(")
_CUSTOM_CALL = re.compile(r"\scustom-call\(([^)]*)\)")
_OPERAND = re.compile(r"%([\w.\-]+)")
_CALLEES = re.compile(
    r"\b(?:body|condition|to_apply|true_computation|false_computation)=%?([\w.\-]+)"
    r"|\bbranch_computations=\{([^}]*)\}"
)


def hlo_scopes(hlo_text: str) -> dict[str, str | None]:
    """Every instruction of an HLO module's text -> its innermost
    ``repro.*`` scope, or None (see the module docstring)."""
    own: dict[str, str | None] = {}  # instruction -> scope of its own op_name
    home: dict[str, str] = {}  # instruction -> its computation
    caller: dict[str, str] = {}  # computation -> the container instruction running it
    operands: dict[str, list[str]] = {}  # custom call -> its operands
    comp = None
    for line in hlo_text.splitlines():
        if line.endswith("{") and not line[:1].isspace():
            comp = _COMPUTATION.match(line).group(1)
            continue
        m = _INSTRUCTION.match(line)
        if m is None or comp is None:
            continue
        name, rhs = m.groups()
        home[name] = comp
        op = _OP_NAME.search(rhs)
        scopes = _SCOPE.findall(op.group(1)) if op else []
        own[name] = scopes[-1] if scopes else None
        call = _CUSTOM_CALL.search(rhs)
        if call is not None:
            operands[name] = _OPERAND.findall(call.group(1))
        if _CONTAINER.search(rhs):
            for single, branches in _CALLEES.findall(rhs):
                for callee in [single] if single else branches.split(","):
                    caller.setdefault(callee.strip().lstrip("%"), name)

    resolved: dict[str, str | None] = {}

    def scope_of(instr: str) -> str | None:
        if instr in resolved:
            return resolved[instr]
        scope = own[instr]
        if scope is None:
            scope = next((own[o] for o in operands.get(instr, ())
                          if own.get(o) is not None and home[o] == home[instr]), None)
        if scope is None:
            parent = caller.get(home[instr])
            scope = scope_of(parent) if parent is not None else None
        resolved[instr] = scope
        return scope

    return {instr: scope_of(instr) for instr in own}


def _compile(program: _Program) -> str:
    """The compiled HLO text of a recorded program, with its scopes.

    A persistent compile cache keys programs without their metadata, so the
    executable the program ran may come from an entry compiled without the
    scopes, and the in-memory caches hand that executable back to a second
    ``jit`` of the same function.  So this traces a fresh function object
    (no in-memory hit) with the metadata in the cache key (no stale hit).
    The HLO is the executed program's but for metadata, so its instruction
    names are the ones the device trace shows.
    """
    fn = program.fn

    @functools.wraps(fn)
    def fresh(*args):
        return fn(*args)

    key_flag = "jax_compilation_cache_include_metadata_in_key"
    keyed = getattr(jax.config, key_flag)
    jax.config.update(key_flag, True)
    _replaying.on = True
    try:
        lowered = jax.jit(fresh, donate_argnums=program.donate_argnums).lower(*program.shapes)
        return lowered.compile().as_text()
    finally:
        _replaying.on = False
        jax.config.update(key_flag, keyed)


def op_scopes(name: str) -> dict[str, str | None]:
    """The executed program ``name``'s instructions -> their ``repro.*``
    scopes; empty where no such program has been traced.

    Compiles the program again from the recorded argument shapes, with the
    same donation (``_compile``): call it after the work it describes, never
    inside a timed window.
    """
    program = _programs.get(name)
    if program is None:
        return {}
    if program.scopes is None:
        program.scopes = hlo_scopes(_compile(program))
    return program.scopes
