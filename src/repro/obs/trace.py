"""Host-side span/trace layer: timed scopes + structured JSONL events.

The compiled programs are observable in-graph via the telemetry rings
(``repro.obs.rings``); everything *around* them — session compiles,
program dispatches, fleet waves, admission refills, cache lookups — is
host work, traced here:

    from repro import obs

    with obs.span("cohort.wave", cohort=0, slots_active=3):
        ...

A span times its block (``perf_counter_ns``), enters a
``jax.profiler.TraceAnnotation`` of the same name — so when a profiler
trace is active (``--trace-dir`` on the launchers, or
``jax.profiler.trace``) the host scopes line up with the device
timeline — and records a structured event on the process-wide
:class:`Tracer`.  ``configure(jsonl_path=...)`` additionally streams
every event as one JSON line; the default tracer keeps a bounded
in-memory buffer so tracing is always on and never grows without bound.

Events are plain dicts::

    {"ev": "span", "name": "cohort.wave", "ts": <unix seconds>,
     "dur_us": 812.4, "t0_us": <perf_counter us>, "id": 7, "parent": 3,
     "call": 3, "compiles": 0, "compile_ms": 0.0, "slots_active": 3, ...}
    {"ev": "event", "name": "cohort.refill", "ts": ..., "slot": 2, ...}

``ts`` is the wall time at the span's end; ``t0_us`` its start on the
``time.perf_counter`` clock, the clock of a caller's own call timer.
Spans nest per thread: ``parent`` is the ``id`` of the span open around
this one (``None`` for a root) and ``call`` the ``id`` of its root, so
the spans of one entry-point call share it and a span's self time is
its ``dur_us`` less its children's.  ``compiles`` counts the backend
compiles (cache loads included) that ran while this span was the
innermost one open on its thread, and ``compile_ms`` the time JAX spent
tracing, lowering and compiling there; children keep their own.

Everything is best-effort and side-effect-free for the traced
computation: tracing never touches program math, RNG streams, or
compile keys.
"""

from __future__ import annotations

import collections
import contextlib
import itertools
import json
import threading
import time
from typing import Any, Deque, Dict, Iterator, List, Optional

import jax
from jax._src import dispatch as _dispatch

#: in-memory event buffer bound of the default tracer — big enough for
#: a whole fleet drain, small enough to never matter.
DEFAULT_BUFFER = 4096


def _jsonable(v: Any) -> Any:
    """Coerce numpy/jax scalars (and anything else) to JSON-safe
    values; arrays become lists, unknown objects become ``repr``."""
    if isinstance(v, (str, int, float, bool)) or v is None:
        return v
    if hasattr(v, "item") and getattr(v, "ndim", None) == 0:
        return v.item()
    if hasattr(v, "tolist"):
        return v.tolist()
    return repr(v)


class _Open:
    """The bookkeeping of a span while it is open."""

    __slots__ = ("id", "parent", "call", "compiles", "compile_ms")

    def __init__(self, span_id: int, parent: Optional["_Open"]):
        self.id = span_id
        self.parent = None if parent is None else parent.id
        self.call = span_id if parent is None else parent.call
        self.compiles = 0
        self.compile_ms = 0.0


class Tracer:
    """Collects span/event records; optionally streams them as JSONL.

    One process-wide instance (:func:`get_tracer`) backs the module
    level :func:`span` / :func:`event` helpers; tests and embedders can
    build private tracers and swap them in with :func:`configure` /
    :func:`use_tracer`.  Span ids are unique within a tracer; the spans
    open on each thread form a stack of their own.
    """

    def __init__(self, jsonl_path: Optional[str] = None,
                 buffer: int = DEFAULT_BUFFER):
        self._events: Deque[Dict[str, Any]] = collections.deque(
            maxlen=buffer)
        self._path = jsonl_path
        self._file = open(jsonl_path, "a") if jsonl_path else None
        self._ids = itertools.count(1)
        self._local = threading.local()

    def _stack(self) -> List[_Open]:
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
        return stack

    def _innermost(self) -> Optional[_Open]:
        """The innermost span open on the calling thread, if any."""
        stack = getattr(self._local, "stack", None)
        return stack[-1] if stack else None

    # -- recording -----------------------------------------------------------

    def emit(self, record: Dict[str, Any]) -> None:
        self._events.append(record)
        if self._file is not None:
            self._file.write(json.dumps(record) + "\n")
            self._file.flush()

    def event(self, name: str, **attrs: Any) -> None:
        """Record an instantaneous event."""
        self.emit({"ev": "event", "name": name, "ts": time.time(),
                   **{k: _jsonable(v) for k, v in attrs.items()}})

    @contextlib.contextmanager
    def span(self, name: str, **attrs: Any) -> Iterator[Dict[str, Any]]:
        """Timed scope: wall duration + ``jax.profiler.TraceAnnotation``.

        Yields a mutable dict — attributes added to it inside the block
        land on the emitted record (e.g. a wave span learns how many
        slots finished only after stepping)."""
        extra: Dict[str, Any] = {}
        stack = self._stack()
        op = _Open(next(self._ids), stack[-1] if stack else None)
        stack.append(op)
        t0 = time.perf_counter_ns()
        with jax.profiler.TraceAnnotation(name):
            try:
                yield extra
            finally:
                dur_ns = time.perf_counter_ns() - t0
                stack.pop()
                self.emit({"ev": "span", "name": name, "ts": time.time(),
                           "dur_us": dur_ns / 1e3, "t0_us": t0 / 1e3,
                           "id": op.id, "parent": op.parent,
                           "call": op.call, "compiles": op.compiles,
                           "compile_ms": op.compile_ms,
                           **{k: _jsonable(v) for k, v in attrs.items()},
                           **{k: _jsonable(v) for k, v in extra.items()}})

    # -- introspection / lifecycle -------------------------------------------

    def events(self, name: Optional[str] = None) -> List[Dict[str, Any]]:
        """A snapshot of the buffered events (newest last), optionally
        filtered by ``name``."""
        evs = list(self._events)
        if name is not None:
            evs = [e for e in evs if e.get("name") == name]
        return evs

    def clear(self) -> None:
        self._events.clear()

    @property
    def jsonl_path(self) -> Optional[str]:
        return self._path

    def close(self) -> None:
        if self._file is not None:
            self._file.close()
            self._file = None


_TRACER = Tracer()

#: the ``jax.monitoring`` duration events of a compile: tracing to a
#: jaxpr, lowering to MLIR, and the backend compile (or persistent-cache
#: load), which alone counts as one compile
_COMPILE_EVENTS = frozenset((_dispatch.JAXPR_TRACE_EVENT,
                             _dispatch.JAXPR_TO_MLIR_MODULE_EVENT,
                             _dispatch.BACKEND_COMPILE_EVENT))


def _on_duration(event: str, duration: float, **_: Any) -> None:
    """Charge a compile to the innermost span open on this thread of the
    process-wide tracer; with none open it is dropped."""
    if event not in _COMPILE_EVENTS:
        return
    op = _TRACER._innermost()
    if op is not None:
        op.compile_ms += duration * 1e3
        op.compiles += event == _dispatch.BACKEND_COMPILE_EVENT


# registered once, when this module is first imported
jax.monitoring.register_event_duration_secs_listener(_on_duration)


def get_tracer() -> Tracer:
    """The process-wide tracer behind :func:`span` / :func:`event`."""
    return _TRACER


def use_tracer(tracer: Tracer) -> Tracer:
    """Swap the process-wide tracer (returns the previous one)."""
    global _TRACER
    prev, _TRACER = _TRACER, tracer
    return prev


def configure(jsonl_path: Optional[str] = None,
              buffer: int = DEFAULT_BUFFER) -> Tracer:
    """Replace the process-wide tracer — with a JSONL sink, the way the
    launchers' ``--metrics-out`` wires span streaming on."""
    old = use_tracer(Tracer(jsonl_path=jsonl_path, buffer=buffer))
    old.close()
    return get_tracer()


def span(name: str, **attrs: Any):
    """``with obs.span("session.dispatch", mode="sync"): ...``"""
    return _TRACER.span(name, **attrs)


def event(name: str, **attrs: Any) -> None:
    """Record an instantaneous event on the process-wide tracer."""
    _TRACER.event(name, **attrs)


def read_jsonl(path: str) -> List[Dict[str, Any]]:
    """Parse a span/event JSONL file (skipping blank lines)."""
    out = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if line:
                out.append(json.loads(line))
    return out
