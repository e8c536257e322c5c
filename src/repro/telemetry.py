"""Host spans and compile counters of the query path.

``span(name, **attrs)`` marks one step of the work where it happens: a
query's call, a plan's offline build and its parts, an artifact built for
the shared pool, a plan's dispatch.  Each span

* opens ``jax.profiler.TraceAnnotation("repro." + name, **attrs)``, so that
  an operator reading a profile finds it on the host plane of the same
  ``.xplane.pb`` as the device ops, on the same clock;
* is timed with ``time.perf_counter``;
* on exit, appends one :class:`Span` record to a bounded in-memory ring
  (:func:`spans`) and adds to per-name totals (:func:`totals`).

The innermost open span of each thread is kept on a thread-local stack.  A
span opened with no open parent is a root, a request: its id is the
request id of every span opened inside it.

JAX reports the seconds it spends tracing a function to a jaxpr, lowering
it to an MLIR module and compiling it (or loading the compiled program from
the persistent cache) as ``jax.monitoring`` duration events.  One listener,
registered when this module is imported, credits each such event to the
innermost open span of the thread that caused it: ``programs`` counts the
backend compiles, ``compile_s`` sums the seconds of all three.  Events
outside any span are not counted.  Summed over :func:`totals`, they are the
process's program loads since the last :func:`reset`.
"""
from __future__ import annotations

import collections
import contextlib
import itertools
import threading
import time
from typing import Deque, Dict, List, NamedTuple, Optional

import jax

PREFIX = "repro."
#: Records the ring keeps; older ones are dropped first.
RING_SIZE = 1 << 16

_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
_COMPILE_EVENTS = frozenset((
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    _BACKEND_COMPILE,
))


class Span(NamedTuple):
    """One closed span.  Times are ``time.perf_counter`` seconds;
    ``programs`` and ``compile_s`` count only the compile events that came
    while this span was the innermost one open, not its children's."""

    id: int
    name: str
    start: float
    end: float
    parent: Optional[int]     # id of the enclosing span; None for a root
    request: int              # id of the root span
    programs: int             # backend compiles and cache loads
    compile_s: float          # trace + lower + compile-or-load seconds
    attrs: Dict               # the keyword arguments the span was opened with


class Total(NamedTuple):
    """Per-name totals over every closed span of that name."""

    count: int
    seconds: float
    max: float
    programs: int
    compile_s: float


_local = threading.local()
_ids = itertools.count(1)
_lock = threading.Lock()
_ring: Deque[tuple] = collections.deque(maxlen=RING_SIZE)   # Span fields
_totals: Dict[str, list] = {}                                # Total fields


def _stack() -> List["_Open"]:
    stack = getattr(_local, "stack", None)
    if stack is None:
        stack = _local.stack = []
    return stack


class _Open(contextlib.ContextDecorator):
    """What :func:`span` returns: a context manager, or a decorator that
    opens a fresh span around each call of the function."""

    def __init__(self, name: str, attrs: Dict):
        self.name = name
        self.attrs = attrs

    def _recreate_cm(self) -> "_Open":
        return _Open(self.name, dict(self.attrs))

    def __enter__(self) -> "_Open":
        self.annotation = jax.profiler.TraceAnnotation(PREFIX + self.name,
                                                       **self.attrs)
        self.stack = stack = _stack()
        self.id = next(_ids)
        if stack:
            self.parent = stack[-1].id
            self.request = stack[-1].request
        else:
            self.parent = None
            self.request = self.id
        self.programs = 0
        self.compile_s = 0.0
        stack.append(self)
        self.annotation.__enter__()
        self.start = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        end = time.perf_counter()
        self.annotation.__exit__(*exc)
        self.stack.pop()
        dt = end - self.start
        with _lock:
            _ring.append((self.id, self.name, self.start, end, self.parent,
                          self.request, self.programs, self.compile_s,
                          self.attrs))
            t = _totals.get(self.name)
            if t is None:
                _totals[self.name] = [1, dt, dt, self.programs,
                                      self.compile_s]
            else:
                t[0] += 1
                t[1] += dt
                t[2] = max(t[2], dt)
                t[3] += self.programs
                t[4] += self.compile_s


def span(name: str, **attrs) -> _Open:
    """Record one span named ``name`` (``with span(...)``, or
    ``@span(...)`` on a function); ``attrs`` annotate its profiler event
    and are kept on its record."""
    return _Open(name, attrs)


def annotate(**attrs) -> None:
    """Add ``attrs`` to the record of the innermost span open on this
    thread (a decision made inside the span, known only once it is made).
    They reach :func:`spans`, not the profiler event, which took the
    attrs the span was opened with.  Nothing happens outside any span."""
    stack = _stack()
    if stack:
        stack[-1].attrs.update(attrs)


def spans() -> List[Span]:
    """The ring's records, oldest first (each appended as its span closed,
    so a child comes before its parent)."""
    with _lock:
        return [Span._make(r) for r in _ring]


def totals() -> Dict[str, Total]:
    """Per-name totals since the last :func:`reset`."""
    with _lock:
        return {name: Total._make(t) for name, t in _totals.items()}


def reset() -> None:
    """Forget every record and total (spans still open are kept)."""
    with _lock:
        _ring.clear()
        _totals.clear()


def _on_duration(event: str, secs: float, **_) -> None:
    if event not in _COMPILE_EVENTS:
        return
    stack = getattr(_local, "stack", None)
    if not stack:
        return
    top = stack[-1]
    top.compile_s += secs
    if event == _BACKEND_COMPILE:
        top.programs += 1


jax.monitoring.register_event_duration_secs_listener(_on_duration)
