"""Program spans and compile counts of the serving path.

A span names one piece of host work on the request path: the HTTP
front-end, the window scheduler, replica dispatch and the paged engine.
Spans are recorded only while tracing is on: after ``enable(True)``, or while
a JAX profiler session is active (``jax.profiler.start_trace`` or a capture
through the profiler server), so a profile of a running server carries the
program's spans with no switch thrown.  Off, :func:`span` hands back one
shared no-op context: no record, no ``TraceAnnotation``, no clock read.

On, each span is two things on one timeline:

* an in-memory :class:`Record` (ids, parent span, thread, start and end on
  ``time.perf_counter_ns()``) in a bounded buffer that :func:`records`
  returns;
* a ``jax.profiler.TraceAnnotation`` named like the span and carrying its
  ``span_id`` and ids, so the same span sits on the host plane of a profiler
  trace beside the device's operations.

A span's parent is the innermost span open on its thread; work handed to
another thread names its parent explicitly (``span(..., parent=...)``).
Spans go on the host only: a ``jax.named_scope`` inside a jitted program would
rename the device operations that trace readers group by and change the
programs' compile-cache keys.

Compiles: a ``jax.monitoring`` listener counts backend compiles by program
name (:func:`compile_counts`) whether tracing is on or not; with tracing on,
each compile is also a ``jit.compile`` record.
"""
from __future__ import annotations

import itertools
import threading
import time
from collections import deque
from typing import NamedTuple, Optional

import jax

__all__ = ["Record", "OFF", "enable", "span", "current_id",
           "records", "clear", "compile_counts"]

MAX_RECORDS = 65536        # about 15 spans a query: 70 s of this at 60 queries/s
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

_profiling = jax.profiler.TraceAnnotation.is_enabled
_forced = False
_records: deque = deque(maxlen=MAX_RECORDS)
_ids = itertools.count(1)
_local = threading.local()
_compiles: dict[str, int] = {}
_compiles_lock = threading.Lock()


class Record(NamedTuple):
    span_id: int
    parent_id: int            # 0: a root span
    name: str
    ids: dict
    thread: int               # threading.get_ident() of the recording thread
    t0_ns: int                # time.perf_counter_ns()
    t1_ns: int


class _Off:
    """The context every span gets while tracing is off."""

    __slots__ = ()
    span_id = 0

    def __enter__(self):
        return self

    def __exit__(self, *exc) -> bool:
        return False

    def tag(self, **ids) -> None:
        pass


OFF = _Off()


def _stack() -> list:
    stack = getattr(_local, "stack", None)
    if stack is None:
        stack = _local.stack = []
    return stack


class _Span:
    __slots__ = ("name", "parent_id", "ids", "span_id", "t0_ns", "_ann")

    def __init__(self, name: str, parent: Optional[int], ids: dict):
        self.name = name
        self.parent_id = parent
        self.ids = ids

    def __enter__(self) -> "_Span":
        stack = _stack()
        self.span_id = next(_ids)
        if self.parent_id is None:
            self.parent_id = stack[-1].span_id if stack else 0
        stack.append(self)
        self._ann = jax.profiler.TraceAnnotation(self.name, span_id=self.span_id,
                                                 **self.ids)
        self._ann.__enter__()
        self.t0_ns = time.perf_counter_ns()
        return self

    def tag(self, **ids) -> None:
        """Ids learnt after the span began (a request's rid once submitted)."""
        self.ids.update(ids)
        self._ann.set_metadata(**ids)

    def __exit__(self, *exc) -> bool:
        t1 = time.perf_counter_ns()
        self._ann.__exit__(*exc)
        _stack().pop()
        _records.append(Record(self.span_id, self.parent_id, self.name, self.ids,
                               threading.get_ident(), self.t0_ns, t1))
        return False


def enable(on: bool) -> None:
    """Record spans whether or not a profiler session is active."""
    global _forced
    _forced = bool(on)


def span(name: str, parent: Optional[int] = None, **ids):
    """A context manager over one piece of host work; see the module
    docstring.  ``parent`` names the parent span's id for work handed across
    threads; ``ids`` (rid, invocation id, ...) go on the record and the
    trace event."""
    if not (_forced or _profiling()):
        return OFF
    return _Span(name, parent, ids)


def current_id() -> int:
    """Id of the innermost span open on this thread (0: none)."""
    stack = getattr(_local, "stack", None)
    return stack[-1].span_id if stack else 0


def records() -> list[Record]:
    return list(_records)


def clear() -> None:
    _records.clear()


def compile_counts() -> dict[str, int]:
    """Backend compiles since start-up, by program name."""
    with _compiles_lock:
        return dict(_compiles)


def _on_duration(event: str, duration_secs: float, fun_name: Optional[str] = None,
                 **_) -> None:
    if event != COMPILE_EVENT:
        return
    name = fun_name or "unknown"
    if name.startswith("jit(") and name.endswith(")"):
        name = name[4:-1]
    with _compiles_lock:
        _compiles[name] = _compiles.get(name, 0) + 1
    if _forced or _profiling():
        t1 = time.perf_counter_ns()
        _records.append(Record(next(_ids), current_id(), "jit.compile",
                               {"program": name}, threading.get_ident(),
                               t1 - int(duration_secs * 1e9), t1))


jax.monitoring.register_event_duration_secs_listener(_on_duration)
