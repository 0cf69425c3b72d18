"""Profiler instrumentation utilities: the program's one span recorder.

Parity: ``/root/reference/python/paddle/profiler/utils.py:37 RecordEvent``.

A :class:`RecordEvent` is kept while somebody is looking: while a jax
device trace is being taken (``jax.profiler.start_trace`` / ``trace``,
which is what ``TraceAnnotation.is_enabled()`` reports) or while a
Paddle-style ``Profiler`` is in its record state. Otherwise ``begin()``
is one predicate and nothing is stored, so the hot paths carry their
spans unconditionally.

Two clocks, one span on both:

- the record (:class:`Span`, read with :func:`recorded_spans`) is on
  ``time.perf_counter_ns``: the clock of ``Request`` stamps, of
  ``sched.step_times`` and of the benchmark's window and spans
  (``time.perf_counter`` seconds × 1e9);
- the same scope is a ``jax.profiler.TraceAnnotation`` carrying the
  span's ``attrs`` as metadata, which puts it on the profiler's clock,
  the clock of the device planes of the ``.xplane.pb``.

A span that exists on both fixes the offset between the two (the
benchmark's ``bench:traced`` is such a span).

Records form a tree per thread: ``parent_id`` is the span that was open
on the same thread when this one began (0 at the top). The buffer is
bounded: past ``MAX_SPANS`` the oldest records go and
:func:`dropped_spans` counts them.

:func:`record_gc_pauses` adds the garbage collector's pauses to the
record as ``gc`` spans (generation, collected), under whatever span was
open when the collection began.
"""
from __future__ import annotations

import gc
import itertools
import threading
import time
from collections import deque
from contextlib import ContextDecorator
from typing import NamedTuple

from jax.profiler import TraceAnnotation

MAX_SPANS = 1 << 17


class Span(NamedTuple):
    """One finished :class:`RecordEvent` (times on ``perf_counter_ns``)."""
    name: str
    tid: int
    start_ns: int
    end_ns: int
    type: str
    span_id: int
    parent_id: int
    attrs: dict


_lock = threading.RLock()     # a gc span can end inside a locked read
_host_events: deque = deque(maxlen=MAX_SPANS)
_dropped = 0
_counter_samples: list = []      # (name, ts_ns, value) -> "ph":"C" events
_collecting = False
_span_ids = itertools.count(1)
_open = threading.local()   # .stack: ids of the spans open on a thread; .tid


def _set_collecting(flag: bool):
    global _collecting
    _collecting = flag


def recorded_spans() -> list:
    """The kept records, oldest first, without draining them: under a
    bare jax trace no ``Profiler`` ever collects the buffer."""
    with _lock:
        return list(_host_events)


def dropped_spans() -> int:
    """Records the bounded buffer has let go since the process began."""
    return _dropped


def _drain_events():
    with _lock:
        ev = list(_host_events)
        _host_events.clear()
    return ev


def _drain_counters():
    global _counter_samples
    with _lock:
        cs, _counter_samples = _counter_samples, []
    return cs


def record_counter(name: str, value: float):
    """Record a chrome-trace counter sample (``"ph": "C"``) — the memory/
    throughput track alongside the RecordEvent spans. No-op unless a
    Profiler record span is active, so per-step samplers can call it
    unconditionally."""
    if _collecting:
        with _lock:
            _counter_samples.append(
                (name, time.perf_counter_ns(), float(value)))


class RecordEvent(ContextDecorator):
    """User-scoped event: ``with RecordEvent('data_load'): ...`` or
    decorator. Keyword ``attrs`` (small scalars: ``rid``, ``bucket``,
    ``tokens``...) go into the record and into the trace annotation's
    metadata; :meth:`set` adds those known only once the work is done.
    ``annotation`` is the annotation class: the train step passes
    ``jax.profiler.StepTraceAnnotation``."""

    def __init__(self, name: str, event_type=None, *,
                 annotation=TraceAnnotation, **attrs):
        self.name = name
        self.event_type = event_type or "UserDefined"
        self.attrs = attrs
        self._annotation = annotation
        self._note = None
        self._begin_ns = None

    def begin(self):
        tracing = TraceAnnotation.is_enabled()
        if not (tracing or _collecting):
            return self
        if tracing:
            self._note = self._annotation(self.name, **self.attrs)
            self._note.__enter__()
        try:
            stack = _open.stack
        except AttributeError:
            stack = _open.stack = []
            _open.tid = threading.get_ident()
        self._parent_id = stack[-1] if stack else 0
        self._span_id = next(_span_ids)
        stack.append(self._span_id)
        self._begin_ns = time.perf_counter_ns()
        return self

    @property
    def kept(self) -> bool:
        """Whether this span is being recorded (between ``begin()`` and
        ``end()`` while somebody is looking): work done only for the
        record, such as waiting for the device apart from the copy that
        follows, runs only then."""
        return self._begin_ns is not None

    def set(self, **attrs):
        """Attributes known only after ``begin()`` (a count, a result)."""
        if self._begin_ns is None:
            return
        self.attrs.update(attrs)
        if self._note is not None:
            self._note.set_metadata(**attrs)

    def end(self, *_exc):
        global _dropped
        if self._begin_ns is None:
            return
        end_ns = time.perf_counter_ns()
        # off the stack before anything allocates: a collection that
        # begins after end_ns must not be recorded inside this span
        try:
            stack, tid = _open.stack, _open.tid
        except AttributeError:      # ended on a thread that began none
            stack, tid = (), threading.get_ident()
        if stack and stack[-1] == self._span_id:
            stack.pop()
        elif self._span_id in stack:
            # and whatever was opened inside this span and never ended
            del stack[stack.index(self._span_id):]
        if self._note is not None:
            self._note.__exit__(None, None, None)
            self._note = None
        # tuple.__new__: the record without a Python-level constructor,
        # which would be charged to the parent's time between children
        span = tuple.__new__(Span, (
            self.name, tid, self._begin_ns, end_ns,
            self.event_type, self._span_id, self._parent_id, self.attrs))
        self._begin_ns = None
        with _lock:
            if len(_host_events) == _host_events.maxlen:
                _dropped += 1
            _host_events.append(span)

    # one frame each way: the recorder's own time sits between a span's
    # children, where no span names it
    __enter__ = begin
    __exit__ = end


_gc_event = None       # the collection in progress (one at a time)


def _gc_span(phase, info):
    global _gc_event
    if phase == "start":
        if TraceAnnotation.is_enabled() or _collecting:
            _gc_event = RecordEvent("gc", generation=info["generation"])
            _gc_event.begin()
    elif _gc_event is not None:
        _gc_event.set(collected=info["collected"])
        _gc_event.end()
        _gc_event = None


def record_gc_pauses():
    """Record every garbage collection as a ``gc`` span, kept like any
    other span (only while somebody is looking). Idempotent: the
    callback is installed once per process."""
    if _gc_span not in gc.callbacks:
        gc.callbacks.append(_gc_span)


def load_profiler_result(filename: str):
    """Load an exported chrome-trace json (profiler.py export counterpart)."""
    import json
    with open(filename) as f:
        return json.load(f)
