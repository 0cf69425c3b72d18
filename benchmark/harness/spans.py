"""Host spans around the calls into each layer, from the benchmark's side.

Kept in memory as ``(name, start_s, end_s)`` on ``time.perf_counter``.
While a trace is being taken each span is also a
``jax.profiler.TraceAnnotation`` named ``bench:<name>``, which puts it on
the device trace's clock, where idle gaps are attributed to it.
"""
from __future__ import annotations

import contextlib
import time


class Spans:
    def __init__(self):
        self.records = []
        self.tracing = False

    @contextlib.contextmanager
    def span(self, name):
        note = None
        if self.tracing:
            from jax.profiler import TraceAnnotation
            note = TraceAnnotation("bench:" + name)
            note.__enter__()
        start = time.perf_counter()
        try:
            yield
        finally:
            self.records.append((name, start, time.perf_counter()))
            if note is not None:
                note.__exit__(None, None, None)

    def within(self, name, lo, hi):
        """``(start, end)`` of the spans of that name that ended inside
        ``[lo, hi]``."""
        return [(s, e) for n, s, e in self.records
                if n == name and lo <= e <= hi]
