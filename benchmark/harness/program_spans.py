"""The program's own spans, as the per-layer readers take them.

``paddle_tpu.profiler.utils.RecordEvent`` keeps a record of every span of
the program while a profiler trace is being taken: ``(name, tid,
start_ns, end_ns, type, span_id, parent_id, attrs)`` on
``time.perf_counter_ns``, the clock of ``run.spans.records`` and of
``run.window`` (seconds there). ``parent_id`` is the span that was open
on the same thread when this one began, so the records are a tree:
``sched.step`` holds the scheduler's phases, those hold
``engine.prefill_begin`` / ``engine.prefill_step`` / ``engine.decode``,
and those hold ``engine.host_prep`` / ``engine.dispatch`` /
``engine.readback``.

A reader takes the records that ended inside the benchmark's ``traced``
span. Where there are none (an untraced run, a cell of the other kind, a
program that keeps no such record) it has nothing to read and returns
``None``.
"""
from __future__ import annotations

import collections
import statistics


def traced(run):
    """The program's records that ended inside the traced span."""
    try:
        from paddle_tpu.profiler.utils import recorded_spans
    except ImportError:         # a program from before it kept them
        return []
    marks = [(s, e) for name, s, e in run.spans.records if name == "traced"]
    if not marks:
        return []
    lo, hi = marks[0][0] * 1e9, marks[-1][1] * 1e9
    return [s for s in recorded_spans() if lo <= s.end_ns <= hi]


def named(spans, name):
    return [s for s in spans if s.name == name]


def children(spans):
    """``{span_id: [the records that name it as their parent]}``."""
    kids = collections.defaultdict(list)
    for s in spans:
        kids[s.parent_id].append(s)
    return kids


def ms(span):
    return (span.end_ns - span.start_ns) / 1e6


def covered_ms(span, kids, prefix):
    """What the outermost descendants of ``span`` whose name starts with
    ``prefix`` cover of it, in milliseconds."""
    total = 0.0
    for child in kids.get(span.span_id, ()):
        total += ms(child) if child.name.startswith(prefix) \
            else covered_ms(child, kids, prefix)
    return total


def child_ms(span, kids, *names):
    """The time of ``span``'s own children of those names."""
    return sum(ms(c) for c in kids.get(span.span_id, ())
               if c.name in names)


def median_ms(values):
    values = list(values)
    return statistics.median(values) if values else None
