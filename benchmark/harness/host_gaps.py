"""The host between programs, on the program's own clock.

A **gap** is a stretch in which the device has no program while work is
waiting. It starts at the end of an ``engine.device_wait`` (the span
ends when the program's result is ready, and a readback leaves nothing
in flight) and ends at the end of the next ``engine.dispatch``, where the
``engine.decode`` or ``engine.prefill_step`` around that dispatch reads
``in_flight`` 0. A dispatch that reads ``in_flight`` 1 or more ends no
gap and starts none: a program was already on the device (a chunk, the
copy of a shared page), and the host cannot see when it ended. Such
dispatches are counted (``skipped``); a gap they would have ended is
dropped. A gap that overlaps the benchmark's ``wait_for_arrival`` span
is the traffic's idle, not the host's, and is left out.

Inside a gap each instant belongs to the innermost program span over it
(that span's self time: its extent less its children's). It is named by
that span, unless the span is one of the phases in ``CONTAINERS`` and
holds named steps of a tick: its own time between those children is
what no span names, and so is time under no program span at all. A
``gc`` span (a collection, under whatever span was open) names its
time too, and is no phase.

Everything is read from ``program_spans.traced(run)``. A program that
keeps no ``engine.device_wait`` (an untraced run, a training cell, a
program from before the span) has nothing to read: ``account`` returns
None.
"""
from __future__ import annotations

import bisect
import collections

from harness import program_spans as ps

SCHED = ("sched.", "pool.", "prefix.", "state.")
CONTAINERS = ("sched.step", "sched.prefill_tick", "sched.decode_tick",
              "engine.prefill_begin", "engine.prefill_step",
              "engine.decode", "engine.readback")
WRAPPERS = ("engine.decode", "engine.prefill_step")

Gap = collections.namedtuple("Gap", "lo hi named_ns sched_ns")
Account = collections.namedtuple("Account", "gaps skipped window_ns tails")


def _self_intervals(span, kids):
    """``span``'s extent less its children's, as ``(lo, hi)`` pieces."""
    out, at = [], span.start_ns
    for c in sorted(kids.get(span.span_id, ()), key=lambda c: c.start_ns):
        if c.start_ns > at:
            out.append((at, c.start_ns))
        at = max(at, c.end_ns)
    if span.end_ns > at:
        out.append((at, span.end_ns))
    return out


def names_its_time(span, kids):
    """Whether ``span``'s own time is named: any span but a phase
    container with children (a container that held no phase, such as a
    prefill tick with nothing to prefill, did its own work)."""
    return span.name not in CONTAINERS or all(
        c.name == "gc" for c in kids.get(span.span_id, ()))


class Named:
    """The self time of the spans among ``spans`` (on ``threads``, where
    given) that name their time (``named=False``: of those that do not),
    as sorted disjoint pieces, and how much of an interval they cover."""

    def __init__(self, spans, threads=None, named=True):
        kids = ps.children(spans)
        self.pieces = sorted(
            (a, b, s.name) for s in spans
            if (threads is None or s.tid in threads)
            and names_its_time(s, kids) == named
            for a, b in _self_intervals(s, kids))
        self.starts = [p[0] for p in self.pieces]

    def by_name(self, lo, hi):
        """``{name: nanoseconds of [lo, hi] under that span's own time}``."""
        out = collections.Counter()
        i = max(0, bisect.bisect_right(self.starts, lo) - 1)
        for a, b, name in self.pieces[i:]:
            if a >= hi:
                break
            out[name] += max(0, min(b, hi) - max(a, lo))
        return out

    def covered(self, lo, hi, prefixes=()):
        """Nanoseconds of ``[lo, hi]`` that a span names (one whose name
        starts with one of ``prefixes``, where given)."""
        return sum(ns for name, ns in self.by_name(lo, hi).items()
                   if not prefixes or name.startswith(prefixes))


def _window_ns(run, spans):
    """The traced span, from where the kept records begin (the bounded
    buffer may have let the oldest go)."""
    marks = [(s, e) for name, s, e in run.spans.records if name == "traced"]
    lo, hi = marks[0][0] * 1e9, marks[-1][1] * 1e9
    return hi - max(lo, min(s.start_ns for s in spans))


def _arrivals(run):
    return sorted((s * 1e9, e * 1e9) for name, s, e in run.spans.records
                  if name == "wait_for_arrival")


def account(run):
    """The gaps of the traced window, with what names their time; the
    skipped dispatches; the window; and the readback tail of every
    ``engine.decode`` (its end less the end of its ``engine.device_wait``).
    None where the program keeps no ``engine.device_wait``."""
    spans = ps.traced(run)
    if not ps.named(spans, "engine.device_wait"):
        return None
    by_id = {s.span_id: s for s in spans}
    kids = ps.children(spans)
    named = Named(spans,
                  {s.tid for s in ps.named(spans, "engine.dispatch")})

    marks = [(s.end_ns, 0, None) for s in ps.named(spans,
                                                   "engine.device_wait")]
    for s in ps.named(spans, "engine.dispatch"):
        parent = by_id.get(s.parent_id)
        if parent is not None and parent.name in WRAPPERS:
            marks.append((s.end_ns, 1, parent.attrs.get("in_flight")))
    waits = _arrivals(run)
    gaps, skipped, ready = [], 0, None
    for at, kind, in_flight in sorted(marks, key=lambda m: m[:2]):
        if kind == 0:
            ready = at
            continue
        if in_flight != 0:
            skipped += 1
        elif ready is not None and not any(
                a < at and b > ready for a, b in waits):
            gaps.append(Gap(ready, at, named.covered(ready, at),
                            named.covered(ready, at, SCHED)))
        ready = None

    tails = [(s.end_ns - w.end_ns) / 1e6
             for s in ps.named(spans, "engine.decode")
             for back in ps.named(kids.get(s.span_id, ()), "engine.readback")
             for w in ps.named(kids.get(back.span_id, ()),
                               "engine.device_wait")]
    return Account(gaps, skipped, _window_ns(run, spans), tails)


def gap_ms(gap):
    return (gap.hi - gap.lo) / 1e6
