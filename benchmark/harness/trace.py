"""From the profiler's ``.xplane.pb`` to numbers.

Read with ``jax.profiler.ProfileData`` and nothing else. What the trace
holds, as looked at by hand on a TPU v5 lite (PR 26):

- one plane per chip, ``/device:TPU:<n>``; its line ``XLA Ops`` holds one
  event per executed HLO operation. An event's name is the instruction's
  whole text, ``%fusion.12 = bf16[...] fusion(...)``: the operation's own
  name is what stands before `` = ``. A Pallas kernel is a ``custom-call``
  with ``custom_call_target="tpu_custom_call"``, under its ``name=`` where
  it has one (``%paged_attention_decode.4``) and under the name of the
  jaxpr it sits in where it has none (``%closed_call.13``,
  ``%checkpoint.22``: the flash kernels). A ``while`` (a scanned layer
  stack) is one long event that encloses the events of its body, so time
  by operation is self time: an event's duration less what its children
  cover. The lines ``Steps`` and ``XLA Modules`` hold one event per
  executed program; ``Async XLA Ops`` holds copies that overlap the
  operations and is not counted as busy time.
- ``/host:CPU``: one line per thread. ``jax.profiler.TraceAnnotation``
  spans appear on the thread that opened them, on the same clock as the
  device planes. The benchmark names its spans ``bench:<name>``.

The traced window is the span ``bench:traced``: busy time, idle gaps and
operation times are all clipped to it.
"""
from __future__ import annotations

import collections
import glob
import os
import re
import shutil

DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
SPAN_PREFIX = "bench:"
WINDOW_SPAN = "bench:traced"
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
               "collective-permute", "all-to-all", "collective-broadcast")


def find_xplane(trace_dir):
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load(path):
    from jax.profiler import ProfileData
    return ProfileData.from_file(path)


def device_events(profile, host_ops=False):
    """``{device: [(start_ns, end_ns, name), ...]}`` sorted by start.
    ``host_ops`` is the CPU rehearsal's steering: the host backend has no
    device plane and runs its operations on host threads, marked by an
    ``hlo_op`` stat; all of them together stand for one device."""
    out = collections.defaultdict(list)
    for plane in profile.planes:
        if plane.name.startswith(DEVICE_PLANE):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    out[plane.name].extend(
                        (e.start_ns, e.start_ns + e.duration_ns, e.name)
                        for e in line.events)
        elif host_ops and plane.name == HOST_PLANE:
            for line in plane.lines:
                if not line.name.startswith("tf_XLA"):
                    continue
                for e in line.events:
                    if any(k == "hlo_op" for k, _ in e.stats):
                        out["host-ops"].append(
                            (e.start_ns, e.start_ns + e.duration_ns, e.name))
    return {dev: sorted(evs) for dev, evs in out.items()}


def host_spans(profile):
    """The benchmark's own spans: ``[(start_ns, end_ns, name)]`` with the
    prefix taken off, sorted by start."""
    spans = []
    for plane in profile.planes:
        if plane.name != HOST_PLANE:
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(SPAN_PREFIX):
                    spans.append((e.start_ns, e.start_ns + e.duration_ns,
                                  e.name[len(SPAN_PREFIX):]))
    return sorted(spans)


def merged(intervals, lo, hi):
    """Union of ``(start, end, ...)`` intervals clipped to ``[lo, hi]``,
    as a sorted list of disjoint ``(start, end)``."""
    out = []
    for iv in sorted((max(iv[0], lo), min(iv[1], hi)) for iv in intervals):
        if iv[1] <= iv[0]:
            continue
        if out and iv[0] <= out[-1][1]:
            out[-1][1] = max(out[-1][1], iv[1])
        else:
            out.append([iv[0], iv[1]])
    return [(a, b) for a, b in out]


def self_times(events, lo, hi):
    """``{name: [self_ns, calls]}``: each event's clipped duration less
    the part that events nested inside it cover."""
    acc = collections.defaultdict(lambda: [0.0, 0])
    stack = []      # [end, name, self_ns]

    def close(upto):
        while stack and stack[-1][0] <= upto:
            end, name, own = stack.pop()
            acc[name][0] += own
            acc[name][1] += 1

    for start, end, name in events:
        start, end = max(start, lo), min(end, hi)
        if end <= start:
            continue
        close(start)
        if stack:
            stack[-1][2] -= min(end, stack[-1][0]) - start
        stack.append([end, name, end - start])
    close(float("inf"))
    return acc


PALLAS = 'custom_call_target="tpu_custom_call"'


def op_name(text):
    """``%fusion.12 = bf16[...] fusion(...)`` → ``fusion.12``."""
    return text.split(" = ", 1)[0].lstrip("%")


def op_kind(text):
    """An operation without its number, a Pallas kernel marked as one:
    ``fusion``, ``pallas:paged_attention_decode``."""
    name = op_name(text)
    head, _, tail = name.rpartition(".")
    while head and (tail.isdigit() or tail == "clone"):
        name = head
        head, _, tail = name.rpartition(".")
    return ("pallas:" if PALLAS in text else "") + name[:56]


_COLLECTIVE_CALL = re.compile(
    r" (?:%s)(?:-start|-done)?\(" % "|".join(COLLECTIVES))


def is_collective(text):
    """By the instruction's opcode where the event holds its whole text
    (a ``psum`` of a ``shard_map`` is ``%psum.5 = bf16[...] all-reduce(``),
    else by its name."""
    if " = " in text:
        return bool(_COLLECTIVE_CALL.search(text.split(" = ", 1)[1]))
    return op_name(text).startswith(COLLECTIVES)


def innermost(spans, t):
    """Name of the shortest benchmark span that covers instant ``t``."""
    best = None
    for start, end, name in spans:
        if start > t:
            break
        if end >= t and name != "traced" and (
                best is None or end - start < best[0]):
            best = (end - start, name)
    return best[1] if best else "outside_spans"


def summarize(profile, host_ops=False, top=10):
    """Everything the per-layer readers and ``breakdown`` need, in
    seconds: ``window_s``; per device ``busy_s``, ``collective_s`` and
    ``ops`` (``{name: [self seconds, calls]}``); their means over the
    devices; ``device_ops`` (self time by kind of operation, the largest
    ``top``) and ``idle_gaps`` (idle time of the first device by the
    benchmark's span that covers each gap's middle, the largest ``top``).
    """
    spans = host_spans(profile)
    window = [s for s in spans if s[2] == WINDOW_SPAN[len(SPAN_PREFIX):]]
    if not window:
        raise ValueError("the trace holds no bench:traced span")
    lo, hi = window[0][0], window[-1][1]
    devices = device_events(profile, host_ops)
    if not devices:
        raise ValueError("the trace holds no device operations")
    per_device, by_kind = {}, collections.defaultdict(float)
    for dev, events in sorted(devices.items()):
        own = self_times(events, lo, hi)
        busy = merged(events, lo, hi)
        per_device[dev] = {
            "busy_s": sum(b - a for a, b in busy) / 1e9,
            "collective_s": sum(v[0] for k, v in own.items()
                                if is_collective(k)) / 1e9,
            "ops": {k: [v[0] / 1e9, v[1]] for k, v in own.items()},
            "busy": busy,
        }
        for k, v in own.items():
            by_kind[op_kind(k)] += v[0] / 1e9 / len(devices)
    first = per_device[sorted(per_device)[0]]
    gaps = collections.defaultdict(float)
    edges = [lo] + [t for iv in first["busy"] for t in iv] + [hi]
    for a, b in zip(edges[0::2], edges[1::2]):
        if b > a:
            gaps[innermost(spans, (a + b) / 2)] += (b - a) / 1e9
    n = len(per_device)
    rank = lambda d: [[k, v] for k, v in sorted(
        d.items(), key=lambda kv: -kv[1])[:top]]
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": sum(d["busy_s"] for d in per_device.values()) / n,
        "collective_s": sum(d["collective_s"]
                            for d in per_device.values()) / n,
        "devices": {dev: {k: d[k] for k in ("busy_s", "collective_s", "ops")}
                    for dev, d in per_device.items()},
        "device_ops": rank(by_kind),
        "idle_gaps": rank(gaps),
        "spans": [(s / 1e9, e / 1e9, name) for s, e, name in spans],
        "t0_s": lo / 1e9,
    }


class Recording:
    """The profiler trace of the last seconds of a run's window: started
    by the driver once ``due()`` says so, stopped after the window has
    closed (stopping costs seconds of host work, which then fall outside
    the window), read into ``run.trace_summary`` once the driver has the
    time, and deleted."""

    def __init__(self, run, seconds):
        self.run = run
        self.wanted = bool(run.trace)
        self.lead = max(0.0, run.seconds - seconds)
        self.dir = os.path.join(os.path.dirname(run.root), ".bench_out",
                                f"trace-{run.cell['name']}")
        self._span = None

    def due(self, since_open):
        return self.wanted and self._span is None and since_open >= self.lead

    def start(self):
        import jax
        shutil.rmtree(self.dir, ignore_errors=True)
        jax.profiler.start_trace(self.dir)
        self.run.spans.tracing = True
        self._span = self.run.spans.span("traced")
        self._span.__enter__()

    def stop(self):
        if self._span is None or not self.run.spans.tracing:
            return
        import jax
        self._span.__exit__(None, None, None)
        self.run.spans.tracing = False
        jax.profiler.stop_trace()

    def read(self):
        if self._span is None:
            return
        self.run.trace_summary = summarize(
            load(find_xplane(self.dir)),
            host_ops=self.run.device["platform"] != "tpu")
        shutil.rmtree(self.dir, ignore_errors=True)


def idle_share(summary):
    """1 less the union of the device's operation intervals over the
    traced window, mean over the chips, in percent."""
    return 100.0 * (1.0 - summary["busy_s"] / summary["window_s"])
