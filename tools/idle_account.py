"""The device's idle time of one traced serving run, accounted for.

Runs one cell of the benchmark (``benchmark/run.py``, ``--trace 1``) in
this process, keeps its profiler trace, and sets three accounts of the
same traced window beside each other:

- the program's, on its own clock (``benchmark/harness/host_gaps.py``):
  the gaps between the end of an ``engine.device_wait`` and the end of
  the next ``engine.dispatch`` entered with nothing in flight, and what
  names their time;
- the device's: its idle time, less what lies under the benchmark's
  ``wait_for_arrival``;
- the two ends the program cannot stamp, from the trace's host plane
  (the clock of the program's own annotations there), joined by
  ``run_id``: a dispatch's end to the runtime's ``DoEnqueueProgram`` of
  that program, and the runtime's ``CompleteCallbacks`` /
  ``tpu::System::Execute=>Done`` to the end of the ``engine.device_wait``
  that waited for it.

It also compares a traced tick with an untraced one of the same run
(the benchmark's ``sched.step`` spans that hold one ``engine.decode``
and no chunk, inside and before the traced stretch).

Needs the chip the cell asks for, like ``benchmark/run.py``::

    python tools/idle_account.py --workload serve-1.3b-chat-steady \\
        --seed 2147483901 --seconds 45 --out idle.json
"""
from __future__ import annotations

import argparse
import bisect
import collections
import importlib.util
import json
import os
import shutil
import statistics
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmark")

RUNTIME = ("DoEnqueueProgram", "CompleteCallbacks",
           "tpu::System::Execute=>Done")
PROGRAM = ("engine.dispatch", "engine.device_wait")


def _stats(values, scale=1e-3):
    """Median, p10 and p90 of ``values`` (nanoseconds) in microseconds."""
    values = sorted(v * scale for v in values)
    if not values:
        return None
    at = lambda q: values[min(len(values) - 1, int(q * len(values)))]
    return {"n": len(values), "median": statistics.median(values),
            "p10": at(0.1), "p90": at(0.9)}


def host_events(profile):
    """From the host plane: ``{name: [(start, end, stats)]}`` of the
    runtime's events, the program's ``engine.dispatch`` and
    ``engine.device_wait`` annotations and the benchmark's spans, each
    list sorted by start (the profiler's clock, nanoseconds)."""
    out = collections.defaultdict(list)
    for plane in profile.planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name in RUNTIME or e.name in PROGRAM \
                        or e.name.startswith("bench:"):
                    out[e.name].append((e.start_ns, e.start_ns
                                        + e.duration_ns, dict(e.stats)))
    return {k: sorted(v, key=lambda x: x[0]) for k, v in out.items()}


def device_modules(profile):
    """``{run_id: (start, end)}`` of the first device's programs."""
    out = {}
    planes = sorted((p for p in profile.planes
                     if p.name.startswith("/device:TPU:")),
                    key=lambda p: p.name)
    for line in planes[0].lines if planes else ():
        if line.name == "XLA Modules":
            for e in line.events:
                run_id = dict(e.stats).get("run_id")
                if run_id is not None:
                    out[run_id] = (e.start_ns, e.start_ns + e.duration_ns)
    return out


def runtime_ends(host, modules):
    """The ends the program cannot stamp, in nanoseconds: for every
    ``engine.dispatch``, its end to the end of the first
    ``DoEnqueueProgram`` that begins after it began (``enqueue``); for
    every ``engine.device_wait``, the start of the ``CompleteCallbacks``
    of the program the last dispatch before it enqueued, and the end of
    the ``Execute=>Done`` after that, to its end (``complete``, ``done``);
    and the same programs' device start and end on the profiler's
    alignment (``device_start``: enqueue's end to the program's start;
    ``device_end``: the program's end to its callbacks)."""
    enq = host.get("DoEnqueueProgram", [])
    enq_starts = [e[0] for e in enq]
    callbacks = {e[2].get("run_id"): e for e in host.get(
        "CompleteCallbacks", [])}
    done = host.get("tpu::System::Execute=>Done", [])
    done_starts = [e[0] for e in done]
    out = collections.defaultdict(list)
    dispatched = []         # (dispatch start, run_id of its program)
    for start, end, _ in host.get("engine.dispatch", []):
        i = bisect.bisect_left(enq_starts, start)
        if i < len(enq):
            e_start, e_end, stats = enq[i]
            dispatched.append((start, stats.get("run_id")))
            out["enqueue"].append(e_end - end)
            mod = modules.get(stats.get("run_id"))
            if mod is not None:
                out["device_start"].append(mod[0] - e_end)
    # the runtime enqueues a program some 0.1 ms after its dispatch
    # returns, often after the readback began: join by the dispatch
    d_starts = [d[0] for d in dispatched]
    for start, end, _ in host.get("engine.device_wait", []):
        i = bisect.bisect_left(d_starts, start) - 1
        if i < 0:
            continue
        run_id = dispatched[i][1]
        cb = callbacks.get(run_id)
        if cb is None:
            continue
        out["complete"].append(end - cb[0])
        j = bisect.bisect_left(done_starts, cb[0])
        if j < len(done):
            out["done"].append(end - done[j][1])
        mod = modules.get(run_id)
        if mod is not None:
            out["device_end"].append(cb[0] - mod[1])
    return out


def device_idle(profile, host, host_ops=False):
    """The first device's idle intervals inside ``bench:traced``, the
    ``bench:wait_for_arrival`` intervals there, and the window
    (nanoseconds). ``host_ops``: the CPU backend's operations stand for
    the device, as in ``harness/trace.py``."""
    from harness import trace
    lo, hi = host["bench:traced"][0][0], host["bench:traced"][-1][1]
    events = sorted(trace.device_events(profile, host_ops).items())[0][1]
    busy = trace.merged(events, lo, hi)
    edges = [lo] + [t for iv in busy for t in iv] + [hi]
    idle = [(a, b) for a, b in zip(edges[0::2], edges[1::2]) if b > a]
    waits = trace.merged([(s, e) for s, e, _ in host.get(
        "bench:wait_for_arrival", [])], lo, hi)
    return idle, waits, (lo, hi)


def overlap(a, b):
    """Total overlap of two sorted lists of disjoint intervals."""
    total, j = 0, 0
    for lo, hi in a:
        while j < len(b) and b[j][1] <= lo:
            j += 1
        k = j
        while k < len(b) and b[k][0] < hi:
            total += max(0, min(hi, b[k][1]) - max(lo, b[k][0]))
            k += 1
    return total


def ticks(run):
    """Median plain tick (a benchmark ``sched.step`` that holds one
    ``engine.decode`` and nothing of prefill) inside the traced stretch
    and in the window before it, in milliseconds."""
    recs = run.spans.records
    (t_lo, t_hi), = [(s, e) for n, s, e in recs if n == "traced"][:1]
    inner = collections.defaultdict(list)
    steps = sorted((s, e) for n, s, e in recs if n == "sched.step")
    starts = [s for s, _ in steps]
    for name, s, e in recs:
        if name in ("engine.decode", "engine.prefill_step",
                    "engine.prefill_begin"):
            i = bisect.bisect_right(starts, s) - 1
            if i >= 0 and e <= steps[i][1]:
                inner[i].append(name)
    plain = [(s, e) for i, (s, e) in enumerate(steps)
             if inner.get(i) == ["engine.decode"]]
    w_lo, w_hi = run.window
    traced = [1e3 * (e - s) for s, e in plain if t_lo <= s and e <= t_hi]
    before = [1e3 * (e - s) for s, e in plain if w_lo <= s and e < t_lo]
    return {"traced_ms": statistics.median(traced) if traced else None,
            "untraced_ms": statistics.median(before) if before else None,
            "n_traced": len(traced), "n_untraced": len(before)}


def unnamed_by_container(acc, spans):
    """Where the gaps' unnamed time lies: ``{container: ms}``, and the
    time under no program span (``"(none)"``: in a benchmark run, its
    loop between steps; its ``EngineProbe`` around ``engine.decode``
    falls in ``sched.decode_tick``'s own time)."""
    from harness import host_gaps
    inside = host_gaps.Named(spans, named=False)
    out = collections.Counter()
    for g in acc.gaps:
        there = inside.by_name(g.lo, g.hi)
        out.update(there)
        out["(none)"] += (g.hi - g.lo) - g.named_ns - sum(there.values())
    return {k: v / 1e6 for k, v in out.most_common()}


def named_in_gaps(acc, spans):
    """The named time of the gaps by span name, in milliseconds."""
    from harness import host_gaps
    named = host_gaps.Named(spans)
    out = collections.Counter()
    for g in acc.gaps:
        out.update(named.by_name(g.lo, g.hi))
    return {k: v / 1e6 for k, v in out.most_common(20)}


def account(run, xplane):
    """Every number of the module docstring, as a JSON-ready dict."""
    from harness import host_gaps, program_spans as ps, trace
    profile = trace.load(xplane)
    host = host_events(profile)
    acc = host_gaps.account(run)
    spans = ps.traced(run)
    idle, waits, (lo, hi) = device_idle(
        profile, host, host_ops=run.device["platform"] != "tpu")
    idle_ns = sum(b - a for a, b in idle)
    arrivals_ns = overlap(idle, waits)
    gaps_ns = sum(g.hi - g.lo for g in acc.gaps) if acc else 0
    ends = runtime_ends(host, device_modules(profile))
    return {
        "window_s": (hi - lo) / 1e9,
        "device_idle_s": idle_ns / 1e9,
        "device_idle_under_arrivals_s": arrivals_ns / 1e9,
        "device_idle_less_arrivals_s": (idle_ns - arrivals_ns) / 1e9,
        "program_gaps_s": gaps_ns / 1e9,
        "program_gaps": len(acc.gaps) if acc else 0,
        "program_window_s": acc.window_ns / 1e9 if acc else None,
        "skipped_dispatches": acc.skipped if acc else None,
        "left_s": (idle_ns - arrivals_ns - gaps_ns) / 1e9,
        "runtime_ends_us": {k: _stats(v) for k, v in ends.items()},
        "unnamed_in_gaps_ms": unnamed_by_container(acc, spans)
        if acc else None,
        "named_in_gaps_ms": named_in_gaps(acc, spans) if acc else None,
        "ticks": ticks(run),
    }


def traced_run(argv, steer=None):
    """One traced run of ``benchmark/run.py`` in this process; its trace
    is kept under ``.bench_out/`` (``(run, path of the .xplane.pb)``)."""
    spec = importlib.util.spec_from_file_location(
        "bench_run", os.path.join(BENCH, "run.py"))
    bench_run = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bench_run)       # puts harness/ on sys.path
    from harness import trace
    keep = os.path.join(REPO, ".bench_out", "idle_account.xplane.pb")
    os.makedirs(os.path.dirname(keep), exist_ok=True)
    read = trace.Recording.read

    def read_and_keep(self):
        if self._span is not None:
            shutil.copy(trace.find_xplane(self.dir), keep)
        read(self)

    trace.Recording.read = read_and_keep
    try:
        return bench_run.execute(argv, steer), keep
    finally:
        trace.Recording.read = read


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", required=True, help="the account, as JSON")
    args = ap.parse_args(argv)
    run, keep = traced_run(["--workload", args.workload, "--seed",
                            str(args.seed), "--seconds", str(args.seconds),
                            "--trace", "1"])
    out = account(run, keep)
    out.update(workload=args.workload, seed=args.seed,
               metrics=run.result()["metrics"])
    with open(args.out, "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps({k: out[k] for k in (
        "device_idle_less_arrivals_s", "program_gaps_s", "left_s",
        "skipped_dispatches", "ticks")}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
