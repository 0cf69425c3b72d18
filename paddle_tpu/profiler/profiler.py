"""Profiler with scheduler states + chrome-trace export.

Parity: ``/root/reference/python/paddle/profiler/profiler.py`` (:79
ProfilerState, :117 make_scheduler, :215 export_chrome_tracing, :344
Profiler, :838 summary). TPU-native redesign: the CUPTI device tracer is
replaced by ``jax.profiler`` (XPlane/TensorBoard trace of XLA ops); the host
tracer is the RecordEvent buffer in ``utils.py``. ``export_chrome_tracing``
emits chrome://tracing JSON from host events (same output contract as the
reference's chrometracing_logger.cc); device-side analysis is read in
TensorBoard from the jax trace directory.
"""
from __future__ import annotations

import json
import os
import time
from collections import defaultdict
from enum import Enum

from . import utils as _utils


class ProfilerState(Enum):
    CLOSED = 0
    READY = 1
    RECORD = 2
    RECORD_AND_RETURN = 3  # record and emit the trace at this step's end


class ProfilerTarget(Enum):
    CPU = 0
    GPU = 1
    XPU = 2
    CUSTOM_DEVICE = 3
    TPU = 4


def make_scheduler(*, closed: int, ready: int, record: int, repeat: int = 0,
                   skip_first: int = 0):
    """State machine over step numbers (profiler.py:117 parity):
    skip_first CLOSED steps, then cycles of [closed × CLOSED, ready × READY,
    record × RECORD(last=RECORD_AND_RETURN)], repeated ``repeat`` times
    (0 = forever)."""
    assert record > 0, "record span must be positive"
    span = closed + ready + record

    def scheduler(step: int) -> ProfilerState:
        if step < skip_first:
            return ProfilerState.CLOSED
        step -= skip_first
        cycle = step // span
        if repeat and cycle >= repeat:
            return ProfilerState.CLOSED
        pos = step % span
        if pos < closed:
            return ProfilerState.CLOSED
        if pos < closed + ready:
            return ProfilerState.READY
        if pos == span - 1:
            return ProfilerState.RECORD_AND_RETURN
        return ProfilerState.RECORD

    return scheduler


def _default_state_fn(step: int) -> ProfilerState:
    return ProfilerState.RECORD  # profile everything between start and stop


def export_chrome_tracing(dir_name: str, worker_name: str = None):
    """Returns an on_trace_ready callback writing chrome trace json files."""
    os.makedirs(dir_name, exist_ok=True)

    def handle(prof: "Profiler"):
        name = worker_name or f"host_{os.getpid()}"
        path = os.path.join(
            dir_name, f"{name}_time_{int(time.time() * 1000)}.paddle_trace.json")
        prof.export(path, format="json")

    return handle


def _time_scale(time_unit: str):
    """ns -> requested unit multiplier. Accepts s|ms|us|ns."""
    table = {"s": (1e-9, "s"), "ms": (1e-6, "ms"),
             "us": (1e-3, "us"), "ns": (1.0, "ns")}
    if time_unit not in table:
        raise ValueError(f"time_unit must be one of {sorted(table)}, "
                         f"got {time_unit!r}")
    return table[time_unit]


def aggregate_events(name_dur_ns):
    """Fold (name, duration_ns) pairs into {name: (calls, total_ns)} —
    shared by ``Profiler.summary`` and ``tools/trace_summary.py``."""
    agg = defaultdict(lambda: [0, 0.0])
    for name, dur_ns in name_dur_ns:
        a = agg[name]
        a[0] += 1
        a[1] += dur_ns
    return {k: (v[0], v[1]) for k, v in agg.items()}


def format_agg_table(agg, time_unit="ms", top=None):
    """Render the aggregate dict as table lines (descending total time)."""
    scale, unit = _time_scale(time_unit)
    rows = sorted(agg.items(), key=lambda kv: -kv[1][1])
    if top is not None:
        rows = rows[:top]
    width = max([len(k) for k in agg] + [10]) + 2
    lines = [f"{'Name':<{width}}{'Calls':>8}{f'Total({unit})':>14}"
             f"{f'Avg({unit})':>14}",
             "-" * (width + 36)]
    for name, (calls, total_ns) in rows:
        total = total_ns * scale
        lines.append(f"{name:<{width}}{calls:>8}{total:>14.3f}"
                     f"{total / calls:>14.3f}")
    return lines


class SummaryView(Enum):
    DeviceView = 0
    OverView = 1
    ModelView = 2
    DistributedView = 3
    KernelView = 4
    OperatorView = 5
    MemoryView = 6
    MemoryManipulationView = 7
    UDFView = 8


class Profiler:
    """Scheduler-driven profiler (profiler.py:344 parity).

    Usage::

        with profiler.Profiler(scheduler=(2, 5)) as p:
            for batch in loader:
                train_step(batch)
                p.step()
        p.summary()
    """

    def __init__(self, *, targets=None, scheduler=None, on_trace_ready=None,
                 record_shapes=False, profile_memory=False, timer_only=False,
                 emit_nvtx=False, custom_device_types=None, with_flops=False):
        self.targets = targets or [ProfilerTarget.CPU, ProfilerTarget.TPU]
        if scheduler is None:
            self._state_fn = _default_state_fn
        elif isinstance(scheduler, (tuple, list)):
            start, end = scheduler
            self._state_fn = make_scheduler(
                closed=max(start, 0), ready=0, record=end - start, repeat=1)
        else:
            self._state_fn = scheduler
        self.on_trace_ready = on_trace_ready
        self.timer_only = timer_only
        self.step_num = 0
        self.current_state = ProfilerState.CLOSED
        self._events = []            # drained utils.Span records
        self._counters = []          # drained (name, ts_ns, value) samples
        self._jax_trace_dir = None
        self._jax_tracing = False
        self._step_t0 = None
        self._step_times = []

    # ----------------------------------------------------------- lifecycle
    def start(self):
        self.current_state = self._state_fn(self.step_num)
        self._apply_state()
        self._step_t0 = time.perf_counter()
        return self

    def stop(self):
        if self._step_t0 is not None:
            # flush the final in-flight step: without this the last step
            # between the latest step() and stop() is missing from
            # summary(). Two non-steps are excluded: a stop() right after
            # step() (step-at-end-of-loop idiom, sub-0.1ms residue) and
            # span-only sessions that never called step() at all.
            dt = time.perf_counter() - self._step_t0
            if self._step_times and dt >= 1e-4:
                self._step_times.append(dt)
            self._step_t0 = None
        if self.current_state in (ProfilerState.RECORD,
                                  ProfilerState.RECORD_AND_RETURN):
            self._end_record()
            if self.on_trace_ready:
                self.on_trace_ready(self)
        self.current_state = ProfilerState.CLOSED
        _utils._set_collecting(False)

    def step(self, num_samples=None):
        if self._step_t0 is not None:
            self._step_times.append(time.perf_counter() - self._step_t0)
        prev = self.current_state
        if prev == ProfilerState.RECORD_AND_RETURN:
            self._end_record()
            if self.on_trace_ready:
                self.on_trace_ready(self)
        self.step_num += 1
        self.current_state = self._state_fn(self.step_num)
        if prev != self.current_state or \
                prev == ProfilerState.RECORD_AND_RETURN:
            self._apply_state()
        self._step_t0 = time.perf_counter()

    def __enter__(self):
        return self.start()

    def __exit__(self, *exc):
        self.stop()
        return False

    def _apply_state(self):
        recording = self.current_state in (
            ProfilerState.RECORD, ProfilerState.RECORD_AND_RETURN)
        _utils._set_collecting(recording and not self.timer_only)
        want_jax = recording and not self.timer_only and \
            ProfilerTarget.TPU in self.targets
        if want_jax and not self._jax_tracing:
            try:
                import jax
                self._jax_trace_dir = os.environ.get(
                    "PADDLE_PROFILER_JAX_DIR", "/tmp/paddle_tpu_jax_trace")
                jax.profiler.start_trace(self._jax_trace_dir)
                self._jax_tracing = True
            except Exception:
                self._jax_tracing = False

    def _end_record(self):
        self._events.extend(_utils._drain_events())
        self._counters.extend(_utils._drain_counters())
        _utils._set_collecting(False)
        if self._jax_tracing:
            try:
                import jax
                jax.profiler.stop_trace()
            except Exception:
                pass
            self._jax_tracing = False

    # ------------------------------------------------------------- analysis
    def export(self, path: str, format: str = "json"):
        """Write collected host events (spans + counter samples) as
        chrome://tracing JSON."""
        if format == "pb":
            raise NotImplementedError(
                "protobuf export is not implemented on this stack; use "
                "format='json' (chrome://tracing / perfetto readable), or "
                "for machine-readable per-op measured-vs-predicted data "
                "use the op-attribution JSON "
                "(paddle_tpu.observability.opprof — "
                "OpAttribution.save('attribution.json'), readable by "
                "tools/perf_doctor.py --ops and tools/trace_summary.py)")
        assert format == "json", format
        events = []
        for ev in self._events:
            events.append({
                "name": ev.name, "ph": "X", "cat": ev.type,
                "pid": os.getpid(), "tid": ev.tid,
                "ts": ev.start_ns / 1e3,  # µs
                "dur": (ev.end_ns - ev.start_ns) / 1e3,
                "args": dict(ev.attrs, span_id=ev.span_id,
                             parent_id=ev.parent_id),
            })
        for name, ts, value in self._counters:
            events.append({
                "name": name, "ph": "C", "cat": "Counter",
                "pid": os.getpid(), "ts": ts / 1e3,
                "args": {"value": value},
            })
        payload = {"traceEvents": events,
                   "displayTimeUnit": "ms",
                   "metadata": {"tool": "paddle_tpu.profiler",
                                "jax_trace_dir": self._jax_trace_dir}}
        dirname = os.path.dirname(path)
        if dirname:
            os.makedirs(dirname, exist_ok=True)
        with open(path, "w") as f:
            json.dump(payload, f)
        return path

    def summary(self, sorted_by=None, op_detail=True, thread_sep=False,
                time_unit="ms", views=None):
        """Print aggregated host-event table + step-time stats in the
        requested ``time_unit`` ('s'|'ms'|'us'|'ns'); returns the aggregate
        dict (profiler_statistic.py condensed; totals keyed ``total_ms``
        for stability plus ``total_<unit>`` for the requested unit)."""
        agg = aggregate_events(
            (ev.name, ev.end_ns - ev.start_ns) for ev in self._events)
        lines = format_agg_table(agg, time_unit=time_unit)
        if self._step_times:
            scale, unit = _time_scale(time_unit)
            st = [s * 1e9 * scale for s in self._step_times]  # s -> unit
            lines.append(lines[1])
            lines.append(
                f"steps: {len(st)}  avg: {sum(st) / len(st):.3f}{unit}  "
                f"min: {min(st):.3f}{unit}  max: {max(st):.3f}{unit}")
        print("\n".join(lines))
        scale, unit = _time_scale(time_unit)
        # total_ms uses the same expression as the dynamic key so the
        # time_unit="ms" overwrite is bit-identical, not off by one ulp
        return {k: {"calls": calls, "total_ms": ns * 1e-6,
                    f"total_{unit}": ns * scale}
                for k, (calls, ns) in agg.items()}
