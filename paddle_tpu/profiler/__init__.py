"""paddle.profiler parity (reference: ``python/paddle/profiler/``).

The profiler is the *tracing* half of the observability stack:

- :class:`Profiler` — scheduler-driven record spans; ``export()`` writes
  chrome://tracing JSON containing the ``RecordEvent`` spans emitted by
  the instrumented hot paths (the train steps, the eager collectives,
  the serving scheduler and engine) plus ``"ph": "C"`` counter tracks
  (device memory).
- :class:`RecordEvent` — the one span recorder: kept while a
  ``Profiler`` records **or** a jax device trace is being taken (then
  also a jax trace annotation on the trace's clock), one predicate
  otherwise; :func:`~paddle_tpu.profiler.utils.recorded_spans` reads
  the records (a tree by ``parent_id``) without draining them.
- :func:`~paddle_tpu.profiler.utils.record_counter` — add a counter
  sample to the active record span.
- ``tools/trace_summary.py`` — post-hoc aggregate table over an exported
  trace (shares ``profiler.profiler.aggregate_events`` with
  ``Profiler.summary``).

The *metrics* half (Counter/Gauge/Histogram registry, Prometheus/JSONL
exposition, per-run JSONL telemetry and ``run_summary.json``) lives in
:mod:`paddle_tpu.observability`; see the README "Observability" section.

Compile-time findings join the same streams: :mod:`paddle_tpu.analysis`
lint diagnostics (host syncs that would stall these traces, recompile
hazards behind long ``jit build`` spans, rank-divergent collectives) are
emitted as ``analysis_diagnostic`` runlog events — see README "Static
analysis".
"""
from .profiler import (  # noqa: F401
    Profiler, ProfilerState, ProfilerTarget, make_scheduler,
    export_chrome_tracing, SummaryView,
)
from .utils import RecordEvent, load_profiler_result  # noqa: F401
from .timer import benchmark  # noqa: F401
