"""Hot-path instrumentation helpers.

One place defines the metric names the framework emits, so producers
(``ParallelTrainStep``, ``PipelineParallel``, ``distributed.collective``,
the elastic launcher) and consumers (``merge_run_dir``, bench.py, the
Prometheus exposition) agree on the schema:

====================================  =========  =============================
metric                                type       labels
====================================  =========  =============================
paddle_train_step_seconds             histogram  path={parallel,pipeline,fit}
paddle_tokens_per_sec                 gauge      path
paddle_train_mfu                      gauge      path
paddle_loss_scale                     gauge      —
paddle_found_inf_total                counter    —
paddle_loss_scale_skips_total         counter    —
paddle_jit_compile_total              counter    what
paddle_jit_compile_seconds_total      counter    what
paddle_collective_calls_total         counter    op, group, dtype
paddle_collective_bytes_total         counter    op, group, dtype
paddle_collective_compressed_bytes_total counter op, group,
                                                 wire={int8,bf16}
paddle_collective_compression_ratio   gauge      op, group
paddle_device_memory_bytes            gauge      —
paddle_device_peak_memory_bytes       gauge      —
paddle_elastic_restarts_total         counter    —
paddle_elastic_preemption_resumes_total counter  —
paddle_elastic_generation             gauge      —
paddle_elastic_lease_age_seconds      gauge      host
paddle_worker_exit_total              counter    code
paddle_checkpoint_saves_total         counter    mode={async,sync,emergency},
                                                 result={ok,error}
paddle_checkpoint_save_seconds        histogram  mode
paddle_checkpoint_bytes_total         counter    mode
paddle_checkpoint_in_flight           gauge      —
paddle_checkpoint_restores_total      counter    result={ok,fallback,corrupt}
paddle_store_retries_total            counter    op
paddle_anomalies_total                counter    kind={step_time_spike,
                                                 loss_spike,loss_nan,
                                                 mfu_drift,memory_creep,
                                                 loss_scale_thrash},
                                                 path
paddle_analysis_predicted_step_ms     gauge      target
paddle_analysis_predicted_peak_hbm_mb gauge      target
paddle_analysis_predicted_mfu         gauge      target
paddle_cost_model_drift_ratio         gauge      family={dot,elementwise,
                                                 scatter_gather,collective,
                                                 pallas,other}
paddle_serving_requests_total         counter    event={submitted,admitted,
                                                 finished,rejected,
                                                 migrated_in,migrated_out};
                                                 rejected also carries
                                                 reason={max_new<1,too_long,
                                                 queue_full,pool_too_small}
paddle_serving_queue_depth            gauge      —
paddle_serving_ttft_seconds           histogram  —
paddle_serving_queue_wait_seconds     histogram  —
paddle_serving_prefill_seconds        histogram  —
paddle_serving_per_token_seconds      histogram  —
paddle_serving_tokens_out_total       counter    —
paddle_serving_kv_pages_in_use        gauge      —
paddle_serving_slo_violations_total   counter    slo={ttft_p95,per_token_p99,
                                                 queue_wait_p95}
paddle_serving_slo_burn_rate          gauge      slo
paddle_serving_goodput_tokens_total   counter    —
paddle_serving_prefix_cache_hits_total counter   —
paddle_serving_prefix_tokens_reused_total counter —
paddle_serving_prefill_chunks_total   counter    —
paddle_fleet_replicas                 gauge      state={active,draining}
paddle_fleet_router_queue_depth       gauge      —
paddle_fleet_routed_total             counter    outcome={affinity,fallback,
                                                 round_robin,least_loaded}
paddle_fleet_requeued_total           counter    —
paddle_fleet_scale_events_total       counter    action={scale_out,scale_in}
paddle_fleet_rpc_retries_total        counter    op
paddle_fleet_migrations_total         counter    outcome={completed,failed,
                                                 requeue_fallback}
paddle_fleet_migrated_bytes_total     counter    —
paddle_lock_wait_seconds              histogram  lock
paddle_lock_contention_total          counter    lock
====================================  =========  =============================

Serving decode steps additionally ride ``record_train_step`` with
``path="serving"`` (and timed prefills with ``path="serving_prefill"``),
so the flight recorder and the online anomaly monitors cover the serving
engine exactly like training. Request-scoped serving telemetry (per-
request spans, SLO windows) lives in :mod:`.reqtrace` / :mod:`.slo`.

Everything here must stay off the device critical path: increments are a
dict lookup + float add; the memory sampler reads allocator stats (cheap)
or sweeps live arrays (CPU fallback) once per step.
"""
from __future__ import annotations

import os
import time

from .metrics import get_registry

# step-time buckets from 0.5ms to 2min, tuned around training step scales
STEP_BUCKETS = (0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1,
                0.25, 0.5, 1.0, 2.5, 5.0, 15.0, 60.0, 120.0)
# lock-wait buckets from 1µs to 10s: uncontended acquires land in the
# first buckets, anything past ~100ms is a contention finding
LOCK_WAIT_BUCKETS = (1e-6, 1e-5, 1e-4, 5e-4, 1e-3, 5e-3, 0.01, 0.05,
                     0.1, 0.5, 1.0, 5.0, 10.0)


def lock_wait_histogram():
    """Per-lock acquire wait (runtime lock witness,
    ``PADDLE_LOCK_WITNESS=1``)."""
    return get_registry().histogram(
        "paddle_lock_wait_seconds",
        "seconds spent waiting to acquire a witnessed lock",
        buckets=LOCK_WAIT_BUCKETS)


def lock_contention_counter():
    """Contended acquires (a non-blocking probe failed first)."""
    return get_registry().counter(
        "paddle_lock_contention_total",
        "witnessed lock acquires that had to wait")


def step_seconds():
    return get_registry().histogram(
        "paddle_train_step_seconds",
        "wall-clock seconds per training step", buckets=STEP_BUCKETS)


def tokens_per_sec():
    return get_registry().gauge(
        "paddle_tokens_per_sec", "training throughput, tokens (or samples)/s")


def train_mfu():
    return get_registry().gauge(
        "paddle_train_mfu", "model flops utilization vs chip peak")


def loss_scale_gauge():
    return get_registry().gauge(
        "paddle_loss_scale", "current dynamic loss scale")


def found_inf_counter():
    return get_registry().counter(
        "paddle_found_inf_total", "steps whose gradients contained inf/nan")


def skip_counter():
    return get_registry().counter(
        "paddle_loss_scale_skips_total",
        "optimizer updates skipped on overflow")


def compile_counter():
    return get_registry().counter(
        "paddle_jit_compile_total", "jit build/compile invocations")


def compile_seconds():
    return get_registry().counter(
        "paddle_jit_compile_seconds_total",
        "wall-clock seconds spent in jit build/compile")


def collective_calls():
    return get_registry().counter(
        "paddle_collective_calls_total", "eager collective op invocations")


def collective_bytes():
    return get_registry().counter(
        "paddle_collective_bytes_total",
        "bytes moved through eager collective ops (payload size x ranks "
        "for gather-shaped ops; WIRE bytes for compressed ops)")


def collective_compressed_bytes():
    return get_registry().counter(
        "paddle_collective_compressed_bytes_total",
        "wire bytes moved by compressed collectives, by wire dtype")


def collective_compression_ratio():
    return get_registry().gauge(
        "paddle_collective_compression_ratio",
        "logical/wire byte ratio of the last compressed collective per "
        "op (≈3.9x for f32→int8 with 256-chunk scales, ≈2x for bf16)")


def restarts_counter():
    return get_registry().counter(
        "paddle_elastic_restarts_total", "elastic kill+respawn cycles")


def generation_gauge():
    return get_registry().gauge(
        "paddle_elastic_generation", "current launch generation")


def lease_age_gauge():
    return get_registry().gauge(
        "paddle_elastic_lease_age_seconds",
        "seconds since each worker lease was last refreshed")


def worker_exit_counter():
    return get_registry().counter(
        "paddle_worker_exit_total", "worker exits by code")


def preemption_resumes_counter():
    return get_registry().counter(
        "paddle_elastic_preemption_resumes_total",
        "relaunches after a preemption emergency save (exempt from "
        "max_restarts)")


def checkpoint_saves_counter():
    return get_registry().counter(
        "paddle_checkpoint_saves_total", "checkpoint save attempts")


def checkpoint_save_seconds():
    return get_registry().histogram(
        "paddle_checkpoint_save_seconds",
        "wall-clock seconds persisting one checkpoint",
        buckets=STEP_BUCKETS)


def checkpoint_bytes_counter():
    return get_registry().counter(
        "paddle_checkpoint_bytes_total",
        "bytes of checkpoint state persisted")


def checkpoint_in_flight():
    return get_registry().gauge(
        "paddle_checkpoint_in_flight",
        "1 while an async checkpoint write is in progress")


def checkpoint_restores_counter():
    return get_registry().counter(
        "paddle_checkpoint_restores_total",
        "checkpoint restore attempts by outcome")


def store_retries_counter():
    return get_registry().counter(
        "paddle_store_retries_total",
        "TCPStore client ops retried on transient socket errors")


def anomalies_counter():
    return get_registry().counter(
        "paddle_anomalies_total",
        "online step anomalies by kind (spikes, NaN loss, MFU drift, "
        "memory creep)")


def predicted_step_ms_gauge():
    return get_registry().gauge(
        "paddle_analysis_predicted_step_ms",
        "static-cost-model roofline step time prediction")


def predicted_peak_hbm_gauge():
    return get_registry().gauge(
        "paddle_analysis_predicted_peak_hbm_mb",
        "static liveness-model peak HBM prediction")


def predicted_mfu_gauge():
    return get_registry().gauge(
        "paddle_analysis_predicted_mfu",
        "static-cost-model MFU prediction vs chip peak")


def cost_model_drift_gauge():
    return get_registry().gauge(
        "paddle_cost_model_drift_ratio",
        "measured/predicted time ratio per op family from the latest "
        "op attribution (1.0 = model exact; outside the PTCM001 band "
        "means the cost model needs recalibration)")


def serving_requests_counter():
    return get_registry().counter(
        "paddle_serving_requests_total",
        "serving requests by lifecycle event")


def serving_queue_depth_gauge():
    return get_registry().gauge(
        "paddle_serving_queue_depth",
        "requests waiting for admission to the decode batch")


def serving_ttft_histogram():
    return get_registry().histogram(
        "paddle_serving_ttft_seconds",
        "submit-to-first-token latency per admitted request",
        buckets=STEP_BUCKETS)


def serving_tokens_out_counter():
    return get_registry().counter(
        "paddle_serving_tokens_out_total",
        "tokens emitted by the serving engine")


def serving_kv_pages_gauge():
    return get_registry().gauge(
        "paddle_serving_kv_pages_in_use",
        "KV-cache pool pages currently allocated to live sequences")


def serving_queue_wait_histogram():
    return get_registry().histogram(
        "paddle_serving_queue_wait_seconds",
        "submit-to-admission wait per admitted request",
        buckets=STEP_BUCKETS)


def serving_prefill_histogram():
    return get_registry().histogram(
        "paddle_serving_prefill_seconds",
        "wall-clock seconds per request prefill (page alloc + bucketed "
        "forward + first sampled token)", buckets=STEP_BUCKETS)


def serving_per_token_histogram():
    return get_registry().histogram(
        "paddle_serving_per_token_seconds",
        "decode-tick latency per emitted token (one observation per "
        "active request per step)", buckets=STEP_BUCKETS)


def serving_slo_violations():
    return get_registry().counter(
        "paddle_serving_slo_violations_total",
        "rolling-window SLO violations by target (see observability.slo)")


def serving_slo_burn_rate_gauge():
    return get_registry().gauge(
        "paddle_serving_slo_burn_rate",
        "error-budget burn rate per SLO (1.0 = burning exactly at "
        "budget)")


def serving_goodput_tokens_counter():
    return get_registry().counter(
        "paddle_serving_goodput_tokens_total",
        "tokens from requests that met every configured SLO target")


def serving_prefix_hits_counter():
    return get_registry().counter(
        "paddle_serving_prefix_cache_hits_total",
        "admissions whose prompt reused >0 cached prefix tokens")


def serving_prefix_tokens_reused_counter():
    return get_registry().counter(
        "paddle_serving_prefix_tokens_reused_total",
        "prompt tokens served from the prefix cache instead of "
        "prefilled (skipped prefill work)")


def serving_prefill_chunks_counter():
    return get_registry().counter(
        "paddle_serving_prefill_chunks_total",
        "chunk-program invocations (chunked prefill interleaves these "
        "with decode ticks)")


def fleet_replicas_gauge():
    return get_registry().gauge(
        "paddle_fleet_replicas",
        "serving-engine replicas by state (active / draining)")


def fleet_router_queue_gauge():
    return get_registry().gauge(
        "paddle_fleet_router_queue_depth",
        "requests waiting at the fleet router for a routable replica")


def fleet_routed_counter():
    return get_registry().counter(
        "paddle_fleet_routed_total",
        "routing decisions by outcome (affinity = preferred replica "
        "taken, fallback = preferred saturated -> least-loaded)")


def fleet_requeued_counter():
    return get_registry().counter(
        "paddle_fleet_requeued_total",
        "in-flight requests re-enqueued at the router after their "
        "replica died (idempotent by request id; zero failed requests)")


def fleet_scale_events_counter():
    return get_registry().counter(
        "paddle_fleet_scale_events_total",
        "autoscaler actions executed (SLO-burn scale-out / idle "
        "drain-then-retire scale-in)")


def fleet_rpc_retries_counter():
    return get_registry().counter(
        "paddle_fleet_rpc_retries_total",
        "fleet control-plane RPC retries by op (transient socket "
        "errors, exponential backoff with jitter)")


def fleet_migrations_counter():
    return get_registry().counter(
        "paddle_fleet_migrations_total",
        "live KV-page migrations by outcome (completed / failed / "
        "requeue_fallback when a wedged replica forces requeue-by-rid)")


def fleet_migrated_bytes_counter():
    return get_registry().counter(
        "paddle_fleet_migrated_bytes_total",
        "KV-page payload bytes streamed between replicas by live "
        "migration (uncached suffix only)")


def serving_deadline_exceeded_counter():
    return get_registry().counter(
        "paddle_serving_deadline_exceeded_total",
        "requests cancelled at tick because their deadline expired "
        "(queued, prefilling, or mid-decode; pages reclaimed, prefix "
        "cache still published)")


def serving_overload_mode_gauge():
    return get_registry().gauge(
        "paddle_serving_overload_mode",
        "overload-control mode (0 = healthy, 1 = brownout, 2 = "
        "shedding), driven by SLO burn rates")


def serving_degraded_seconds_counter():
    return get_registry().counter(
        "paddle_serving_degraded_seconds_total",
        "wall-clock seconds spent serving in brownout or shedding mode")


def fleet_breaker_events_counter():
    return get_registry().counter(
        "paddle_fleet_breaker_events_total",
        "router circuit-breaker transitions per replica (open on "
        "consecutive RPC failures, close on half-open probe success)")


def fleet_hedged_submits_counter():
    return get_registry().counter(
        "paddle_fleet_hedged_submits_total",
        "submits re-dispatched to the next-best affinity candidate "
        "after the preferred replica timed out (idempotent by rid)")


def record_predicted(step_ms=None, peak_hbm_mb=None, mfu=None,
                     target="step"):
    """Publish static-analysis predictions (cost/memory passes) as
    gauges, so dashboards can chart predicted-vs-measured drift."""
    if step_ms is not None:
        predicted_step_ms_gauge().set(float(step_ms), target=target)
    if peak_hbm_mb is not None:
        predicted_peak_hbm_gauge().set(float(peak_hbm_mb), target=target)
    if mfu is not None:
        predicted_mfu_gauge().set(float(mfu), target=target)


# ---------------------------------------------------------------- recorders

_FLUSH_INTERVAL_S = 5.0
_last_flush = 0.0


def record_train_step(seconds: float, tokens: int | None = None,
                      flops_per_token: float | None = None,
                      path: str = "parallel", loss=None, found_inf=None,
                      loss_scale=None):
    """Per-step accounting: step-time histogram + derived throughput/MFU,
    plus the always-on flight-recorder ring and the online anomaly
    monitors (``loss`` may be a live device scalar — it is stored raw /
    resolved with one step of lag, never blocking this path). Under a
    telemetry-enabled launch (``PADDLE_TELEMETRY_DIR``) this also
    snapshots the registry into the rank's JSONL every few seconds, so a
    SIGKILLed worker still leaves near-current telemetry behind (the
    snapshot write is atomic via rename)."""
    global _last_flush, _last_wire_dtype
    # consume the wire tag: it means "a compressed collective ran since
    # the PREVIOUS step record", not "compression was ever on" — a step
    # with no compressed traffic must record wire_dtype=None
    wire = _last_wire_dtype
    _last_wire_dtype = None
    step_seconds().observe(seconds, path=path)
    tps = mfu = None
    if tokens and seconds > 0:
        tps = tokens / seconds
        tokens_per_sec().set(tps, path=path)
        if flops_per_token:
            mfu = tps * flops_per_token / peak_flops_per_chip()
            train_mfu().set(mfu, path=path)
    reg = get_registry()
    mem_gauge = reg.get("paddle_device_memory_bytes")
    mem = mem_gauge.value if mem_gauge is not None else None
    from . import anomaly, flight
    flight.get_flight_recorder().record_step(
        seconds, loss=loss, tokens_per_sec=tps, mfu=mfu,
        found_inf=found_inf, loss_scale=loss_scale, memory_bytes=mem,
        collective_bytes=_collective_bytes_cum(reg),
        wire_dtype=wire, path=path)
    if anomaly.monitoring_enabled():
        anomaly.get_monitor(path).observe(
            seconds, loss=loss, mfu=mfu, memory_bytes=mem,
            found_inf=found_inf)
    from .runlog import get_run_logger
    logger = get_run_logger()
    if logger is not None:
        now = time.monotonic()
        if now - _last_flush > _FLUSH_INTERVAL_S:
            _last_flush = now
            logger.flush_metrics()


def _collective_bytes_cum(reg) -> float | None:
    """Cumulative eager-collective wire bytes (sum over op/group/dtype
    series) — a handful of dict reads, cheap enough for the step path."""
    c = reg.get("paddle_collective_bytes_total")
    if c is None:
        return None
    return sum(state["value"] for _, state in c.collect())


def record_checkpoint_save(seconds: float, nbytes: int, mode: str = "async"):
    """Per-save accounting (duration histogram + bytes); also snapshots
    the registry into the rank's runlog so a preempted worker leaves the
    save telemetry behind."""
    checkpoint_save_seconds().observe(seconds, mode=mode)
    if nbytes:
        checkpoint_bytes_counter().inc(float(nbytes), mode=mode)
    from .runlog import get_run_logger
    logger = get_run_logger()
    if logger is not None:
        try:
            logger.flush_metrics()
        except Exception:
            pass


def record_compile(seconds: float, what: str):
    compile_counter().inc(what=what)
    compile_seconds().inc(seconds, what=what)
    from . import flight
    flight.get_flight_recorder().record(
        "compile", what=what, seconds=round(float(seconds), 4))


_last_wire_dtype = None  # most recent compressed wire dtype (flight tag)


def record_collective(op: str, nbytes: int, group=None, dtype=None,
                      wire_dtype=None, wire_nbytes=None):
    """Account one eager collective. ``nbytes`` is the LOGICAL payload;
    for a compressed op, ``wire_nbytes`` is what actually crosses the
    interconnect — the bytes-moved counter records wire bytes (so the
    perf doctor's comm bucket reconciles post-compression), while the
    compressed-bytes counter and compression-ratio gauge carry the
    compression view by wire dtype."""
    global _last_wire_dtype
    labels = {"op": op,
              "group": str(getattr(group, "axis_name", group or "world")),
              "dtype": str(dtype)}
    collective_calls().inc(**labels)
    moved = wire_nbytes if wire_nbytes is not None else nbytes
    if moved:
        collective_bytes().inc(float(moved), **labels)
    if wire_dtype and wire_nbytes is not None:
        _last_wire_dtype = str(wire_dtype)
        collective_compressed_bytes().inc(
            float(wire_nbytes), op=op, group=labels["group"],
            wire=str(wire_dtype))
        if nbytes:
            collective_compression_ratio().set(
                float(nbytes) / max(float(wire_nbytes), 1.0),
                op=op, group=labels["group"])



_LIVE_ARRAY_SAMPLE_EVERY = 10
_mem_calls = 0
_mem_source = None  # discovered on first sample


def sample_device_memory(chrome_counter: bool = True) -> dict | None:
    """Read device memory stats into the registry gauges; when a profiler
    record span is active, also emit a chrome-trace counter sample
    (``"ph": "C"``) so the memory track lines up with the event spans.

    Allocator-backed devices (TPU/GPU) sample every call — the read is a
    stat fetch. The CPU fallback sweeps every live jax array, O(n) python
    work that must stay off the hot path, so it samples every
    ``_LIVE_ARRAY_SAMPLE_EVERY``-th call unless a profiler record span is
    active (trace fidelity wins there). Returns None on skipped calls."""
    global _mem_calls, _mem_source
    _mem_calls += 1
    if _mem_source == "live_arrays":
        from ..profiler import utils as _putils
        if not _putils._collecting and \
                _mem_calls % _LIVE_ARRAY_SAMPLE_EVERY != 1:
            return None
    from .. import device as device_mod
    stats = device_mod.memory_stats()
    _mem_source = stats["source"]
    reg = get_registry()
    reg.gauge("paddle_device_memory_bytes",
              "bytes currently allocated on device").set(
        stats["allocated_bytes"])
    reg.gauge("paddle_device_peak_memory_bytes",
              "peak bytes allocated on device").set(
        stats["peak_allocated_bytes"])
    if chrome_counter:
        from ..profiler.utils import record_counter
        record_counter("device_memory_bytes", stats["allocated_bytes"])
    return stats


# Chip roofline table (public TPU spec sheets, bf16 peak / HBM / ICI).
# ``ici_bw`` is the per-chip aggregate interconnect bandwidth the ring
# collective model divides wire bytes by; ``hbm_gb`` is the per-chip
# capacity the OOM-before-compile gate defaults to. The cpu row is a
# fallback — chip_specs() replaces its compute/bandwidth constants with
# measured ones from a one-shot microbenchmark on first use, so CPU
# smoke-run rooflines reflect the actual host rather than fantasy.
CHIP_SPECS = {
    "v4":  dict(peak_flops=275e12, hbm_bw=1228e9, ici_bw=268e9, hbm_gb=32),
    "v5p": dict(peak_flops=459e12, hbm_bw=2765e9, ici_bw=540e9, hbm_gb=95),
    "v5e": dict(peak_flops=197e12, hbm_bw=819e9, ici_bw=186e9, hbm_gb=16),
    "v5 lite": dict(peak_flops=197e12, hbm_bw=819e9, ici_bw=186e9,
                    hbm_gb=16),
    "v6e": dict(peak_flops=918e12, hbm_bw=1640e9, ici_bw=367e9, hbm_gb=32),
    "v6":  dict(peak_flops=918e12, hbm_bw=1640e9, ici_bw=367e9, hbm_gb=32),
    "cpu": dict(peak_flops=1e12, hbm_bw=50e9, ici_bw=10e9, hbm_gb=8),
}

_cpu_bench_cache: dict | None = None


def _cpu_microbench() -> dict:
    """Measured compute/bandwidth constants for the host CPU, replacing
    the table's placeholder row. One small GEMM (BLAS f32 peak proxy)
    and one large-buffer copy (streaming bandwidth proxy), both clamped
    to sane host ranges so a noisy scheduler can't produce a roofline
    that is obviously wrong. Runs once per process (~10 ms), cached."""
    global _cpu_bench_cache
    if _cpu_bench_cache is not None:
        return _cpu_bench_cache
    import numpy as np
    n, reps = 384, 4
    a = np.full((n, n), 1.0 / n, np.float32)
    b = np.full((n, n), 0.5, np.float32)
    (a @ b)  # warm BLAS up outside the timed window
    t0 = time.perf_counter()
    for _ in range(reps):
        (a @ b)
    gemm_s = max(time.perf_counter() - t0, 1e-7)
    flops = 2.0 * n ** 3 * reps / gemm_s
    src = np.zeros(4 << 20, np.float32)  # 16 MiB, beyond typical L2
    dst = np.empty_like(src)
    np.copyto(dst, src)
    t0 = time.perf_counter()
    for _ in range(reps):
        np.copyto(dst, src)
    copy_s = max(time.perf_counter() - t0, 1e-7)
    bw = 2.0 * src.nbytes * reps / copy_s  # read + write streams
    try:
        ram_gb = os.sysconf("SC_PHYS_PAGES") * os.sysconf("SC_PAGE_SIZE") \
            / float(1 << 30)
    except (ValueError, OSError, AttributeError):
        ram_gb = 8.0
    _cpu_bench_cache = dict(
        peak_flops=min(max(flops, 1e10), 5e13),
        hbm_bw=min(max(bw, 1e9), 2e11),
        hbm_gb=min(max(ram_gb, 1.0), 64.0),
    )
    return _cpu_bench_cache


def chip_specs(kind: str | None = None) -> dict:
    """Roofline constants for ``kind`` (or the attached device when None):
    ``{name, peak_flops, hbm_bw, ici_bw, hbm_gb}``. Shared by the MFU
    gauge, bench.py, and the static cost model, so predicted and measured
    MFU always divide by the same peak.

    ``PADDLE_CHIP_KIND`` overrides the device probe so CPU smoke and
    no-backend rounds can price any chip without code edits (an explicit
    ``kind`` argument still wins). When ``PADDLE_COST_CALIBRATION``
    names a fitted calibration for this chip, its constants are merged
    in (``mxu_efficiency`` override, achieved-HBM-BW scaling) and the
    row carries the ``calibration_id``."""
    if kind is None:
        kind = os.environ.get("PADDLE_CHIP_KIND") or None
    if kind is None:
        import jax
        d = jax.devices()[0]
        kind = getattr(d, "device_kind", "") or d.platform
    kind_l = str(kind).lower()
    spec = None
    for k, row in CHIP_SPECS.items():
        if k in kind_l:
            spec = dict(row, name=k)
            break
    if spec is None:
        # a device the table does not know has no peak to divide by: an
        # MFU or a roofline priced with another chip's row is a wrong
        # number, not an estimate
        raise ValueError(
            f"chip_specs: unknown device kind {kind!r} — not one of "
            f"{sorted(CHIP_SPECS)}; add its row to CHIP_SPECS or pass "
            f"kind=/PADDLE_CHIP_KIND for a trace-only tool")
    if spec["name"] == "cpu":
        spec.update(_cpu_microbench())
    from .calibration import active_calibration, apply_to_chip
    return apply_to_chip(spec, active_calibration())


def peak_flops_per_chip() -> float:
    """bf16 peak for the attached chip (raises on a device kind the
    table does not know)."""
    return chip_specs()["peak_flops"]


class timed:
    """Context manager returning its elapsed seconds via ``.seconds``."""

    def __enter__(self):
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self.seconds = time.perf_counter() - self._t0
        return False
