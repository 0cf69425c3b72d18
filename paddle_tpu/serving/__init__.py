"""Ragged paged-attention serving engine.

The "millions of users" runtime: checkpoint-load → paged-KV generator →
continuous batching, with per-request telemetry. Two engines over one
core, a pool, a kernel and a scheduler:

- :mod:`.kv_pool` — ``PagePool``: the KV cache as fixed-size HBM pages
  with per-sequence page tables and a free list, so live memory tracks
  actual tokens (plus fragmentation accounting). Page 0 is the reserved
  sink for padding writes.
- :mod:`paddle_tpu.kernels.paged_attention` — the Pallas ragged
  paged-attention decode kernel: one grid step per (sequence, kv head,
  KV page block), page table scalar-prefetched so BlockSpecs gather
  pages from HBM, masked to each sequence's true length; interpret-mode
  fallback on CPU so tier-1 asserts kernel == XLA reference attention.
- :mod:`.engine_core` — ``PagedEngine``, what every paged engine does
  and no model decides (the pool and prefix cache, one AOT-compiled
  decode program a batch bucket and one chunk program — a shape
  outside the set RAISES, serving never recompiles —, ``status()``,
  the chunked prefill's skeleton, ``release()``), and
  ``EngineContract``, what the scheduler and the fleet may rely on of
  an engine, written down once.
- :mod:`.engine` — ``ServingEngine``, the GPT adapter: stacked decode
  weights (shared with ``GPTGenerator``), its step functions and
  one-token ``decode()``, one-shot prefill programs per prompt-length
  bucket where no chunk is set, page buffers donated on TPU.
  ``ServingEngine.from_checkpoint`` wires checkpoint load.
- :mod:`.sdar_engine` — ``SdarServingEngine``, the SDAR-MoE adapter
  (block diffusion; below).
- :mod:`.scheduler` — ``ContinuousBatchingScheduler``: evict finished /
  admit queued (with full-completion page reservation, so decode can't
  OOM the pool) / one bucketed decode step, every tick. Serving steps
  feed the flight recorder + anomaly monitors (``path="serving"``, timed
  prefills ``path="serving_prefill"``) and the ``paddle_serving_*``
  metric family.

Request-scoped observability (see ``paddle_tpu.observability``): every
``Request`` carries a ``reqtrace.RequestTrace`` (lifecycle spans +
per-token samples, streamed to ``requests.jsonl`` / chrome trace);
``ContinuousBatchingScheduler(slo=...)`` attaches ``slo.SLOTracker``
guardrails (TTFT p95 / per-token p99 / queue-wait p95, burn rates,
goodput, flight dumps naming offending rids); ``scheduler.serve_http()``
exposes live ``/metrics`` + ``/healthz`` + ``/status``; and
``tools/perf_doctor.py <run_dir>`` prints the per-output-token
measured-vs-predicted attribution for any serving run dir.

Prefix sharing & prefill scheduling (README "Prefix caching &
disaggregated serving"):

- :mod:`.prefix_cache` — ``PrefixCache``: radix-style token trie over
  the pool's refcounted pages. ``ServingEngine(prefix_cache=True)``
  maps the longest cached prefix straight into a new sequence's page
  table (COW on a mid-page divergence), prefills only the suffix, and
  publishes pages at prefill-complete + release (multi-turn hits);
  LRU eviction under page pressure via ``reclaim``. ``pool.stats()``
  gains ``pages_shared`` / ``tokens_reused`` / ``prefix_hit_rate``.
- **Chunked prefill** — ``ServingEngine(prefill_chunk=C)`` replaces the
  per-bucket prefill programs with ONE traced-offset chunk program
  (:func:`.engine.chunk_prefill_fn`); the scheduler's
  ``prefill_token_budget`` bounds per-tick prefill work so long
  prompts interleave with decode ticks instead of stalling them.
- **Disaggregated prefill/decode** — ``ServingEngine(
  disaggregated=True)`` runs prefill on its own (virtual) mesh
  (:func:`.engine.prefill_kv_fn`), ships dense K/V to the decode mesh
  once per request, and lands it with :func:`.engine.scatter_kv_fn`;
  each side keeps its own bucket set.

Fleet serving (README "Fleet serving"):

- :mod:`.fleet` — ``FleetRouter``: N replica PROCESSES (each a full
  engine + scheduler + SLO tracker + ``/metrics``/``/healthz``/
  ``/status``), spawned via ``distributed.spawn``'s store-backed
  rendezvous and warm-started ``from_checkpoint``; a JSON-over-TCP RPC
  plane (stdlib sockets, no new deps); crash recovery that re-enqueues
  the dead replica's in-flight requests at the router (idempotent by
  global request id — a replica SIGKILL under load costs seconds of
  throughput and ZERO failed requests) and relaunches a replacement
  with the elastic controller's restart accounting.
- :mod:`.router` — the pure policies: ``PrefixAffinityRouter``
  (rendezvous hash over the first page-granularity token block → the
  replica already holding that prefix's KV pages; least-loaded
  fallback by queue depth + free pages) and ``SLOAutoscaler`` (scale
  out on SUSTAINED SLO burn, drain-then-retire on sustained idle —
  scale-in never drops an in-flight request).
- Federation: every replica logs into one shared run dir (rank =
  replica id), so ``merge_run_dir`` folds the fleet into ONE
  ``run_summary.json`` (per-replica breakdown + router-queue bucket in
  the doctor's serving attribution, straggler REPLICA named);
  ``FleetRouter.serve_http()`` exposes fleet ``/status`` and a
  federated ``/metrics`` (per-replica series relabeled
  ``replica="<k>"``). ``serving.predict --mode fleet`` prices the
  whole thing (per-replica roofline × N minus router overhead,
  hit-rate-split TTFT) as the ``serving_fleet_predicted`` anchor.

Block-diffusion serving (README "Block-diffusion serving"):
:mod:`.sdar_engine` — ``SdarServingEngine`` serves SDAR-MoE
(``models.sdar``: RMSNorm, RoPE, grouped heads with QK-norm, 128
dropless SiLU experts through the grouped-matmul kernel,
``kernels.grouped_matmul``) from the same pool and scheduler. A step is
one denoising or commit pass over each running sequence's block of
``block_len`` positions and yields 0 to ``block_len`` tokens; prefill
yields none. The scheduler reads the contract's ``block_len``
and drives such an engine through its ``_block_tick``.

The static gate: ``python tools/check_program.py --model serving`` lints
the decode step AND the chunk program, and replays a randomized
admission mix through the real scheduler
(:func:`.scheduler.simulate_decode_signatures`) in the GPT engine's
three prefill modes and as a block engine, to prove each shape set is
closed — zero retraces for any
request mix. TPU-less rounds still carry serving numbers via
:mod:`.predict` (``serving_predicted`` plus the
``serving_shared_prefix_predicted`` / ``serving_disagg_predicted``
anchors from the PR-5 static cost model over the real traced programs).

Quickstart::

    from paddle_tpu.serving import ServingEngine, ContinuousBatchingScheduler
    eng = ServingEngine.from_checkpoint("gpt.pdparams", cfg, page_size=64)
    sched = ContinuousBatchingScheduler(eng)
    reqs = [sched.submit(ids, max_new_tokens=64) for ids in prompts]
    sched.run()          # continuous batching until drained
    out = reqs[0].output_ids
"""
from .kv_pool import PagePool, PagePoolError, PagePoolOOM  # noqa: F401
from .engine_core import (EngineContract, EngineShapeError,  # noqa: F401
                          PagedEngine)
from .engine import (ServingEngine, chunk_prefill_fn,  # noqa: F401
                     decode_step_fn, prefill_fn, prefill_kv_fn,
                     scatter_kv_fn)
from .sdar_engine import (SdarServingEngine,  # noqa: F401
                          sdar_block_step_fn, sdar_chunk_prefill_fn)
from .prefix_cache import (PrefixCache,  # noqa: F401
                           make_shared_prefix_workload)
from .scheduler import (ContinuousBatchingScheduler,  # noqa: F401
                        MigrationUnsupported, Request,
                        simulate_decode_signatures)
from .router import PrefixAffinityRouter, SLOAutoscaler  # noqa: F401
from .fleet import FleetError, FleetRouter, ReplicaHandle  # noqa: F401

# the hybrid engine (a state pool beside the page pool) is found on first
# use: `import paddle_tpu` is 11-15 s of every run's set-up, and no run
# that serves another model should pay for this one's modules
_LAZY = {"Phi4FlashServingEngine": ".phi4flash_engine",
         "StatePool": ".state_pool", "StatePoolFull": ".state_pool"}


def __getattr__(name):
    if name in _LAZY:
        from importlib import import_module
        return getattr(import_module(_LAZY[name], __name__), name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


__all__ = [
    "PagePool", "PagePoolError", "PagePoolOOM",
    "EngineContract", "PagedEngine", "EngineShapeError",
    "ServingEngine", "SdarServingEngine", "Phi4FlashServingEngine",
    "StatePool", "StatePoolFull",
    "PrefixCache", "ContinuousBatchingScheduler", "Request",
    "MigrationUnsupported",
    "simulate_decode_signatures", "make_shared_prefix_workload",
    "FleetRouter", "FleetError", "ReplicaHandle",
    "PrefixAffinityRouter", "SLOAutoscaler",
]
