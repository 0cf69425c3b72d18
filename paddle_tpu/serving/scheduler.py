"""Continuous batching: admit/evict every step over bucketed decode shapes.

The scheduler owns the request lifecycle (queued → running → finished)
and drives the engine one decode step at a time:

1. **evict** — sequences that hit ``max_new_tokens`` (or the optional
   EOS id) release their pages back to the pool;
2. **admit** — queued requests prefill (allocating pages) while a free
   batch slot exists AND the pool can hold the request's *full*
   completion (prompt + max_new, reserved up front, so a running
   sequence can never OOM the pool mid-decode);
3. **decode** — the active set, in deterministic (admission-order) slot
   order, runs one step of the smallest AOT batch bucket that fits.

What the scheduler may rely on of an engine is written down once, in
:class:`~.engine_core.EngineContract` (``decode_buckets``, ``pool``,
``block_len``, ``prefill_chunk``, ``prefix_cache``, ``can_migrate``,
``decode_bucket``, ``reclaim_cache_pages``, ``status``): it reads those
and probes nothing. An engine whose step is not one token declares
``block_len`` (and a prefill that yields no token): the decode phase is
then :meth:`ContinuousBatchingScheduler._block_tick`, one pass over each
running sequence's block that yields 0 to ``block_len`` tokens for it;
the pool grows by a block, time to first token is the first block, and
a request still ends at ``max_new_tokens`` exactly. The one-token path
pays one comparison a tick for it.

Every decode signature the scheduler can ever request is therefore
``(bucket, pages_per_seq)`` for a configured bucket —
:func:`simulate_decode_signatures` replays this exact logic (device-free)
over a randomized admission mix so ``tools/check_program.py`` can prove
the AOT shape set is closed: zero retraces at serving time.

Telemetry — aggregate AND request-scoped:

- queue depth / KV pages gauges, request + token counters, TTFT /
  queue-wait / prefill / per-token histograms; decode steps ride
  ``record_train_step(path="serving")`` and timed prefills
  ``path="serving_prefill"``, so both feed the flight recorder and the
  online anomaly monitors exactly like train steps;
- every ``Request`` carries a :class:`~paddle_tpu.observability.
  reqtrace.RequestTrace` (one span per lifecycle phase, per-token
  decode samples); terminal records stream to ``requests.jsonl`` in the
  active run dir and export to chrome trace;
- an optional :class:`~paddle_tpu.observability.slo.SLOTracker`
  (``slo=...``) enforces TTFT / per-token / queue-wait targets with
  burn-rate accounting, violation events, and flight dumps naming the
  offending rids;
- while a device trace is being taken (or a ``Profiler`` records) every
  tick is a ``sched.step`` :class:`~paddle_tpu.profiler.RecordEvent`
  with one child per phase (``sched.expire`` / ``evict`` / ``admit`` /
  ``prefill_tick`` / ``hooks`` / ``prepare`` / ``decode_tick`` /
  ``account``) and the engine's spans under those, and ``submit()`` is
  a ``sched.submit``; off, each is one predicate;
- :meth:`ContinuousBatchingScheduler.serve_http` exposes ``/metrics``,
  ``/healthz`` (flips unhealthy after an engine failure), and
  ``/status`` (queue/pool/SLO snapshot) on a stdlib HTTP thread.
"""
from __future__ import annotations

import itertools
import os
import threading
import time
from collections import deque
from dataclasses import dataclass, field

import numpy as np

from ..observability import instrument as obs, lockwitness
from ..profiler.utils import RecordEvent, record_gc_pauses
from .engine_core import EngineContract, smallest_bucket

__all__ = ["Request", "ContinuousBatchingScheduler",
           "MigrationUnsupported", "simulate_decode_signatures"]


class MigrationUnsupported(RuntimeError):
    """A running request of this engine cannot be checkpointed for live
    migration (a block engine: a block in flight)."""


def _env_pos_float(name: str):
    """Positive-float env knob; unset / 0 / garbage → None."""
    try:
        v = float(os.environ.get(name, "") or 0.0)
    except ValueError:
        v = 0.0
    return v if v > 0 else None


@dataclass
class Request:
    rid: int
    prompt: np.ndarray                 # [S] int32
    max_new_tokens: int
    eos_id: int | None = None
    submit_time: float = field(default_factory=time.perf_counter)
    admit_time: float | None = None
    prefill_start_time: float | None = None  # its first chunk began
    first_token_time: float | None = None
    finish_time: float | None = None
    prefill_s: float | None = None     # measured prefill walltime
    cached_prefix_len: int = 0         # prompt tokens reused from cache
    prefill_chunks: int = 0            # chunk program invocations
    router_wait_s: float = 0.0         # fleet: wait at the router before
    #                                    this replica saw the request
    migrations: int = 0                # fleet: live-migration hops
    migrate_s: float = 0.0             # fleet: transfer+restore walltime
    migrate_bytes: int = 0             # fleet: K/V payload moved
    deadline_s: float | None = None    # relative to submit_time; an
    #                                    expired request cancels at the
    #                                    next tick wherever it lives
    retry_after_s: float | None = None  # backpressure hint on rejects
    degraded_s: float = 0.0            # decode walltime spent while the
    #                                    scheduler was in brownout/shed
    tokens: list = field(default_factory=list)   # generated ids
    # block engines only (a step is a pass over a block, not a token):
    passes: int = 0                    # passes this request took part in
    last_emit_time: float | None = None  # its last block came out
    block_record: list | None = None   # (token, pass of its block at
    #                                    which it was unmasked, its
    #                                    confidence there) of every
    #                                    generated position, those past
    #                                    max_new_tokens too
    state: str = "queued"              # queued|prefilling|running|
    #                                    finished|rejected|
    #                                    deadline_exceeded
    reject_reason: str | None = None   # max_new<1|too_long|retry_after|
    #                                    pool_too_small|draining|shed
    slo_met: bool | None = None        # stamped at finish by the tracker
    trace: object = None               # observability.reqtrace.RequestTrace

    @property
    def output_ids(self) -> np.ndarray:
        return np.concatenate(
            [self.prompt, np.asarray(self.tokens, np.int32)])

    @property
    def done(self) -> bool:
        if len(self.tokens) >= self.max_new_tokens:
            return True
        return bool(self.eos_id is not None and self.tokens
                    and self.tokens[-1] == self.eos_id)

    def expired(self, now: float) -> bool:
        """Deadline check against the request's own clock (deadline_s
        is RELATIVE to submit_time, so it survives a live migration's
        clock rebuild)."""
        return self.deadline_s is not None \
            and (now - self.submit_time) > self.deadline_s

    def summary(self) -> dict:
        """Per-request serving record (times in seconds). ``is not
        None`` guards throughout: a monotonic clock CAN legitimately
        read 0.0, so truthiness would misreport a real timestamp as
        missing."""
        queue_wait = prefill_wait = ttft = decode_s = total_s = tps = None
        if self.admit_time is not None:
            queue_wait = self.admit_time - self.submit_time
            if self.prefill_start_time is not None:
                # admitted, waiting behind other prompts' chunks
                prefill_wait = self.prefill_start_time - self.admit_time
        if self.first_token_time is not None:
            ttft = self.first_token_time - self.submit_time
        if self.finish_time is not None:
            total_s = self.finish_time - self.submit_time
            if self.first_token_time is not None:
                decode_s = self.finish_time - self.first_token_time
        if decode_s is not None and decode_s > 0 and len(self.tokens) > 1:
            tps = (len(self.tokens) - 1) / decode_s
        out = {"rid": self.rid, "state": self.state,
               "reject_reason": self.reject_reason,
               "prompt_len": int(self.prompt.shape[0]),
               "new_tokens": len(self.tokens),
               "router_wait_s": self.router_wait_s,
               "queue_wait_s": queue_wait,
               "prefill_wait_s": prefill_wait, "ttft_s": ttft,
               "prefill_s": self.prefill_s,
               "cached_prefix_len": self.cached_prefix_len,
               "prefill_chunks": self.prefill_chunks,
               "decode_s": decode_s, "total_s": total_s,
               "decode_tokens_per_sec": tps,
               "slo_met": self.slo_met}
        if self.migrations:
            out["migrations"] = self.migrations
            out["migrate_s"] = round(self.migrate_s, 6)
            out["migrate_bytes"] = self.migrate_bytes
        if self.deadline_s is not None:
            out["deadline_s"] = self.deadline_s
        if self.retry_after_s is not None:
            out["retry_after_s"] = round(self.retry_after_s, 3)
        if self.degraded_s:
            out["degraded_s"] = round(self.degraded_s, 6)
        if self.passes:
            out["passes"] = self.passes
        if self.trace is not None and self.trace.token_samples:
            out["per_token_s"] = self.trace.per_token_stats()
        return out


class ContinuousBatchingScheduler:
    def __init__(self, engine, max_queue: int = 1024, slo=None,
                 max_retained: int = 4096, prefill_token_budget=None):
        from ..observability.slo import SLOConfig, SLOTracker
        record_gc_pauses()      # a collection inside a tick is a span of it
        self.engine = engine
        self.buckets = tuple(engine.decode_buckets)
        self.max_concurrency = self.buckets[-1]
        self.max_queue = int(max_queue)
        self._queue: deque = deque()
        self._running: dict = {}          # rid -> Request, insertion order
        self._prefilling: dict = {}       # rid -> Request (chunked mode)
        self._begun: set = set()          # rids whose prefill has pages
        # fleet live migration: requests checkpointed OUT of _running
        # (source stays authoritative until the destination ACKs) and
        # staged page reservations for requests migrating IN
        self._migrating: dict = {}        # rid -> Request (outbound hold)
        self._migrating_in: dict = {}     # rid -> {"need": pages reserved}
        self.migrations_out = 0
        self.migrations_in = 0
        # chunked engines interleave prefill with decode: each tick
        # spends at most this many prefill tokens (chunk-granular; the
        # default of one chunk is the tightest decode-stall bound)
        self.chunked = engine.prefill_chunk is not None
        # how a step advances a sequence: one token (1), or one pass over
        # a block of this many positions that yields 0..block tokens and
        # whose prefill yields none. A page holds whole blocks (the
        # engine checks), so a completion rounded up to a block needs the
        # pages that `_completion_pages` already reckons
        self.block_len = int(engine.block_len)
        self.prefill_token_budget = int(prefill_token_budget) \
            if prefill_token_budget else (engine.prefill_chunk
                                          if self.chunked else None)
        self.prefill_tokens_per_tick: list = []   # observability/tests
        self._reserved_pages = 0          # pages promised, not yet alloc'd
        self._rid = itertools.count()
        # terminal Request objects kept in memory for run()/bench/status
        # consumers, bounded to the most recent max_retained per list —
        # a long-lived server must not grow without limit (the durable
        # per-request record is the requests.jsonl stream)
        self.max_retained = int(max_retained)
        self.finished: list = []
        self.rejected: list = []
        self.deadline_exceeded: list = []
        self.step_times: list = []        # decode-step walltimes (s)
        self.steps = 0
        self.slo = None
        if slo is not None:
            self.slo = slo if isinstance(slo, SLOTracker) \
                else SLOTracker(slo if isinstance(slo, (SLOConfig, dict))
                                else SLOConfig())
        self.healthy = True
        self.last_error: str | None = None
        # ---- overload control (deadlines / admission / brownout) ----
        # env knobs so a whole fleet tunes the policy without code:
        # PADDLE_FLEET_DEADLINE_DEFAULT_S (0/unset = no default
        # deadline), PADDLE_FLEET_BROWNOUT_BURN (burn rate that enters
        # brownout; shedding at 2x, hysteretic exits at half),
        # PADDLE_FLEET_RETRY_AFTER_CAP_S (ceiling on the backpressure
        # hint)
        self.default_deadline_s = _env_pos_float(
            "PADDLE_FLEET_DEADLINE_DEFAULT_S")
        self.brownout_burn = _env_pos_float(
            "PADDLE_FLEET_BROWNOUT_BURN") or 1.0
        self.retry_after_cap_s = _env_pos_float(
            "PADDLE_FLEET_RETRY_AFTER_CAP_S") or 30.0
        self.mode = "healthy"             # healthy|brownout|shedding
        self.mode_transitions = 0
        self.mode_seconds = {"healthy": 0.0, "brownout": 0.0,
                             "shedding": 0.0}
        self._mode_since = time.perf_counter()
        self.degraded_s_total = 0.0       # decode walltime off-healthy
        self.deadline_cancelled = 0
        # speculative/background work (cache warmers, draft models,
        # prefetch) registers callables here; brownout and shedding
        # pause them — cache RECLAIM stays on (it frees capacity)
        self.background_hooks: list = []
        self._finish_ts: deque = deque(maxlen=64)  # drain-rate window
        # drain-then-retire (fleet scale-in): a draining scheduler
        # finishes queued + running work but accepts no new submits —
        # /healthz reports "draining" so a router can tell retiring
        # from dead
        self.draining = False
        # one coarse lock makes /status (and concurrent submit) a
        # consistent cut of queue/pool state; step() holds it for the
        # tick, so a scrape waits at most one decode step
        self._lock = lockwitness.named_lock("serving.scheduler")
        self._start_ts = time.time()

    # ----------------------------------------------------------- intake
    def submit(self, prompt_ids, max_new_tokens: int, eos_id=None,
               rid=None, router_wait_s: float = 0.0,
               deadline_s: float | None = None) -> Request:
        """Queue one request. ``rid`` lets a fleet router thread its
        GLOBAL request id through (re-enqueues stay idempotent by id
        and the federated ``requests.jsonl`` speaks one id space);
        ``router_wait_s`` stamps the time the request already waited at
        that router, so fleet-level latency attribution sees it.
        ``deadline_s`` (relative to now; default from
        ``PADDLE_FLEET_DEADLINE_DEFAULT_S``) cancels the request at
        the first tick past the deadline, wherever it lives.

        Overload backpressure replaces the old binary ``queue_full``:
        a request refused for capacity is priced against the recent
        drain rate and rejected with reason ``retry_after`` plus a
        machine-readable ``retry_after_s`` hint; in shedding mode all
        cache-miss traffic is refused the same way (reason ``shed``)."""
        from ..observability.reqtrace import RequestTrace
        with RecordEvent("sched.submit"):
            prompt = np.asarray(prompt_ids, np.int32).reshape(-1)
            with self._lock:
                r = Request(next(self._rid) if rid is None else int(rid),
                            prompt, int(max_new_tokens), eos_id=eos_id,
                            router_wait_s=float(router_wait_s))
                r.deadline_s = float(deadline_s) \
                    if deadline_s is not None and deadline_s > 0 \
                    else self.default_deadline_s
                r.trace = RequestTrace(r.rid, r.submit_time)
                pool = self.engine.pool
                total = prompt.shape[0] + r.max_new_tokens
                # max_new >= 1: prefill always emits one token, so total >=
                # n+1 and the engine's prompt-room check can never fire at
                # admission
                reason = None
                if self.draining:
                    reason = "draining"
                elif r.max_new_tokens < 1:
                    reason = "max_new<1"
                elif total > pool.max_seq_len:
                    reason = "too_long"
                elif len(self._queue) >= self.max_queue:
                    reason = "retry_after"
                    r.retry_after_s = self._retry_after_estimate()
                elif pool.pages_needed(total) > pool.num_pages - 1:
                    reason = "pool_too_small"
                elif self.mode == "shedding" \
                        and not self._cache_hit_tokens(prompt):
                    # shedding: only traffic the prefix cache makes cheap
                    # still gets in — everything else backs off
                    reason = "shed"
                    r.retry_after_s = self._retry_after_estimate()
                if reason is not None:
                    r.state = "rejected"
                    r.reject_reason = reason
                    r.trace.span("rejected", r.submit_time,
                                 time.perf_counter(), reason=reason)
                    self.rejected.append(r)
                    del self.rejected[:-self.max_retained]
                    obs.serving_requests_counter().inc(event="rejected",
                                                       reason=reason)
                    if self.slo is not None:
                        self.slo.observe_request(r.summary())
                    self._log_request(r)
                    return r
                self._queue.append(r)
                obs.serving_requests_counter().inc(event="submitted")
                obs.serving_queue_depth_gauge().set(float(len(self._queue)))
                return r

    @property
    def pending(self) -> int:
        return len(self._queue) + len(self._prefilling) \
            + len(self._running)

    def drain(self):
        """Enter drain-then-retire: refuse new submits (reject reason
        ``draining``), keep stepping until the in-flight work finishes.
        A fleet router drains a replica before retiring it so scale-in
        never drops a request."""
        with self._lock:
            self.draining = True

    # ------------------------------------------------- overload control
    def _cache_hit_tokens(self, prompt) -> int:
        """Side-effect-free prefix-cache probe (``match`` moves no
        refcounts and records no stats): how many prompt tokens would
        be served from cache. Brownout prefers hits at admission;
        shedding rejects misses outright."""
        cache = self.engine.prefix_cache
        if cache is None:
            return 0
        try:
            return int(cache.match(prompt)[2])
        except Exception:
            return 0

    def _drain_rate(self) -> float:
        """Recent completion throughput (requests/s) over the finish-
        timestamp window — the denominator of ``retry_after_s``."""
        ts = self._finish_ts
        if len(ts) >= 2 and ts[-1] > ts[0]:
            return (len(ts) - 1) / (ts[-1] - ts[0])
        return 0.0

    def _retry_after_estimate(self) -> float:
        """Backpressure hint: time for the present backlog to drain at
        the observed completion rate, scaled up by the SLO burn rate
        (a burning replica wants MORE backoff than its queue length
        alone says), capped at ``PADDLE_FLEET_RETRY_AFTER_CAP_S``."""
        backlog = (len(self._queue) + len(self._prefilling)
                   + len(self._running)) or 1
        rate = self._drain_rate()
        est = backlog / rate if rate > 0 else self.retry_after_cap_s
        if self.slo is not None:
            rates = self.slo.burn_rates()
            if rates:
                est *= max(1.0, max(rates.values()))
        return round(min(max(est, 0.05), self.retry_after_cap_s), 3)

    def _update_mode(self, now: float):
        """``healthy → brownout → shedding`` policy machine on the SLO
        burn rates. Brownout enters at ``PADDLE_FLEET_BROWNOUT_BURN``
        (1.0 = burning the error budget exactly), shedding at 2x;
        exits are hysteretic (half the entry threshold) so a burn rate
        hovering at the line doesn't flap the mode every tick. Each
        transition is a runlog event + gauge flip."""
        self.mode_seconds[self.mode] += now - self._mode_since
        self._mode_since = now
        if self.slo is None:
            return
        rates = self.slo.burn_rates()
        burn = max(rates.values()) if rates else 0.0
        prev = self.mode
        if burn >= 2 * self.brownout_burn:
            self.mode = "shedding"
        elif self.mode == "shedding":
            if burn < self.brownout_burn:
                self.mode = "brownout"
        elif burn >= self.brownout_burn:
            self.mode = "brownout"
        elif self.mode == "brownout" \
                and burn < 0.5 * self.brownout_burn:
            self.mode = "healthy"
        if self.mode != prev:
            from ..observability.runlog import get_run_logger
            self.mode_transitions += 1
            obs.serving_overload_mode_gauge().set(float(
                {"healthy": 0, "brownout": 1, "shedding": 2}[self.mode]))
            logger = get_run_logger()
            if logger is not None:
                logger.log("overload_mode", mode=self.mode, prev=prev,
                           burn_rate=round(burn, 4))

    def _cancel_locked(self, r: Request, now: float, phase: str):
        """Shared terminal path for deadline expiry and explicit
        cancel: reclaim whatever the phase holds (queued = nothing;
        prefilling = withdraw-style release; running = the finished
        path's release, which still publishes the decoded prefix to
        the cache — a cancelled request's prefix stays warm), then
        stamp the ``deadline_exceeded`` terminal state. Cancel is an
        EVICTION, never a recompile: no new program shapes — the
        closure replay's cancellation mix proves it."""
        if phase == "prefilling":
            self._drop_prefilling(r)
        elif phase == "running":
            self._release(r, cached=r.tokens[:-1])
        r.state = "deadline_exceeded"
        r.finish_time = now
        if r.trace is not None:
            start = r.first_token_time
            if start is None:
                start = r.admit_time
            if start is None:
                start = r.submit_time
            r.trace.span("deadline_exceeded", start, now,
                         cancelled_in=phase, tokens=len(r.tokens))
        if self.slo is not None:
            r.slo_met = self.slo.observe_request(r.summary())
        self.deadline_exceeded.append(r)
        del self.deadline_exceeded[:-self.max_retained]
        self.deadline_cancelled += 1
        obs.serving_requests_counter().inc(event="deadline_exceeded")
        obs.serving_deadline_exceeded_counter().inc(phase=phase)
        self._log_request(r)

    def _cancel_expired(self, now: float):
        """Per-tick deadline sweep: expired requests cancel wherever
        they live — queued, mid-prefill, or mid-decode — converting
        lateness into freed pages instead of compounding queue wait."""
        if self._queue and any(r.deadline_s is not None
                               for r in self._queue):
            expired = [r for r in self._queue if r.expired(now)]
            if expired:
                keep = [r for r in self._queue if not r.expired(now)]
                self._queue.clear()
                self._queue.extend(keep)
                for r in expired:
                    self._cancel_locked(r, now, "queued")
        for rid in [rid for rid, r in self._prefilling.items()
                    if r.expired(now)]:
            self._cancel_locked(self._prefilling.pop(rid), now,
                                "prefilling")
        for rid in [rid for rid, r in self._running.items()
                    if r.expired(now) and not r.done]:
            self._cancel_locked(self._running.pop(rid), now, "running")

    def cancel(self, rid) -> bool:
        """Cancel one request wherever it lives (queued / prefilling /
        running), through the exact terminal path a deadline expiry
        takes. Returns False for unknown, already-terminal, or
        done-this-tick rids (those finish normally)."""
        with self._lock:
            now = time.perf_counter()
            for i, r in enumerate(self._queue):
                if r.rid == rid:
                    del self._queue[i]
                    self._cancel_locked(r, now, "queued")
                    return True
            r = self._prefilling.pop(rid, None)
            if r is not None:
                self._cancel_locked(r, now, "prefilling")
                return True
            r = self._running.get(rid)
            if r is None or r.done:
                return False
            del self._running[rid]
            self._cancel_locked(r, now, "running")
            return True

    # ------------------------------------------------------------ phases
    def _completion_pages(self, r: Request) -> int:
        return self.engine.pool.pages_needed(
            int(r.prompt.shape[0]) + r.max_new_tokens)

    def _release(self, r: Request, cached=None):
        """Give back what is left of ``r``'s page reservation and free
        its sequence; ``cached`` (the generated tokens whose K/V entered
        the pool) publishes it, after the prompt, to the prefix cache."""
        held = len(self.engine.pool.table(r.rid))
        self._reserved_pages -= self._completion_pages(r) - held
        self.engine.release(
            r.rid, token_ids=None if cached is None else np.concatenate(
                [r.prompt, np.asarray(cached, np.int32)]))

    def _drop_prefilling(self, r: Request):
        """A request out of the prefill phase, with or without pages."""
        if r.rid in self._begun:
            self._begun.discard(r.rid)
            self._release(r)
        else:
            self._reserved_pages -= self._completion_pages(r)

    def _log_request(self, r: Request):
        """Stream a request's terminal record to requests.jsonl (no-op
        outside a telemetry-enabled run)."""
        from ..observability.reqtrace import request_record
        from ..observability.runlog import get_run_logger
        logger = get_run_logger()
        if logger is not None:
            try:
                logger.log_request(request_record(r.summary(), r.trace))
            except Exception:
                pass  # telemetry must never take the serving loop down

    def _evict_finished(self) -> int:
        done = [rid for rid, r in self._running.items() if r.done]
        for rid in done:
            r = self._running.pop(rid)
            # everything but the final sampled token has K/V in the
            # pool — exactly what the prefix cache may re-serve (a block
            # engine emits a block once it is committed: all of them)
            self._release(r, cached=r.tokens if self.block_len > 1
                          else r.tokens[:-1])
            r.state = "finished"
            r.finish_time = time.perf_counter()
            self._finish_ts.append(r.finish_time)
            if r.trace is not None and r.first_token_time is not None:
                r.trace.span("decode", r.first_token_time, r.finish_time,
                             tokens=max(len(r.tokens) - 1, 0))
            if self.slo is not None:
                r.slo_met = self.slo.observe_request(r.summary())
            self.finished.append(r)
            del self.finished[:-self.max_retained]
            obs.serving_requests_counter().inc(event="finished")
            self._log_request(r)
        return len(done)

    def _page_room(self, need: int) -> bool:
        """Free pages (after reservations) cover ``need``? Under
        pressure, ask the engine to reclaim prefix-cache pages first —
        cached pages are free capacity until a paying request needs
        them (LRU eviction inside)."""
        pool = self.engine.pool
        avail = pool.free_pages - self._reserved_pages
        if avail < need:
            avail += self.engine.reclaim_cache_pages(need - avail)
        return avail >= need

    def _next_admit_index(self) -> int:
        """Head-of-line normally; under brownout/shedding prefer the
        first queued request with a cached prefix — the cheapest
        goodput per page when capacity is what's scarce. Falls back to
        index 0, so the healthy path stays deterministic."""
        if self.mode == "healthy" or not self._queue:
            return 0
        for i, r in enumerate(self._queue):
            if self._cache_hit_tokens(r.prompt):
                return i
        return 0

    def _brownout_clamp(self, r: Request):
        """Brownout halves the completion budget at admission (floor
        1) — shorter answers under pressure, never dropped ones. Done
        once, at the admission that actually takes the request."""
        if self.mode != "healthy":
            r.max_new_tokens = max(1, (r.max_new_tokens + 1) // 2)

    def _admit_chunked(self):
        """Chunked admission: reserve the full completion and hand the
        request to the prefill phase — page allocation AND the prefix-
        cache match happen at its first chunk (so a same-prefix request
        earlier in the queue has published its pages by then)."""
        while self._queue and (len(self._running) + len(self._prefilling)
                               + len(self._migrating_in)
                               < self.max_concurrency):
            i = self._next_admit_index()
            r = self._queue[i]
            need = self._completion_pages(r)
            if not self._page_room(need):
                break  # head-of-line: keep arrival order deterministic
            del self._queue[i]
            self._brownout_clamp(r)
            need = self._completion_pages(r)
            r.admit_time = time.perf_counter()
            r.state = "prefilling"
            r.prefill_s = 0.0
            self._reserved_pages += need
            self._prefilling[r.rid] = r
            if r.trace is not None:
                r.trace.span("queued", r.submit_time, r.admit_time)
            obs.serving_requests_counter().inc(event="admitted")
            obs.serving_queue_wait_histogram().observe(
                r.admit_time - r.submit_time)

    def _prefill_tick(self):
        """Spend the per-tick prefill token budget on head-of-line
        prefilling requests, one chunk at a time — the decode step that
        follows is stalled by at most ``prefill_token_budget`` tokens
        of prefill work (chunk-granular), never a whole long prompt."""
        eng = self.engine
        budget = self.prefill_token_budget
        spent = 0
        while self._prefilling and spent < budget:
            rid, r = next(iter(self._prefilling.items()))
            pool = eng.pool
            t0 = time.perf_counter()
            if rid not in self._begun:
                r.prefill_start_time = t0
                cached = eng.prefill_begin(rid, r.prompt)
                self._begun.add(rid)
                r.cached_prefix_len = cached
                self._reserved_pages -= len(pool.table(rid))
                if cached:
                    obs.serving_prefix_hits_counter().inc()
                    obs.serving_prefix_tokens_reused_counter().inc(
                        float(cached))
            processed, done, tok = eng.prefill_step(rid)
            dt = time.perf_counter() - t0
            spent += processed
            r.prefill_s += dt
            r.prefill_chunks += 1
            with RecordEvent("sched.account", path="serving_prefill"):
                obs.serving_prefill_chunks_counter().inc()
                obs.record_train_step(dt, tokens=processed,
                                      path="serving_prefill")
            if not done:
                continue
            del self._prefilling[rid]
            self._begun.discard(rid)
            t_done = time.perf_counter()
            r.state = "running"
            self._running[rid] = r
            if tok is None:
                # a block engine's prefill yields no token: the first
                # block is the first token (`_block_tick` stamps it)
                r.last_emit_time = t_done
                r.block_record = []
                if r.trace is not None:
                    r.trace.span("prefill", r.admit_time, t_done,
                                 prompt_len=int(r.prompt.shape[0]),
                                 chunks=r.prefill_chunks,
                                 cached_prefix_len=r.cached_prefix_len)
                obs.serving_prefill_histogram().observe(r.prefill_s)
                continue
            r.tokens.append(tok)
            r.first_token_time = t_done
            with RecordEvent("sched.account", path="first_token"):
                if r.trace is not None:
                    r.trace.span("prefill", r.admit_time, t_done,
                                 prompt_len=int(r.prompt.shape[0]),
                                 chunks=r.prefill_chunks,
                                 cached_prefix_len=r.cached_prefix_len)
                obs.serving_prefill_histogram().observe(r.prefill_s)
                obs.serving_ttft_histogram().observe(
                    r.first_token_time - r.submit_time)
                obs.serving_tokens_out_counter().inc()
                if self.slo is not None:
                    self.slo.observe_admission(
                        rid, ttft_s=r.first_token_time - r.submit_time,
                        queue_wait_s=r.admit_time - r.submit_time)
        if spent:
            self.prefill_tokens_per_tick.append(spent)
        return spent

    def _admit(self):
        if self.chunked:
            return self._admit_chunked()
        pool = self.engine.pool
        while self._queue and (len(self._running)
                               + len(self._migrating_in)
                               < self.max_concurrency):
            i = self._next_admit_index()
            r = self._queue[i]
            need = self._completion_pages(r)
            if not self._page_room(need):
                break  # head-of-line: keep arrival order deterministic
            del self._queue[i]
            self._brownout_clamp(r)
            need = self._completion_pages(r)
            r.admit_time = r.prefill_start_time = time.perf_counter()
            # the prefill IS part of the serving hot path: time it, so
            # it reaches the histogram, the flight recorder, and the
            # anomaly monitors (path="serving_prefill") — invisible
            # prefill cost was the old blind spot
            tok = self.engine.prefill(r.rid, r.prompt)
            t_done = time.perf_counter()
            r.prefill_s = t_done - r.admit_time
            self._reserved_pages += need - len(pool.table(r.rid))
            r.tokens.append(tok)
            r.state = "running"
            r.first_token_time = t_done
            self._running[r.rid] = r
            if r.trace is not None:
                r.trace.span("queued", r.submit_time, r.admit_time)
                r.trace.span("prefill", r.admit_time, t_done,
                             prompt_len=int(r.prompt.shape[0]))
            obs.serving_requests_counter().inc(event="admitted")
            obs.serving_queue_wait_histogram().observe(
                r.admit_time - r.submit_time)
            obs.serving_prefill_histogram().observe(r.prefill_s)
            obs.serving_ttft_histogram().observe(
                r.first_token_time - r.submit_time)
            obs.serving_tokens_out_counter().inc()
            obs.record_train_step(r.prefill_s,
                                  tokens=int(r.prompt.shape[0]),
                                  path="serving_prefill")
            if self.slo is not None:
                # ttft/queue-wait are final NOW — the guardrail windows
                # must see a stall at admission, not at completion
                self.slo.observe_admission(
                    r.rid, ttft_s=r.first_token_time - r.submit_time,
                    queue_wait_s=r.admit_time - r.submit_time)

    def step(self) -> bool:
        """One scheduler tick (evict → admit → one bucketed decode step).
        Returns False when idle (nothing queued or running). An engine
        failure marks the scheduler unhealthy (``/healthz`` → 503) and
        re-raises."""
        try:
            with self._lock, RecordEvent(
                    "sched.step", step=self.steps, queued=len(self._queue),
                    prefilling=len(self._prefilling),
                    running=len(self._running)):
                return self._step_locked()
        except Exception as e:
            self.healthy = False
            self.last_error = repr(e)[:300]
            from ..observability.runlog import get_run_logger
            logger = get_run_logger()
            if logger is not None:
                logger.log("serving_engine_error", error=self.last_error)
            raise

    def _step_locked(self) -> bool:
        """The tick, phase by phase. Each phase is a ``RecordEvent``
        under ``step()``'s ``sched.step`` (kept only while a trace is
        being taken): what is left of the step outside them is its own
        time."""
        with RecordEvent("sched.expire"):
            now = time.perf_counter()
            self._update_mode(now)
            self._cancel_expired(now)
        with RecordEvent("sched.evict") as ev:
            ev.set(n_evicted=self._evict_finished())
        with RecordEvent("sched.admit") as ev:
            waiting = len(self._queue)
            self._admit()
            ev.set(n_admitted=waiting - len(self._queue))
        if self.chunked:
            with RecordEvent("sched.prefill_tick") as ev:
                ev.set(tokens=self._prefill_tick())
        if self.mode == "healthy" and self.background_hooks:
            # speculative/background work runs only with headroom;
            # brownout/shedding pause it (cache reclaim stays on — it
            # frees capacity, it doesn't spend it)
            with RecordEvent("sched.hooks"):
                for hook in self.background_hooks:
                    try:
                        hook()
                    except Exception:
                        pass  # background work must never take the loop down
        with RecordEvent("sched.prepare"):
            obs.serving_queue_depth_gauge().set(float(len(self._queue)))
            obs.serving_kv_pages_gauge().set(
                float(self.engine.pool.pages_in_use))
            # admission may have finished short requests (max_new=1)
            active = [r for r in self._running.values() if not r.done]
            if not active:
                return bool(self._queue or self._prefilling
                            or self._running)
            t0 = time.perf_counter()
            # ONE bucket-selection implementation: the engine's (raises
            # EngineShapeError on overflow, same as every other shape gate)
            bucket = self.engine.decode_bucket(len(active))
            # a block tick's sched.decode_tick carries it: counted here,
            # so that the phase span opens on the work it names
            masked = self.engine.masked_positions([r.rid for r in active]) \
                if self.block_len > 1 else None
        if self.block_len > 1:
            return self._block_tick(active, bucket, t0, masked)
        with RecordEvent("sched.decode_tick", n_active=len(active),
                         bucket=bucket):
            pool = self.engine.pool
            with RecordEvent("pool.extend"):
                for r in active:
                    held = len(pool.table(r.rid))
                    pool.extend(r.rid, 1)
                    self._reserved_pages -= len(pool.table(r.rid)) - held
            toks = self.engine.decode([r.rid for r in active], bucket)
        dt = time.perf_counter() - t0
        for r, t in zip(active, toks):
            r.tokens.append(t)
        with RecordEvent("sched.account", path="serving"):
            per_token = obs.serving_per_token_histogram()
            for r in active:
                if r.trace is not None:
                    r.trace.add_token(dt)
                per_token.observe(dt)
            if self.slo is not None:
                self.slo.observe_tokens([r.rid for r in active], dt)
            self._account_step(obs, active, dt, len(active))
        return True

    def _account_step(self, obs, active, dt, tokens):
        """What a decode step counts whatever it yields (a token a
        sequence, or a pass's 0 to ``block_len``): degraded time, the
        step and its duration, the tokens that came out."""
        if self.mode != "healthy":
            # degraded time is attributable: the doctor carves it
            # out of the decode residual exactly like migration cost
            self.degraded_s_total += dt
            obs.serving_degraded_seconds_counter().inc(dt)
            for r in active:
                r.degraded_s += dt
        self.steps += 1
        self.step_times.append(dt)
        if tokens:
            obs.serving_tokens_out_counter().inc(float(tokens))
        # serving steps feed the flight recorder + anomaly monitors
        # the same way train steps do
        obs.record_train_step(dt, tokens=tokens, path="serving")

    def _block_tick(self, active, bucket, t0, masked) -> bool:
        """The decode phase where a step is one pass over each running
        sequence's block: the pool grows by a block where a sequence
        starts one, the pass yields 0 to ``block_len`` tokens for it (a
        block comes out whole, once committed), and a request ends at
        ``max_new_tokens`` exactly: what its last block computed past
        that is dropped. Time to first token is the first block; the
        per-token samples are a block's time over its tokens."""
        eng, pool, bl = self.engine, self.engine.pool, self.block_len
        rids = [r.rid for r in active]
        with RecordEvent("sched.decode_tick", n_active=len(active),
                         bucket=bucket, block=bl, masked=masked):
            grown = 0
            with RecordEvent("pool.extend") as ev:
                for r in active:
                    if eng.starts_block(r.rid):
                        held = len(pool.table(r.rid))
                        pool.extend(r.rid, bl)
                        self._reserved_pages -= len(pool.table(r.rid)) - held
                        grown += bl
                ev.set(n=grown)
            out = eng.decode(rids, bucket)
        now = time.perf_counter()
        dt = now - t0
        with RecordEvent("sched.account", path="serving"):
            per_token = obs.serving_per_token_histogram()
            emitted = 0
            for r, (toks, passes, confs) in zip(active, out):
                r.passes += 1
                if not toks:
                    continue
                r.block_record.extend(zip(toks, passes, confs))
                room = r.max_new_tokens - len(r.tokens)
                if r.eos_id is not None and r.eos_id in toks[:room]:
                    room = toks.index(r.eos_id) + 1
                new = toks[:room]
                eng.note_emitted(len(new), len(toks) - len(new))
                r.tokens.extend(new)
                emitted += len(new)
                each = (now - r.last_emit_time) / len(new)
                r.last_emit_time = now
                for _ in new:
                    if r.trace is not None:
                        r.trace.add_token(each)
                    per_token.observe(each)
                if self.slo is not None:
                    self.slo.observe_tokens([r.rid], each)
                if r.first_token_time is None:
                    r.first_token_time = now
                    obs.serving_ttft_histogram().observe(
                        now - r.submit_time)
                    if self.slo is not None:
                        self.slo.observe_admission(
                            r.rid, ttft_s=now - r.submit_time,
                            queue_wait_s=r.admit_time - r.submit_time)
            self._account_step(obs, active, dt, emitted)
        return True

    def run(self, max_steps: int | None = None) -> list:
        """Drive until drained (or ``max_steps``); returns the finished
        requests in completion order (the most recent ``max_retained``
        of them — older ones live on only in ``requests.jsonl``)."""
        n = 0
        while self.pending:
            if max_steps is not None and n >= max_steps:
                break
            self.step()
            n += 1
        with self._lock:
            self._evict_finished()
        return self.finished

    # ------------------------------------------------------ live migration
    # Fleet-level KV-page live migration (source and destination sides).
    # Protocol invariants: a checkpointed request leaves _running but
    # keeps its pages — the SOURCE stays authoritative until the
    # destination ACKs (complete_migration frees + publishes the pages
    # to the source's prefix cache; abort_migration puts the request
    # back token-for-token). The destination reserves pages at prepare
    # time, so a half-applied migration can always be discarded without
    # leaking pool capacity.

    def migratable_rids(self) -> list:
        """Rids currently RUNNING (token-exact checkpointable): decode
        state is fully described by (tokens, pool pages, last token).
        Queued/prefilling requests are cheaper to withdraw + replay.
        Raises :class:`MigrationUnsupported` for a block engine."""
        with self._lock:
            self._refuse_block_migration()
            return [rid for rid, r in self._running.items() if not r.done]

    def _refuse_block_migration(self):
        """A block in flight is more than (tokens, pages, last token):
        which of its positions are still masked, and rows in the pool
        that the next pass overwrites. Until a checkpoint on a block
        boundary exists, a caller that asks a block engine to migrate is
        told so, and withdraws and replays instead."""
        if self.block_len > 1:
            raise MigrationUnsupported(
                f"{type(self.engine).__name__} advances by blocks of "
                f"{self.block_len}: its running requests have no "
                "token-exact checkpoint; let them finish, or cancel and "
                "resubmit")

    def checkpoint_request(self, rid) -> dict | None:
        """Source side: freeze one running request for migration — pull
        it out of the decode set (pages stay put) and return the wire
        metadata. ``elapsed_s`` carries the request's source-side age so
        the destination can restart its clocks with ``total_s`` still
        spanning the whole life; the K/V payload itself travels via
        ``engine.export_kv``. Returns None when the rid is not running
        (finished, queued, or unknown) — the caller falls back to
        withdraw/requeue. Raises :class:`MigrationUnsupported` where the
        rid runs on a block engine (nothing is changed)."""
        with self._lock:
            r = self._running.get(rid)
            if r is None or r.done:
                return None
            self._refuse_block_migration()
            del self._running[rid]
            r.state = "migrating"
            self._migrating[rid] = r
            now = time.perf_counter()
            return {
                "rid": r.rid,
                "prompt": [int(t) for t in r.prompt],
                "tokens": [int(t) for t in r.tokens],
                "max_new": r.max_new_tokens,
                "eos_id": r.eos_id,
                "elapsed_s": now - r.submit_time,
                "queue_wait_s": (r.admit_time - r.submit_time)
                if r.admit_time is not None else 0.0,
                "ttft_s": (r.first_token_time - r.submit_time)
                if r.first_token_time is not None else 0.0,
                "prefill_s": r.prefill_s or 0.0,
                "prefill_chunks": r.prefill_chunks,
                "cached_prefix_len": r.cached_prefix_len,
                "router_wait_s": r.router_wait_s,
                "migrations": r.migrations + 1,
                "migrate_s": r.migrate_s,
                "migrate_bytes": r.migrate_bytes,
                "deadline_s": r.deadline_s,
            }

    def abort_migration(self, rid) -> bool:
        """Source side: restore a checkpointed request to the decode set
        after a failed/refused transfer — nothing moved, so the request
        resumes exactly where it paused."""
        with self._lock:
            r = self._migrating.pop(rid, None)
            if r is None:
                return False
            r.state = "running"
            self._running[rid] = r
            return True

    def complete_migration(self, rid):
        """Source side, after the destination ACKed: release the pages
        (publishing them to the source's prefix cache first, so the
        prefix stays warm here for future same-prefix traffic) and drop
        the request WITHOUT a terminal record — the destination now
        owns its lifecycle and will report it."""
        with self._lock:
            r = self._migrating.pop(rid)
            self._release(r, cached=r.tokens[:-1])
            self.migrations_out += 1
            obs.serving_requests_counter().inc(event="migrated_out")
            return r

    def withdraw(self, rid) -> bool:
        """Drain accelerator: pull a not-yet-running request back out of
        the scheduler (queued, or mid-prefill — its pages are released)
        so the router can re-dispatch it elsewhere. Running requests
        migrate instead; returns False for them."""
        with self._lock:
            for i, r in enumerate(self._queue):
                if r.rid == rid:
                    del self._queue[i]
                    return True
            r = self._prefilling.pop(rid, None)
            if r is None:
                return False
            self._drop_prefilling(r)
            return True

    def prepare_migration_in(self, rid, token_ids, prompt_len: int,
                             max_new: int):
        """Destination side, step 1: admission-check an inbound
        migration and pin any cached prefix. Returns ``(True,
        cached_len)`` — the source then ships only ``[cached_len, n)``
        — or ``(False, reason)``. Pages for the FULL completion (minus
        the cached prefix) are reserved here, so the commit can never
        OOM a pool that said yes."""
        eng = self.engine
        if not eng.can_migrate:
            return False, "engine_unsupported"
        with self._lock:
            if self.draining:
                return False, "draining"
            if rid in self._running or rid in self._prefilling \
                    or rid in self._migrating or rid in self._migrating_in:
                return False, "duplicate_rid"
            if (len(self._running) + len(self._prefilling)
                    + len(self._migrating_in)) >= self.max_concurrency:
                return False, "no_slot"
            pool = eng.pool
            total = int(prompt_len) + int(max_new)
            if total > pool.max_seq_len:
                return False, "too_long"
            cached_len = eng.begin_kv_import(rid, token_ids)
            need = pool.pages_needed(total) - cached_len // pool.page_size
            if not self._page_room(need):
                eng.abort_kv_import(rid)
                return False, "no_pages"
            self._reserved_pages += need
            self._migrating_in[rid] = {"need": need}
            return True, cached_len

    def adopt_migrated(self, meta: dict, k, v):
        """Destination side, step 2: scatter the transferred K/V into
        the pool (``engine.commit_kv_import``), rebuild the request
        from the wire metadata, and enter it into the decode set —
        the next decode step resumes token-exact. Returns ``(True,
        cached_len)`` or ``(False, reason)`` (on failure the staged
        reservation and cache pins are dropped; the source aborts and
        stays authoritative)."""
        from ..observability.reqtrace import RequestTrace
        eng = self.engine
        rid = int(meta["rid"])
        with self._lock:
            st = self._migrating_in.pop(rid, None)
            if st is None:
                return False, "no_staged_migration"
            self._reserved_pages -= st["need"]
            if len(self._running) + len(self._prefilling) \
                    >= self.max_concurrency:
                eng.abort_kv_import(rid)
                return False, "no_slot"
            prompt = np.asarray(meta["prompt"], np.int32)
            tokens = [int(t) for t in meta["tokens"]]
            # K/V exists for prompt + tokens[:-1]; the final sampled
            # token rides as _last_token and decodes next
            total_len = int(prompt.shape[0]) + len(tokens) - 1
            try:
                cached_len = eng.commit_kv_import(
                    rid, total_len, k, v, last_token=tokens[-1])
            except Exception as e:
                eng.abort_kv_import(rid)
                return False, repr(e)[:200]
            now = time.perf_counter()
            r = Request(rid, prompt, int(meta["max_new"]),
                        eos_id=meta.get("eos_id"))
            # restart the walltime clocks shifted by the source-side
            # age, so total_s still spans the request's WHOLE life; the
            # migration window itself is carried in migrate_s (the
            # doctor's migration bucket divides it out of the residual)
            r.submit_time = now - float(meta.get("elapsed_s") or 0.0)
            r.admit_time = r.submit_time \
                + float(meta.get("queue_wait_s") or 0.0)
            r.first_token_time = r.submit_time \
                + float(meta.get("ttft_s") or 0.0)
            r.prefill_s = float(meta.get("prefill_s") or 0.0)
            r.prefill_chunks = int(meta.get("prefill_chunks") or 0)
            r.cached_prefix_len = int(meta.get("cached_prefix_len") or 0)
            r.router_wait_s = float(meta.get("router_wait_s") or 0.0)
            r.migrations = int(meta.get("migrations") or 1)
            r.migrate_s = float(meta.get("migrate_s") or 0.0)
            r.migrate_bytes = int(meta.get("migrate_bytes") or 0)
            # deadline_s is relative to submit_time, which was just
            # rebuilt shifted by elapsed_s — so the deadline keeps
            # counting the request's WHOLE life across the hop
            if meta.get("deadline_s"):
                r.deadline_s = float(meta["deadline_s"])
            r.tokens = tokens
            r.state = "running"
            r.trace = RequestTrace(rid, r.submit_time)
            window = float(meta.get("migrate_window_s") or 0.0)
            if window > 0:
                r.trace.span("migrate_in", now - window, now,
                             bytes=r.migrate_bytes,
                             cached_prefix_rows=cached_len,
                             hop=r.migrations)
            held = len(eng.pool.table(rid))
            self._reserved_pages += self._completion_pages(r) - held
            self._running[rid] = r
            self.migrations_in += 1
            obs.serving_requests_counter().inc(event="migrated_in")
            return True, cached_len

    def abort_migration_in(self, rid) -> bool:
        """Destination side, bail-out: drop a staged inbound migration
        (reservation + cache pins) — idempotent by rid, so a retried
        ``migrate_begin`` after a half-applied attempt starts clean."""
        with self._lock:
            st = self._migrating_in.pop(rid, None)
            if st is None:
                return False
            self._reserved_pages -= st["need"]
            self.engine.abort_kv_import(rid)
            return True

    # ------------------------------------------------------- observability
    def request_records(self) -> list:
        """Terminal per-request summaries (finished + rejected +
        deadline_exceeded) — the records bench percentiles and
        post-hoc analysis read."""
        with self._lock:
            return [r.summary() for r in (self.finished + self.rejected
                                          + self.deadline_exceeded)]

    def status(self) -> dict:
        """JSON snapshot for the ``/status`` endpoint: queue and request
        counts, KV-pool utilization/fragmentation, SLO burn rates, last
        anomaly, engine shape/compile info."""
        with self._lock:
            st = {
                "healthy": self.healthy,
                "draining": self.draining,
                "last_error": self.last_error,
                "ts": time.time(),
                "uptime_s": round(time.time() - self._start_ts, 3),
                "queue_depth": len(self._queue),
                "prefilling": len(self._prefilling),
                "running": len(self._running),
                "migrating_out": len(self._migrating),
                "migrating_in": len(self._migrating_in),
                "migrations_out": self.migrations_out,
                "migrations_in": self.migrations_in,
                "finished": len(self.finished),
                "rejected": len(self.rejected),
                "deadline_exceeded": len(self.deadline_exceeded),
                "steps": self.steps,
                "kv_pool": self.engine.pool.stats(),
                "decode_buckets": list(self.buckets),
                "slo": self.slo.snapshot() if self.slo is not None
                else None,
            }
            # overload-control snapshot: the mode machine, the current
            # backpressure hint, and the admission-pricing inputs — a
            # client that gets a retry_after reject can see the same
            # numbers the scheduler priced it with
            mode_s = dict(self.mode_seconds)
            mode_s[self.mode] += time.perf_counter() - self._mode_since
            burn = 0.0
            if self.slo is not None:
                rates = self.slo.burn_rates()
                burn = max(rates.values()) if rates else 0.0
            st["overload"] = {
                "mode": self.mode,
                "mode_transitions": self.mode_transitions,
                "mode_seconds": {k: round(v, 3)
                                 for k, v in mode_s.items()},
                "degraded_s_total": round(self.degraded_s_total, 6),
                "deadline_cancelled": self.deadline_cancelled,
                "retry_after_s": self._retry_after_estimate(),
                "admission_cost": {
                    "backlog": len(self._queue) + len(self._prefilling)
                    + len(self._running),
                    "drain_rate_rps": round(self._drain_rate(), 4),
                    "free_pages": self.engine.pool.free_pages,
                    "reserved_pages": self._reserved_pages,
                    "prefill_token_budget": self.prefill_token_budget,
                    "burn_rate": round(burn, 4),
                },
            }
            st["engine"] = self.engine.status()
        from ..observability import anomaly
        st["last_anomaly"] = anomaly.last_anomaly()
        return st

    def serve_http(self, host: str = "127.0.0.1", port: int = 0):
        """Start the live /metrics + /healthz + /status endpoint on a
        daemon thread; returns the server (``.url``, ``.close()``)."""
        from ..observability.httpd import ServingStatusServer
        return ServingStatusServer(status_fn=self.status, host=host,
                                   port=port)


# ---------------------------------------------------------------------------
# static bucket-closure proof (device-free)
# ---------------------------------------------------------------------------

class _ShapeProbeEngine(EngineContract):
    """Engine stand-in for :func:`simulate_decode_signatures`: real
    :class:`~.kv_pool.PagePool` bookkeeping and bucket tables, but
    prefill/decode only record the shapes they were asked for. It is
    the :class:`~.engine_core.EngineContract` and no more — in every
    prefill mode (classic bucketed, chunked, disaggregated) and, with
    ``block_len`` > 1, as a block engine whose every block takes one
    denoising and one commit pass."""

    def __init__(self, decode_buckets, prefill_buckets, page_size,
                 num_pages, max_seq_len, prefill_chunk=None,
                 disaggregated=False, block_len=1):
        from .kv_pool import PagePool
        self.decode_buckets = tuple(sorted(set(decode_buckets)))
        self.prefill_buckets = tuple(sorted(set(prefill_buckets)))
        self.prefill_chunk = int(prefill_chunk) if prefill_chunk else None
        self.disaggregated = bool(disaggregated)
        self.block_len = int(block_len)
        self.pool = PagePool(num_pages, page_size, num_layers=1,
                             num_kv_heads=1, head_dim=1,
                             max_seq_len=max_seq_len)
        self.decode_signatures_used: set = set()
        self.prefill_signatures_used: set = set()
        self._chunk_pos: dict = {}
        self._block: dict = {}      # seq_id -> the current block: are
        #                             its rows "held", "passes" taken,
        #                             the "prompt" tokens it begins with

    def prefill(self, seq_id, prompt_ids):
        n = int(np.asarray(prompt_ids).reshape(-1).shape[0])
        sb = smallest_bucket(self.prefill_buckets, n, "prompt tokens")
        self.pool.alloc(seq_id, n)
        if self.disaggregated:
            # prefill program on the prefill mesh + the KV-handoff
            # scatter landing on the decode mesh — both must stay
            # inside the per-side bucket sets
            self.prefill_signatures_used.add(("disagg", sb))
            self.prefill_signatures_used.add(("scatter", sb))
        else:
            self.prefill_signatures_used.add((1, sb))
        return 0

    # ---- chunked-mode surface the scheduler drives -----------------
    def prefill_begin(self, seq_id, prompt_ids):
        n = int(np.asarray(prompt_ids).reshape(-1).shape[0])
        bl = self.block_len
        rest, n = n % bl, n // bl * bl      # whole blocks are prefilled
        self.pool.alloc(seq_id, n or bl)
        self._chunk_pos[seq_id] = [0, n]
        self._block[seq_id] = {"held": n == 0, "passes": 0,
                               "prompt": rest}
        return 0

    def prefill_step(self, seq_id):
        pos, n = self._chunk_pos[seq_id]
        first = 0 if self.block_len == 1 else None
        if pos >= n:                # shorter than a block
            del self._chunk_pos[seq_id]
            return 0, True, first
        c = min(self.prefill_chunk, n - pos)
        self.prefill_signatures_used.add(
            ("chunk", self.prefill_chunk, self.pool.max_pages_per_seq))
        pos += c
        self._chunk_pos[seq_id][0] = pos
        if pos < n:
            return c, False, None
        del self._chunk_pos[seq_id]
        return c, True, first

    def decode(self, seq_ids, bucket):
        self.decode_signatures_used.add(
            (int(bucket), self.pool.max_pages_per_seq))
        if self.block_len == 1:
            return [0] * len(seq_ids)
        out = []
        for sid in seq_ids:
            st = self._block[sid]
            st["held"], st["passes"] = True, st["passes"] + 1
            if st["passes"] % 2:    # denoising pass: nothing comes out
                out.append(([], [], []))
            else:                   # commit: the block, and a new one
                new = self.block_len - st["prompt"]
                st["held"], st["prompt"] = False, 0
                out.append(([0] * new, [0] * new, [1.0] * new))
        return out

    # ---- a block engine's three extra calls ------------------------
    def starts_block(self, seq_id) -> bool:
        return not self._block[seq_id]["held"]

    def masked_positions(self, seq_ids) -> int:
        return sum(self.block_len for s in seq_ids
                   if not self._block[s]["passes"] % 2)

    def note_emitted(self, emitted, dropped):
        pass

    def release(self, seq_id, token_ids=None):
        self._block.pop(seq_id, None)
        self.pool.free(seq_id)


def simulate_decode_signatures(decode_buckets, prefill_buckets, page_size,
                               num_pages, max_seq_len, n_requests=200,
                               seed=0, arrival_p=0.35, prefill_chunk=None,
                               disaggregated=False, cancel_p=0.0,
                               block_len=1):
    """Replay the REAL scheduler over a randomized admission mix (ragged
    prompt lengths, random completion budgets, bursty arrivals) with a
    shape-probe engine. Returns ``(decode_sigs_used, prefill_sigs_used,
    allowed_decode_sigs, allowed_prefill_sigs)`` — the recompile lint
    proves ``used ⊆ allowed``: the AOT bucket set is closed and no
    request mix can retrace at serving time. ``prefill_chunk`` /
    ``disaggregated`` replay the chunked (prefix-cache) and
    disaggregated engine modes, whose prefill-side program sets differ
    (one chunk signature; per-bucket prefill + scatter); ``block_len``
    > 1 replays a block engine (chunked), through ``_block_tick``.

    ``cancel_p`` mixes randomized deadline-style cancellations into
    the replay: after each tick, with that probability, one live
    request (running, else prefilling, else queued) is cancelled
    through :meth:`ContinuousBatchingScheduler.cancel` — the exact
    code path a deadline expiry takes. Cancellation must introduce
    ZERO new signatures (cancel = evict, never a recompile), which is
    what the ``check_program`` gate asserts."""
    rng = np.random.default_rng(seed)
    eng = _ShapeProbeEngine(decode_buckets, prefill_buckets, page_size,
                            num_pages, max_seq_len,
                            prefill_chunk=prefill_chunk,
                            disaggregated=disaggregated,
                            block_len=block_len)
    sched = ContinuousBatchingScheduler(eng)
    submitted = 0
    while submitted < n_requests or sched.pending:
        while submitted < n_requests and rng.random() < arrival_p:
            s = int(rng.integers(1, max_seq_len))
            new = int(rng.integers(1, max(2, max_seq_len - s + 1)))
            sched.submit(np.zeros(s, np.int32), new)
            submitted += 1
        if sched.pending:
            sched.step()
        # short-circuit keeps the rng stream byte-identical for the
        # cancel_p=0 replays (their signature sets are golden)
        if cancel_p and rng.random() < cancel_p:
            live = (sorted(sched._running) or sorted(sched._prefilling)
                    or [r.rid for r in sched._queue])
            if live:
                sched.cancel(live[int(rng.integers(len(live)))])
    pages_per_seq = eng.pool.max_pages_per_seq
    allowed_decode = {(b, pages_per_seq) for b in eng.decode_buckets}
    if prefill_chunk:
        allowed_prefill = {("chunk", eng.prefill_chunk, pages_per_seq)}
    elif disaggregated:
        allowed_prefill = {("disagg", sb) for sb in eng.prefill_buckets} \
            | {("scatter", sb) for sb in eng.prefill_buckets}
    else:
        allowed_prefill = {(1, sb) for sb in eng.prefill_buckets}
    return (eng.decode_signatures_used, eng.prefill_signatures_used,
            allowed_decode, allowed_prefill)
