"""The driver of a serving cell (traffic ``"kind": "serve"``).

The timed path is ``ContinuousBatchingScheduler.submit`` and ``step()``
over the engine that the model family's ``build_engine`` returns, driven
from one process: an open loop that submits each request when it is due
and steps the scheduler while anything is pending. The generator runs a
lead-in before the window opens (part of set-up), so that the window
starts with a batch in flight; the requests due inside the window are the
sample, and the run goes on past the window until they have finished or
``drain_limit_s`` has passed, after which an unfinished one counts as
failed. Arrivals go on at the same rate meanwhile.

Besides what ``harness/traffic.py`` reads, a traffic file of this kind
holds the deployment's ``decode_buckets`` and ``pool_tokens``,
``lead_in_s``, ``drain_limit_s``, ``check_requests`` (how many finished
requests, drawn from the seed, the reference reads beside the longest)
and ``trace_seconds``.
"""
from __future__ import annotations

import gc
import time

import numpy as np

from harness import peaks, trace, traffic

LIVE = ("queued", "prefilling", "running")


class Tracked:
    """One request as the load generator saw it (perf_counter seconds)."""

    def __init__(self, due, prompt, n_out):
        self.due, self.prompt, self.n_out = due, prompt, n_out
        self.submitted = None
        self.request = None
        self.token_times = []

    @property
    def settled(self):
        return self.request is not None and self.request.state not in LIVE


class EngineProbe:
    """Spans and counters at the engine's boundary, put around the bound
    methods of this one engine: every prefill chunk as ``(end, first
    position, tokens)`` and every decode tick as ``(end, live lengths,
    bucket)``. The benchmark's own; nothing inside the program changes."""

    def __init__(self, engine, spans):
        self.chunks, self.ticks = [], []
        self._progress, self._spans, self._pool = {}, spans, engine.pool
        self._begin, self._chunk, self._decode = (
            engine.prefill_begin, engine.prefill_step, engine.decode)
        engine.prefill_begin = self.prefill_begin
        engine.prefill_step = self.prefill_step
        engine.decode = self.decode

    def prefill_begin(self, seq_id, prompt_ids):
        with self._spans.span("engine.prefill_begin"):
            cached = self._begin(seq_id, prompt_ids)
        self._progress[seq_id] = cached
        return cached

    def prefill_step(self, seq_id):
        with self._spans.span("engine.prefill_step"):
            processed, done, token = self._chunk(seq_id)
        at = self._progress.pop(seq_id, 0)
        self.chunks.append((self._spans.records[-1][2], at, processed))
        if not done:
            self._progress[seq_id] = at + processed
        return processed, done, token

    def decode(self, seq_ids, bucket=None):
        lens = [self._pool.seq_len(s) for s in seq_ids]
        with self._spans.span("engine.decode"):
            out = self._decode(seq_ids, bucket)
        self.ticks.append((self._spans.records[-1][2], lens, bucket))
        return out


def warm_up(sched, engine, vocab, rng):
    """Run every decode bucket and the chunk program once: as many short
    requests as the widest bucket, ending one by one."""
    for k in range(max(engine.decode_buckets)):
        sched.submit(rng.integers(0, vocab, 2 * engine.pool.page_size + 1,
                                  dtype=np.int32), max_new_tokens=2 + k)
    while sched.pending:
        sched.step()
    sched.finished.clear()
    sched.step_times.clear()


def serve(run):
    """Set-up, the lead-in, the window and the drain. Fills the run's
    counters and returns ``[(prompt, served tokens)]`` of the requests
    that the reference will read. Everything of the program that this
    function holds dies with it."""
    from paddle_tpu.serving import ContinuousBatchingScheduler
    cfg, mix, spans = run.config, run.traffic, run.spans
    vocab = cfg["vocab_size"]
    engine = run.model.build_engine(cfg, mix, run.seed)
    if run.steer.break_program:
        engine = run.steer.break_program(engine) or engine
    run.mark("build_engine")
    pool = engine.pool
    sched = ContinuousBatchingScheduler(engine, max_queue=1 << 16)
    warm_up(sched, engine, vocab, np.random.default_rng([run.seed, 0x3A]))
    run.mark("warm_up")
    probe = EngineProbe(engine, spans)

    lead, limit = mix["lead_in_s"], mix["drain_limit_s"]
    todo = [Tracked(*r) for r in traffic.requests(
        mix, run.seed, lead, run.seconds, lead + run.seconds + limit,
        vocab)]
    sample = [t for t in todo if lead <= t.due < lead + run.seconds]
    recording = trace.Recording(run, mix["trace_seconds"])
    live, nxt, live_peak, first_tick, last_tick = [], 0, 0, None, None
    t_gen = time.perf_counter()
    t_open, t_close = t_gen + lead, t_gen + lead + run.seconds
    while nxt < len(todo) or sched.pending:
        now = time.perf_counter()
        if first_tick is None and now >= t_open:
            run.open_window(t_open)
            first_tick = len(sched.step_times)
        if recording.due(now - t_open):
            recording.start()
        if last_tick is None and now >= t_close:
            run.close_window(t_close)
            last_tick = len(sched.step_times)
            recording.stop()
        if now >= t_close and (all(t.settled for t in sample)
                               or now >= t_close + limit):
            break
        with spans.span("submit"):
            while nxt < len(todo) and t_gen + todo[nxt].due <= now:
                t = todo[nxt]
                t.submitted = time.perf_counter()
                t.request = sched.submit(t.prompt, max_new_tokens=t.n_out)
                live.append(t)
                nxt += 1
        if sched.pending:
            with spans.span("sched.step"):
                sched.step()
            stamp = time.perf_counter()
            for t in live:
                new = len(t.request.tokens) - len(t.token_times)
                t.token_times.extend([stamp] * new)
            live = [t for t in live if not t.settled]
            if first_tick is not None and last_tick is None:
                live_peak = max(live_peak, pool.live_tokens)
        else:
            with spans.span("wait_for_arrival"):
                time.sleep(max(0.0, min(t_gen + todo[nxt].due,
                                        t_close + limit)
                               - time.perf_counter()))
    if last_tick is None:
        raise RuntimeError("the load ended before the window closed")
    recording.read()

    lo, hi = run.window
    finished = [t for t in sample if t.settled
                and t.request.state == "finished"
                and len(t.token_times) == t.n_out]
    run.attempted = len(sample)
    run.failed = len(sample) - len(finished)
    done = {id(t) for t in finished}
    late = t_close + limit     # a request that failed waited at least so long
    run.counters.update(
        ttft_s=[(t.token_times[0] if id(t) in done else late)
                - (t_gen + t.due) for t in sample],
        itl_s=[b - a for t in finished
               for a, b in zip(t.token_times, t.token_times[1:])],
        tokens_in_window=sum(lo <= s < hi for t in todo
                             for s in t.token_times),
        gen_late_s=[t.submitted - (t_gen + t.due) for t in sample
                    if t.submitted is not None],
        queue_wait_s=[t.request.admit_time - (t_gen + t.due)
                      for t in sample if t.request is not None
                      and t.request.admit_time is not None],
        decode_step_s=list(sched.step_times[first_tick:last_tick]),
        chunks=[c for c in probe.chunks if lo <= c[0] < hi],
        ticks=[k for k in probe.ticks if lo <= k[0] < hi],
        live_tokens_peak=live_peak, pool=pool.stats())
    run.memory_peak_bytes = peaks.memory_peak_bytes(run.chips)

    # the sample the reference reads: the longest, and some drawn by seed
    finished.sort(key=lambda t: -(len(t.prompt) + t.n_out))
    rng = np.random.default_rng([run.seed, 0xC4])
    picks = finished[:1] + [finished[1 + int(i)] for i in rng.permutation(
        max(0, len(finished) - 1))[:mix["check_requests"]]]
    engine.params = pool.k_pages = pool.v_pages = None
    return [(t.prompt, [int(x) for x in t.request.tokens]) for t in picks]


def run(run):
    """The cell, then the comparison. Under ``lower_precision`` (the
    control) the reference in the family's ``SERVING_CONTROL`` precision
    stands in the program's place: at every position of the same prompts
    and served tokens, the token that it puts first is the one compared."""
    served = serve(run)
    gc.collect()        # the engine, its pool and the probe around it
    weights = run.model.init_weights(run.config, run.seed)
    mode = run.model.SERVING_CONTROL if run.steer.lower_precision else None
    gaps = [run.model.served_token_gaps(
        run.config, weights, prompt, tokens,
        run.config["max_position_embeddings"], mode=mode)
        for prompt, tokens in served]
    run.counters["checked_tokens"] = int(sum(len(g) for g in gaps))
    run.compare("logit_gap", max((float(g.max()) for g in gaps),
                                 default=float("inf")))
    run.compare("never_finished", run.failed)
    run.compare("compiles_in_window", run.compiles_in_window)
