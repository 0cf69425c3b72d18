"""The driver of a serving cell whose step is not one token (traffic
``"kind": "serve_blocks"``): generation by diffusion over blocks.

The same open loop, lead-in, window and drain as ``drivers/serve.py``
(whose ``Tracked`` and ``warm_up`` it uses as they are), over the engine
that the model family's ``build_engine`` returns under
``ContinuousBatchingScheduler``, and the same counters under the same
names, so that the serving metrics read them unchanged. What differs:

- a decode call is one *pass* over each running sequence's block of
  ``engine.block_len`` positions, so ``ticks`` holds one live length **for
  every position a pass processed** (``block_len`` a sequence), which is
  what ``step_mfu.serve`` counts; a block's tokens come out together, so
  three gaps in four between tokens are 0;
- ``passes`` (per pass: end, sequences, how many of them committed, the
  busiest expert's assignments over the mean in every layer, the experts
  of every layer that got a token, the assignments) and ``chunk_loads``
  (end, the chunk's real tokens, and the same two of every chunk
  program) feed the block metrics and the expert kernel's roofline,
  which counts the rows from the shapes and holds the engine's count of
  assignments against them;
- the comparison. The engine records for every generated position the
  pass of its block at which it was unmasked and the confidence it read
  there (``Request.block_record``). For the longest finished request and
  ``check_requests`` more, drawn from the seed, the family's
  ``served_gaps`` recomputes every pass's logits from the prompt, the
  served tokens and that record; compared are ``logit_gap``,
  ``order_gap`` and ``conf_gap`` (``models/sdar.py``), with
  ``never_finished`` and ``compiles_in_window``.

A traffic file of this kind also gives the deployment's ``max_seq_len``.
"""
from __future__ import annotations

import gc
import sys
import time

import numpy as np

from drivers.serve import EngineProbe, Tracked, warm_up
from harness import peaks, trace, traffic


class BlockProbe(EngineProbe):
    """``EngineProbe`` for an engine whose decode is a pass over blocks:
    a tick holds the block's end once for each of its positions, and
    every pass and chunk keeps what the engine counted of its experts."""

    def __init__(self, engine, spans):
        super().__init__(engine, spans)
        self._engine, self.passes, self.chunk_loads = engine, [], []
        self._unread = []       # real tokens of the chunk programs whose
        #                         counts the engine has not read back yet

    @staticmethod
    def _experts(load):
        """Of one program's ``[L, E]`` assignment counts: the busiest
        expert over the mean, the experts that got a token, and the
        assignments, layer by layer."""
        load = np.asarray(load, np.float64)
        mean = np.maximum(load.mean(1), 1e-9)
        return ((load.max(1) / mean).tolist(), (load > 0).sum(1).tolist(),
                load.sum(1).tolist())

    def prefill_step(self, seq_id):
        processed, done, token = super().prefill_step(seq_id)
        if processed:
            self._unread.append(processed)
        if done and processed:
            end = self._spans.records[-1][2]
            self.chunk_loads += [
                (end, tokens) + self._experts(load)[1:] for tokens, load
                in zip(self._unread, self._engine.last_chunk_loads)]
            self._unread.clear()
        return processed, done, token

    def decode(self, seq_ids, bucket=None):
        bl = self._engine.block_len
        lens = [self._pool.seq_len(s) for s in seq_ids for _ in range(bl)]
        with self._spans.span("engine.decode"):
            out = self._decode(seq_ids, bucket)
        end = self._spans.records[-1][2]
        self.ticks.append((end, lens, bucket))
        self.passes.append((end, len(seq_ids), sum(bool(o[0]) for o in out))
                           + self._experts(self._engine.last_pass_load))
        return out


def serve(run):
    """Set-up, the lead-in, the window and the drain. Fills the run's
    counters and returns ``[(prompt, record)]`` of the requests that the
    reference will read."""
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
    probe = BlockProbe(engine, spans)

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
    drained = max((t.token_times[-1] for t in finished), default=t_close)
    print(f"drain: the window's last request finished "
          f"{drained - t_close:.1f} s after its close (limit {limit})",
          file=sys.stderr)
    done = {id(t) for t in finished}
    late = t_close + limit     # a request that failed waited at least so long
    inside = lambda rows: [r for r in rows if lo <= r[0] < hi]
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
        chunks=inside(probe.chunks), ticks=inside(probe.ticks),
        passes=inside(probe.passes), chunk_loads=inside(probe.chunk_loads),
        live_tokens_peak=live_peak, pool=pool.stats(),
        drain_s=drained - t_close,
        engine_passes=dict(engine.status()["passes"]))
    run.memory_peak_bytes = peaks.memory_peak_bytes(run.chips)

    # the sample the reference reads: the longest, and some drawn by seed
    finished.sort(key=lambda t: -(len(t.prompt) + t.n_out))
    rng = np.random.default_rng([run.seed, 0xC4])
    picks = finished[:1] + [finished[1 + int(i)] for i in rng.permutation(
        max(0, len(finished) - 1))[:mix["check_requests"]]]
    engine.params = pool.k_pages = pool.v_pages = None
    return [(t.prompt, list(t.request.block_record)) for t in picks]


def run(run):
    """The cell, then the comparison. Under ``lower_precision`` (the
    control) the reference in the family's ``SERVING_CONTROL`` precision
    stands in the program's place: at every pass of the served requests,
    the token that it puts first and the position that it would unmask
    are the ones held against the sound reference."""
    served = serve(run)
    gc.collect()        # the engine, its pool and the probe around it
    mode = run.model.SERVING_CONTROL if run.steer.lower_precision else None
    began = time.perf_counter()
    gaps = run.model.served_gaps(run.config, run.seed, served, mode=mode) \
        if served else dict(dict.fromkeys(
            ("logit_gap", "order_gap", "conf_gap"),
            float("inf")), checked_tokens=0)
    print(f"reference: {len(served)} requests, {gaps['checked_tokens']} "
          f"tokens, {time.perf_counter() - began:.1f} s", file=sys.stderr)
    run.counters["checked_tokens"] = gaps["checked_tokens"]
    run.compare("logit_gap", gaps["logit_gap"])
    run.compare("order_gap", gaps["order_gap"])
    run.compare("conf_gap", gaps["conf_gap"])
    run.compare("never_finished", run.failed)
    run.compare("compiles_in_window", run.compiles_in_window)
