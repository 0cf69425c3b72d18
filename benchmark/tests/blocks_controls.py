#!/usr/bin/env python3
"""Readings of a block-diffusion serving cell on the chip, beside
``chip_controls.py`` (which a PR that adds a cell may not edit), several
seeds in one process:

    python3 benchmark/tests/blocks_controls.py --workload <cell> \\
        --what sound|control|faults|sweep --seeds 101,102 [--seconds 45]
        [--rates 2,3,4,5]

``sound``: the cell as committed, through ``run.py``'s own path; the
lower readings, with the distribution of the gaps between blocks and
their kinds (how many chunk programs ran inside a block's five passes).
``control``: the float8 reference in the program's place through the
harness's own comparison (``Steer.lower_precision``), which has to say
``correct`` false; the upper readings. ``faults``: the cell served once,
then the reference with each fault of the family's ``FAULTS`` planted (a
commit that stored a denoising pass's rows, a causal mask inside the
block, the last expert dropped, weights not renormalised) and the float8
reference, each in the program's place and held against the sound
reference: every one has to pass a limit. ``sweep``: one engine, ``--rates``
in turn for ``--seconds`` each, drained between: the knee.
Every reading is printed as one JSON line and appended to
``chiprun_out/blocks-<cell>.jsonl``.
"""
import argparse
import gc
import json
import os
import statistics
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
REPO = os.path.dirname(BENCH)
for path in (REPO, BENCH, HERE):
    if path not in sys.path:
        sys.path.insert(0, path)

from chip_controls import one_run                   # noqa: E402
from harness.core import percentile as quantile     # noqa: E402


def block_gaps(counters, per_block=5):
    """The gaps between a sequence's blocks, from the passes: a block that
    came out at pass i began ``per_block`` passes before; its kind is the
    number of chunk programs that ended in between."""
    ends = [p[0] for p in counters["passes"]]
    chunks = sorted(c[0] for c in counters["chunks"])
    gaps, kinds = [], []
    for i, p in enumerate(counters["passes"]):
        if p[2] and i >= per_block:
            lo, hi = ends[i - per_block], ends[i]
            n = sum(lo < c <= hi for c in chunks)
            gaps += [hi - lo] * p[2]
            kinds += [min(n, 2)] * p[2]
    return gaps, kinds


def prepared(cell, seed, seconds):
    from harness import peaks
    from harness.core import Run, Steer, load_json
    from harness.spans import Spans
    from paddle_tpu.utils.compile_cache import enable_compile_cache
    run = Run(load_json(os.path.join(REPO, "BENCHMARK.json")), cell, seed,
              seconds, 0, Steer(), BENCH, time.perf_counter())
    run.device = peaks.look_for_chips(run.chips, run.steer)
    run.peaks = peaks.peaks_of(run.device)
    enable_compile_cache()
    run.spans = Spans()
    return run


def sweep(run, rates, seconds, emit):
    import numpy as np
    from drivers.serve import Tracked, warm_up
    from harness import traffic
    from paddle_tpu.serving import ContinuousBatchingScheduler
    cfg, mix = run.config, run.traffic
    engine = run.model.build_engine(cfg, mix, run.seed)
    sched = ContinuousBatchingScheduler(engine, max_queue=1 << 16)
    warm_up(sched, engine, cfg["vocab_size"],
            np.random.default_rng([run.seed, 0x3A]))
    for rate in rates:
        offered = dict(mix, arrivals=dict(mix["arrivals"], rate_per_s=rate))
        todo = [Tracked(*r) for r in traffic.requests(
            offered, run.seed, 0.0, seconds, seconds, cfg["vocab_size"])]
        live, nxt, running, first = [], 0, [], len(sched.step_times)
        t0 = time.perf_counter()
        at_end = None
        while True:
            now = time.perf_counter()
            if at_end is None and now >= t0 + seconds:
                at_end = dict(queued=len(sched._queue),
                              prefilling=len(sched._prefilling),
                              running=len(sched._running))
                last = len(sched.step_times)
            if at_end is not None and (not sched.pending
                                       or now >= t0 + seconds + 120):
                break
            while at_end is None and nxt < len(todo) \
                    and t0 + todo[nxt].due <= now:
                t = todo[nxt]
                t.request = sched.submit(t.prompt, max_new_tokens=t.n_out)
                live.append(t)
                nxt += 1
            if sched.pending:
                sched.step()
                stamp = time.perf_counter()
                running.append(len(sched._running))
                for t in live:
                    new = len(t.request.tokens) - len(t.token_times)
                    t.token_times.extend([stamp] * new)
                live = [t for t in live if not t.settled]
            else:
                time.sleep(max(0.0, min(t0 + seconds, t0 + todo[nxt].due
                                        if nxt < len(todo) else t0 + seconds)
                               - time.perf_counter()))
        seen = [t for t in todo if t.token_times]
        gaps = [b - a for t in seen
                for a, b in zip(t.token_times, t.token_times[1:])]
        blocks = [g for g in gaps if g > 0]
        emit(rate=rate, offered=len(todo), submitted=nxt, **at_end,
             mean_running=statistics.fmean(running) if running else 0.0,
             ttft_p50_ms=1e3 * quantile(
                 [t.token_times[0] - (t0 + t.due) for t in seen], 0.5),
             ttft_p90_ms=1e3 * quantile(
                 [t.token_times[0] - (t0 + t.due) for t in seen], 0.9),
             itl_p90_ms=1e3 * quantile(gaps, 0.9),
             block_gap_p50_ms=1e3 * quantile(blocks, 0.5),
             block_gap_p90_ms=1e3 * quantile(blocks, 0.9),
             pass_ms=1e3 * statistics.median(sched.step_times[first:last]),
             tokens_per_s=sum(t0 <= s < t0 + seconds for t in todo
                              for s in t.token_times) / seconds,
             drained_s=time.perf_counter() - t0 - seconds,
             pool_live_tokens=engine.pool.live_tokens)
        sched.finished.clear()


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--what", required=True,
                    choices=("sound", "control", "faults", "sweep"))
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=45.0)
    ap.add_argument("--rates", default="")
    ap.add_argument("--stand-ins", default="",
                    help="faults: which to plant (all where empty)")
    ap.add_argument("--check", type=int, default=0,
                    help="faults: replay only the last N picked requests")
    args = ap.parse_args()
    from harness.core import Steer
    os.makedirs(os.path.join(REPO, "chiprun_out"), exist_ok=True)
    log = os.path.join(REPO, "chiprun_out", f"blocks-{args.workload}.jsonl")

    def emit(**row):
        line = json.dumps(dict(workload=args.workload, what=args.what,
                               **row))
        print(line, flush=True)
        with open(log, "a") as f:
            f.write(line + "\n")

    for seed in (int(s) for s in args.seeds.split(",")):
        if args.what == "sweep":
            run = prepared(args.workload, seed, args.seconds)
            sweep(run, [float(r) for r in args.rates.split(",")],
                  args.seconds, lambda **row: emit(seed=seed, **row))
            continue
        if args.what == "faults":
            run = prepared(args.workload, seed, args.seconds)
            served = run.driver.serve(run)[-args.check:]
            gc.collect()
            control = run.model.SERVING_CONTROL
            wanted = args.stand_ins.split(",") if args.stand_ins \
                else [control, *run.model.FAULTS]
            began = time.perf_counter()
            emit(seed=seed, stand_in="none", failed=run.failed,
                 requests=len(served),
                 **run.model.served_gaps(run.config, seed, served),
                 seconds=time.perf_counter() - began)
            for name in wanted:
                emit(seed=seed, stand_in=name, **run.model.served_gaps(
                    run.config, seed, served,
                    **({"mode": name} if name == control
                       else {"fault": name})))
            continue
        steer = Steer(lower_precision=args.what == "control")
        line, kept = one_run(args.workload, seed, args.seconds, steer)
        row = {k: v["value"] for k, v in line["compared"].items()}
        emit(seed=seed, correct=line["correct"], attempted=line["attempted"],
             failed=line["failed"], metrics={k: v["value"] for k, v in
                                             line["metrics"].items()},
             memory_peak_bytes=line["device"]["memory_peak_bytes"],
             checked_tokens=kept.counters.get("checked_tokens"),
             drain_s=kept.counters.get("drain_s"),
             passes=kept.counters.get("engine_passes"), **row)
        gaps, kinds = block_gaps(kept.counters)
        if gaps:
            emit(seed=seed, distribution="block_gap_ms", n=len(gaps),
                 kinds={str(k): kinds.count(k) / len(kinds)
                        for k in (0, 1, 2)},
                 by_kind={str(k): 1e3 * statistics.median(
                     g for g, kk in zip(gaps, kinds) if kk == k)
                     for k in set(kinds)},
                 **{f"p{q}": 1e3 * quantile(gaps, q / 100)
                    for q in (25, 50, 55, 60, 65, 70, 75, 90, 99)})
        vals = sorted(kept.counters.get("itl_s", ()))
        if vals:
            emit(seed=seed, distribution="itl_ms", n=len(vals),
                 zeros=sum(v == 0 for v in vals) / len(vals),
                 **{f"p{q}": 1e3 * quantile(vals, q / 100)
                    for q in (50, 75, 80, 85, 88, 90, 92, 95, 99)})


if __name__ == "__main__":
    main()
