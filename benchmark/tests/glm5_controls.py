#!/usr/bin/env python3
"""Readings of the sparse latent serving cell on the chip, beside
``hybrid_controls.py`` (which a PR that adds a cell may not edit),
several seeds in one process:

    python3 benchmark/tests/glm5_controls.py --workload <cell> \\
        --what sound|faults|sweep --seeds 101,102 [--seconds 45]
        [--rates 0.3,0.5] [--drain 60] [--check 1] [--stand-ins ...]

``sound``: the cell as committed through ``run.py``'s own path (untraced),
a seed at a time; the lower readings, the gaps between tokens by
percentile and the ticks by bucket. ``faults``: the cell served once
(``drivers/serve_sparse.py``), the sound comparison, and on the same
served tokens and selections, over the same sound reference pass, the
comparison with the float8 control in the program's place (``correct``
has to be false) and with each fault of the family's ``FAULTS`` planted
in the reference (``--stand-ins`` may add
``bf16``: the reference at the served precision, what rounding alone
reads), over the last ``--check`` of the checked requests. ``sweep``: one
engine, ``--rates`` in turn for ``--seconds`` each from what the last
left, a drain of at most ``--drain`` seconds between: the knee. Every
reading is printed as one JSON line and appended to
``chiprun_out/glm5-<cell>.jsonl``.
"""
import argparse
import collections
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

from blocks_controls import prepared                # noqa: E402
from chip_controls import one_run                   # noqa: E402
from harness.core import Steer                      # noqa: E402
from harness.core import percentile as quantile     # noqa: E402


def compared(run, served, stand_ins):
    """``drivers/serve_sparse.py``'s comparison over what was served, with
    the program's own tokens (``None``) or a ``(mode, fault)`` stand-in in
    the program's place, each of ``stand_ins`` over one sound reference
    pass a request: every number beside its limit, and ``correct``, a row
    a stand-in."""
    import numpy as np
    weights = run.model.init_weights(run.config, run.seed)
    began = time.perf_counter()
    checks = [run.model.stand_in_checks(run.config, weights, prompt, tokens,
                                        probes, stand_ins)
              for prompt, tokens, probes in served]
    rows = []
    for i in range(len(stand_ins)):
        run.compared = []
        gaps = [c[i][0] for c in checks]
        misses = np.concatenate([c[i][1] for c in checks] + [np.zeros(0)])
        routes = np.concatenate([c[i][2] for c in checks] + [np.zeros(0)])
        run.compare("logit_gap", max(float(g.max()) for g in gaps))
        run.compare("selection_miss", float(misses.mean()) if misses.size
                    else float("inf"))
        run.compare("router_gap", float(routes.mean()) if routes.size
                    else float("inf"))
        run.compare("never_finished", run.failed)
        run.compare("compiles_in_window", run.compiles_in_window)
        rows.append(dict(
            {name: value for name, value, _ in run.compared},
            limits={name: limit for name, _, limit in run.compared},
            correct=run.correct, selections=int(misses.size),
            routes=int(routes.size),
            router_gap_max=float(routes.max()) if routes.size else None,
            checked_tokens=int(sum(len(g) for g in gaps)),
            lengths=[len(p) + len(t) for p, t, _ in served],
            seconds=time.perf_counter() - began))
    return rows


def sweep(run, rates, seconds, drain, emit):
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
        at_end = last = None
        while True:
            now = time.perf_counter()
            if at_end is None and now >= t0 + seconds:
                at_end = dict(queued=len(sched._queue),
                              prefilling=len(sched._prefilling),
                              running=len(sched._running))
                last = len(sched.step_times)
            if at_end is not None and (not sched.pending
                                       or now >= t0 + seconds + drain):
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
                time.sleep(max(0.0, min(
                    t0 + seconds, t0 + todo[nxt].due if nxt < len(todo)
                    else t0 + seconds) - time.perf_counter()))
        seen = [t for t in todo if t.token_times]
        gaps = [b - a for t in seen
                for a, b in zip(t.token_times, t.token_times[1:])]
        ttft = [t.token_times[0] - (t0 + t.due) for t in seen]
        ms = lambda v, share: 1e3 * quantile(v, share) if v else None
        emit(rate=rate, offered=len(todo), submitted=nxt, **at_end,
             left_after_drain=len(sched._queue) + len(sched._prefilling)
             + len(sched._running),
             mean_running=statistics.fmean(running) if running else 0.0,
             ttft_p50_ms=ms(ttft, 0.5), ttft_p90_ms=ms(ttft, 0.9),
             itl_p90_ms=ms(gaps, 0.9),
             step_ms=ms(sched.step_times[first:last], 0.5),
             prompt_tokens_per_s=sum(len(t.prompt) for t in seen
                                     if t.token_times[0] < t0 + seconds)
             / seconds,
             tokens_per_s=sum(t0 <= s < t0 + seconds for t in todo
                              for s in t.token_times) / seconds)
        # what is left of this rate runs on into the next
        sched.finished.clear()


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--what", required=True,
                    choices=("sound", "faults", "sweep"))
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=45.0)
    ap.add_argument("--rates", default="")
    ap.add_argument("--drain", type=float, default=60.0)
    ap.add_argument("--check", type=int, default=1,
                    help="faults: hold only the last N checked requests")
    ap.add_argument("--stand-ins", default="",
                    help="faults: which to plant (all where empty)")
    args = ap.parse_args()
    os.makedirs(os.path.join(REPO, "chiprun_out"), exist_ok=True)
    log = os.path.join(REPO, "chiprun_out", f"glm5-{args.workload}.jsonl")

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
                  args.seconds, args.drain,
                  lambda **row: emit(seed=seed, **row))
            continue
        if args.what == "faults":
            run = prepared(args.workload, seed, args.seconds)
            served = run.driver.serve(run)[-args.check:]
            gc.collect()
            wanted = args.stand_ins.split(",") if args.stand_ins \
                else ["control", *run.model.FAULTS]
            emit(seed=seed, stand_in="served", failed=run.failed,
                 attempted=run.attempted,
                 itl_p90_ms=1e3 * quantile(run.counters["itl_s"], 0.9),
                 setup_s=run.setup_s)
            stand_ins = [None] + [
                (run.model.SERVING_CONTROL, None) if name == "control"
                else (None, name) if name in run.model.FAULTS
                else (name, None) for name in wanted]
            for name, row in zip(["none"] + wanted,
                                 compared(run, served, stand_ins)):
                emit(seed=seed, stand_in=name, **row)
            continue
        line, kept = one_run(args.workload, seed, args.seconds, Steer())
        row = {k: v["value"] for k, v in line["compared"].items()}
        emit(seed=seed, correct=line["correct"], attempted=line["attempted"],
             failed=line["failed"], metrics={k: v["value"] for k, v in
                                             line["metrics"].items()},
             memory_peak_bytes=line["device"]["memory_peak_bytes"],
             checked_tokens=kept.counters.get("checked_tokens"),
             checked_selections=kept.counters.get("checked_selections"),
             set_up={name: at - before for (_, before), (name, at)
                     in zip(kept.marks, kept.marks[1:])}, **row)
        ticks = kept.counters.get("ticks", ())
        if ticks:
            by = collections.Counter(b for _, _, b in ticks)
            steps = kept.counters["decode_step_s"]
            emit(seed=seed, distribution="ticks", n=len(ticks),
                 by_bucket={str(b): n / len(ticks)
                            for b, n in sorted(by.items())},
                 mean_live=statistics.fmean(len(l) for _, l, _ in ticks),
                 mean_ctx=statistics.fmean(
                     sum(l) / len(l) for _, l, _ in ticks),
                 tick_ms={f"p{q}": 1e3 * quantile(steps, q / 100)
                          for q in (10, 50, 90, 99)},
                 chunks=len(kept.counters["chunks"]))
        vals = sorted(kept.counters.get("itl_s", ()))
        if vals:
            emit(seed=seed, distribution="itl_ms", n=len(vals),
                 **{f"p{q}": 1e3 * quantile(vals, q / 100)
                    for q in (50, 75, 80, 85, 88, 90, 92, 95, 99)})
        del kept
        gc.collect()


if __name__ == "__main__":
    main()
