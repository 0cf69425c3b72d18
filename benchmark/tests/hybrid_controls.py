#!/usr/bin/env python3
"""Readings of the hybrid serving cell (a state pool beside the page pool)
on the chip, beside ``chip_controls.py`` and ``blocks_controls.py`` (which
a PR that adds a cell may not edit), several seeds in one process:

    python3 benchmark/tests/hybrid_controls.py --workload <cell> \\
        --what sound|control|faults|sweep --seeds 101,102 [--seconds 45]
        [--rates 2,3,4,5]

``sound``: the cell as committed, through ``run.py``'s own path; the
lower readings, with the distribution of the gaps between tokens, the
ticks by bucket and what the engine says of its memory. ``control``: the
float8 reference in the program's place through the harness's own
comparison (``Steer.lower_precision``), which has to say ``correct``
false; the upper readings. ``faults``: the cell served once; the sound
comparison; then on the same served tokens ``drivers/serve.py``'s
comparison with the control in the program's place (``stand_in``
``control``: ``correct`` has to be false), and with each fault of the
family's ``FAULTS`` planted in the reference (the state zeroed at a
chunk boundary, a slot not reset, the window ignored, the cross layers a
row stale, the memory taken after the gate, lambda dropped;
``--stand-ins`` may also name ``bf16``: the reference at the served
precision, what rounding alone reads), each held against the sound
reference in ``logit_gap``. ``sweep``:
one engine, ``--rates`` in turn for ``--seconds`` each, drained between:
the knee (``blocks_controls.sweep``: it drives any engine of the
contract). Every reading is printed as one JSON line and appended to
``chiprun_out/hybrid-<cell>.jsonl``.
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

from blocks_controls import prepared, sweep         # noqa: E402
from chip_controls import one_run                   # noqa: E402
from harness.core import Steer                      # noqa: E402
from harness.core import percentile as quantile     # noqa: E402


def compared(run, served, steer=None, **stand_in):
    """``drivers/serve.py``'s comparison over what was served, with the
    sound reference or with ``mode=`` / ``fault=`` (under ``steer``, the
    control's mode) in the program's place: every number beside its
    limit, and ``correct``."""
    run.steer, run.compared = steer or Steer(), []
    if run.steer.lower_precision:
        stand_in = {"mode": run.model.SERVING_CONTROL}
    weights = run.model.init_weights(run.config, run.seed)
    gaps = [run.model.served_token_gaps(
        run.config, weights, prompt, tokens,
        run.config["max_position_embeddings"], **stand_in)
        for prompt, tokens in served]
    run.compare("logit_gap", max(float(g.max()) for g in gaps))
    run.compare("never_finished", run.failed)
    run.compare("compiles_in_window", run.compiles_in_window)
    return dict({name: value for name, value, _ in run.compared},
                limits={name: limit for name, _, limit in run.compared},
                correct=run.correct,
                checked_tokens=int(sum(len(g) for g in gaps)))


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
    os.makedirs(os.path.join(REPO, "chiprun_out"), exist_ok=True)
    log = os.path.join(REPO, "chiprun_out", f"hybrid-{args.workload}.jsonl")

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
            wanted = args.stand_ins.split(",") if args.stand_ins \
                else ["control", *run.model.FAULTS]
            began = time.perf_counter()
            emit(seed=seed, stand_in="none", failed=run.failed,
                 attempted=run.attempted, requests=len(served),
                 itl_p90_ms=1e3 * quantile(run.counters["itl_s"], 0.9),
                 setup_s=run.setup_s, **compared(run, served),
                 seconds=time.perf_counter() - began)
            for name in wanted:     # the control, a precision, a fault
                emit(seed=seed, stand_in=name, **compared(
                    run, served,
                    **({"steer": Steer(lower_precision=True)}
                       if name == "control" else {"fault": name}
                       if name in run.model.FAULTS else {"mode": name})))
            continue
        steer = Steer(lower_precision=args.what == "control")
        line, kept = one_run(args.workload, seed, args.seconds, steer)
        row = {k: v["value"] for k, v in line["compared"].items()}
        emit(seed=seed, correct=line["correct"], attempted=line["attempted"],
             failed=line["failed"], metrics={k: v["value"] for k, v in
                                             line["metrics"].items()},
             memory_peak_bytes=line["device"]["memory_peak_bytes"],
             checked_tokens=kept.counters.get("checked_tokens"),
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


if __name__ == "__main__":
    main()
