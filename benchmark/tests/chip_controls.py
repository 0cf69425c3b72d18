#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from, taken on the chip
at a cell's own size, several seeds in one process (set-up is long):

    python3 benchmark/tests/chip_controls.py --workload <cell> --what sound|control|faults \\
        --seeds 101,102,103 [--seconds 5]

``sound``: the cell as committed; the lower readings. ``control``: the
lower precision in the program's place, through the harness's own
comparison, which has to say ``correct`` false (a training cell: the
program's own bfloat16 masters and moments; a serving cell: the float8
reference's first token at every position of the prompts and tokens that
the run served); the upper readings. ``faults`` (training cells):
the reference with a fault planted, held against the sound reference:
half of the batch left out, and the exchange between mp ranks left out.
Every reading is printed as one JSON line and appended to
``chiprun_out/controls-<cell>.jsonl``. Needs the chips the cell asks for.
"""
import argparse
import io
import json
import os
import sys
import time
from contextlib import redirect_stdout

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
REPO = os.path.dirname(BENCH)
for path in (REPO, BENCH):
    if path not in sys.path:
        sys.path.insert(0, path)


def one_run(cell, seed, seconds, steer):
    """One run in this process: its parsed result line and its record."""
    import run
    out = io.StringIO()
    with redirect_stdout(out):
        record = run.execute(
            ["--workload", cell, "--seed", str(seed), "--seconds",
             str(seconds), "--trace", "0"], steer=steer,
            t_start=time.perf_counter())
    return json.loads(out.getvalue().strip().splitlines()[-1]), record


def fault_readings(run, seed, fault):
    import jax
    from drivers import train
    feed = train.Feed(seed, run.traffic["batch"], run.traffic["seq"],
                      run.config["vocab_size"])
    early = [feed.next() for _ in range(run.traffic["compared_steps"])]
    args = (run.config, seed, early, run.traffic["reference_rows_per_pass"],
            jax.devices()[:run.chips])
    sound = run.model.reference_train(*args)
    broken = run.model.reference_train(*args, fault=fault)
    out = {f"loss{i}_gap": abs(b - a) / abs(a) for i, (a, b) in
           enumerate(zip(sound["loss"], broken["loss"]), start=1)}
    out["loss_gap"] = max(out.values())
    out["grad_gap"] = train.worst_gap(broken["grad"], sound["grad"])[0]
    out["change_gap"] = train.worst_gap(
        broken["change"], sound["change"],
        skip=train.still_leaves(sound["grad"]))[0]
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--what", choices=("sound", "control", "faults"),
                    required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    args = ap.parse_args()
    from harness.core import Run, Steer, load_json
    os.makedirs(os.path.join(REPO, "chiprun_out"), exist_ok=True)
    log = os.path.join(REPO, "chiprun_out",
                       f"controls-{args.workload}.jsonl")

    def emit(**row):
        line = json.dumps(dict(workload=args.workload, what=args.what,
                               **row))
        print(line, flush=True)
        with open(log, "a") as f:
            f.write(line + "\n")

    for seed in (int(s) for s in args.seeds.split(",")):
        if args.what == "faults":
            from harness import peaks
            from paddle_tpu.utils.compile_cache import enable_compile_cache
            steer = Steer()
            run = Run(load_json(os.path.join(REPO, "BENCHMARK.json")),
                      args.workload, seed, 1.0, 0, steer, BENCH,
                      time.perf_counter())
            peaks.look_for_chips(run.chips, steer)
            enable_compile_cache()
            for fault in ("half_batch", "no_mp_exchange"):
                emit(seed=seed, fault=fault,
                     **fault_readings(run, seed, fault))
            continue
        steer = Steer(lower_precision=args.what == "control")
        line, kept = one_run(args.workload, seed, args.seconds, steer)
        row = {k: v["value"] for k, v in line["compared"].items()}
        emit(seed=seed, correct=line["correct"], attempted=line["attempted"],
             failed=line["failed"], metrics={k: v["value"] for k, v in
                                             line["metrics"].items()},
             memory_peak_bytes=line["device"]["memory_peak_bytes"], **row)
        for key in ("itl_s", "ttft_s", "queue_wait_s"):
            if key in kept.counters:
                vals = sorted(kept.counters[key])
                emit(seed=seed, distribution=key, n=len(vals), **{
                    f"p{q}": vals[min(len(vals) - 1, int(q / 100 * len(vals)))]
                    for q in (50, 75, 80, 85, 88, 90, 92, 95, 97, 99)})


if __name__ == "__main__":
    main()
