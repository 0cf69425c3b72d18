#!/usr/bin/env python3
"""One run of one cell of the benchmark, in one process.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Finds the cell in ``BENCHMARK.json``, its configuration, traffic mix,
driver, model family, metrics and limits by name (``harness/core.py``
lists where), makes weights and inputs from ``--seed``, warms up the
cell's own shapes (set-up), measures for ``--seconds``, checks what the
timed path produced against the plain reference, and prints one JSON
object as the last line of standard output. ``--trace 0`` reports the
cell's end-to-end metrics; ``--trace 1`` takes a profiler trace of part
of the window and reports its per-layer metrics and a breakdown.

It runs on the machine it is started on and needs the chips the cell
asks for: without them it exits non-zero and prints no result. There is
no option that makes it smaller or moves it to a CPU: the rehearsals
under ``tests/`` steer it from Python.
"""
import time

T_START = time.perf_counter()

import argparse     # noqa: E402
import json         # noqa: E402
import os           # noqa: E402
import sys          # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
for path in (REPO, HERE):
    if path not in sys.path:
        sys.path.insert(0, path)


def execute(argv=None, steer=None, t_start=None):
    """Run one cell, print its result, and return the run's record."""
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from harness import core, peaks
    from harness.spans import Spans
    manifest = core.load_json(
        steer.manifest if steer and steer.manifest
        else os.path.join(REPO, "BENCHMARK.json"))
    root = steer.root if steer and steer.root else HERE
    run = core.Run(manifest, args.workload, args.seed, args.seconds,
                   args.trace, steer, root,
                   T_START if t_start is None else t_start)

    import jax
    run.device = peaks.look_for_chips(run.chips, run.steer)
    run.peaks = peaks.peaks_of(run.device)
    # the program's own rule: JAX_COMPILATION_CACHE_DIR where it is set,
    # else <checkout>/.jax_cache, the same path on every run
    from paddle_tpu.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, *_a, **_k: run.count_compile()
        if event == "/jax/core/compile/backend_compile_duration" else None)
    run.spans = Spans()
    run.mark("import_and_chip")

    run.driver.run(run)

    line = run.result()
    print("set-up by phase:", ", ".join(
        f"{name} {at - before:.2f}" for (_, before), (name, at)
        in zip(run.marks, run.marks[1:])), file=sys.stderr)
    for name, value, limit in run.compared:
        print(f"compared {name} {value:.6g} limit {limit:.6g}",
              file=sys.stderr)
    print(f"correct {line['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)
    return run


def main(argv=None, steer=None, t_start=None):
    execute(argv, steer, t_start)
    return 0


if __name__ == "__main__":
    sys.exit(main())
