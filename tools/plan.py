#!/usr/bin/env python
"""Cost-model parallelism planner CLI.

Ranks (dp, mp, pp, sharding, n_micro, remat, donation, wire dtype)
plans for a GPT-family model on N chips, scored by tracing the REAL
hybrid train step on a virtual mesh through the static cost/memory
model — no devices, no compile, a 13B/64-chip plan in seconds::

    python tools/plan.py --model gpt_13b --devices 64 --chip v5e
    python tools/plan.py --model gpt_13b --devices 16 --json   # bench row
    python tools/plan.py --serving --serving-config 345m       # serving space

``--json`` prints one machine-readable document (``bench.py`` consumes
it for the ``gpt_13b_planned_predicted`` row; ``Engine.prepare(plan=)``
accepts the ``best`` entry's mesh degrees verbatim).
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _table(rows, cols):
    head = [c[0] for c in cols]
    body = [[str(c[1](r)) for c in cols] for r in rows]
    widths = [max(len(h), *(len(b[i]) for b in body)) if body else len(h)
              for i, h in enumerate(head)]
    fmt = "  ".join(f"{{:<{w}}}" for w in widths)
    lines = [fmt.format(*head), fmt.format(*("-" * w for w in widths))]
    lines += [fmt.format(*b) for b in body]
    return "\n".join(lines)


def main(argv=None):
    ap = argparse.ArgumentParser(
        description="rank parallelism plans from the static cost model "
                    "(trace-only, any host, no devices)")
    ap.add_argument("--model", default="gpt_13b",
                    choices=["gpt_tiny", "gpt_345m", "gpt_1p3b",
                             "gpt_13b"])
    ap.add_argument("--devices", type=int, default=16,
                    help="slice size N to factor into dp*mp*pp*sharding")
    ap.add_argument("--chip", default="v5e")
    ap.add_argument("--global-batch", type=int, default=0,
                    help="0 = the model's bench default")
    ap.add_argument("--seq", type=int, default=0,
                    help="0 = the model's bench default")
    ap.add_argument("--top-k", type=int, default=5)
    ap.add_argument("--max-traces", type=int, default=12,
                    help="trace budget: finalists priced by the "
                         "trace-based model")
    ap.add_argument("--json", action="store_true",
                    help="one JSON document instead of the table")
    ap.add_argument("--serving", action="store_true",
                    help="search the serving plan space (decode bucket, "
                         "page size, quantize) instead of training")
    ap.add_argument("--serving-config", default="345m",
                    choices=["tiny", "345m", "1.3b", "13b"])
    args = ap.parse_args(argv)

    if not os.environ.get("_PLAN_RESPAWNED"):
        # force the CPU backend in a fresh process BEFORE jax
        # initializes: planning is trace-only and must never hold (or
        # wait on) a chip
        env = dict(os.environ, _PLAN_RESPAWNED="1", JAX_PLATFORMS="cpu")
        return subprocess.run(
            [sys.executable, os.path.abspath(__file__)]
            + (argv if argv is not None else sys.argv[1:]),
            env=env).returncode

    sys.path.insert(0, REPO)
    import jax
    jax.config.update("jax_platforms", "cpu")

    if args.serving:
        from paddle_tpu.distributed.auto_parallel.planner import \
            plan_serving
        out = plan_serving(args.serving_config, chip=args.chip,
                           top_k=args.top_k)
        if args.json:
            out.pop("pruned")
            print(json.dumps(out), flush=True)
            return 0
        print(f"serving plans: {args.serving_config} on {out['chip']} "
              f"({out['planner_s']}s, {out['n_pruned']} pruned)")
        print(_table(out["plans"], [
            ("concurrency", lambda r: r["concurrency"]),
            ("page_size", lambda r: r["page_size"]),
            ("quantize", lambda r: r["quantize"] or "-"),
            ("tok/s", lambda r: r["predicted_tokens_per_sec"]),
            ("step_ms", lambda r: r["predicted_decode_step_ms"]),
            ("hbm_mb", lambda r: r["hbm_mb"]),
            ("bound", lambda r: r["predicted_bound"]),
        ]))
        return 0

    from paddle_tpu.distributed.auto_parallel.planner import plan_gpt
    report = plan_gpt(args.model, devices=args.devices, chip=args.chip,
                      global_batch=args.global_batch or None,
                      seq_len=args.seq or None, top_k=args.top_k,
                      max_traces=args.max_traces)
    doc = report.as_dict()
    doc["best"] = report.best.as_dict() if report.plans else None
    if args.json:
        print(json.dumps(doc), flush=True)
        return 0
    print(f"plans: {args.model} on {args.devices}x {doc['chip']} "
          f"(planner {doc['planner_s']}s, {doc['n_candidates']} "
          f"candidates, {doc['n_traced']} traced, {doc['n_pruned']} "
          f"pruned)")
    print(_table([p.as_dict() for p in report.plans], [
        ("mesh", lambda r: r["mesh"]),
        ("n_micro", lambda r: r["n_micro"]),
        ("remat", lambda r: r["remat"]),
        ("wire", lambda r: r["wire_dtype"] or "-"),
        ("step_ms", lambda r: r["step_ms"]),
        ("MFU", lambda r: r["predicted_mfu"]),
        ("peak_hbm_gb", lambda r: r["peak_hbm_gb"]),
        ("bound", lambda r: r["bound"]),
        ("tok/s/chip", lambda r: r["tokens_per_sec_per_chip"]),
    ]))
    return 0


if __name__ == "__main__":
    sys.exit(main())
