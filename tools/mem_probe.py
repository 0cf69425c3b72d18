"""Pipeline-memory probe: reproducible ``memory_analysis()`` sweeps.

VERDICT r4 #3/#4: the README's "XLA temp memory 4x below GPipe at
n_micro=32" claim previously lived only in a commit message; this tool
makes it (and the 13B fits-or-not question) a checked-in, re-runnable
artifact. It AOT-lowers the ``GPTHybridTrainStep`` via
``GPTHybridTrainStep.abstract`` + ``lower_step`` — no parameter buffers
are materialized, so 13B-scale programs compile on a laptop-sized host —
and prints one JSON line per (schedule, n_micro, remat) combo with XLA's
per-device memory breakdown.

The probe runs on a VIRTUAL CPU mesh: it re-execs itself with
``JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=N``
when the current backend doesn't provide enough devices, so
``python tools/mem_probe.py --config tiny`` works from any environment.

Examples:
  python tools/mem_probe.py                         # tiny sweep (CI-fast)
  python tools/mem_probe.py --config 13b --mp 4 --pp 4 --batch 16 \
      --seq 2048 --n-micro 16 --schedules 1f1b      # the north-star probe

Parity: the memory rationale of reference ``pipeline_parallel.py:119``
(1F1B bounds live micro-batches) + ``fleet/recompute`` (remat), measured
instead of asserted.
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def _mesh_devices_needed(args):
    return args.dp * args.mp * args.pp * args.sharding


def _maybe_respawn(args):
    """Re-exec on a virtual CPU mesh. The parent NEVER touches jax: on a
    machine with a chip the default backend is the TPU, which a probe
    must not hold, and the device count must be forced via XLA_FLAGS
    before the backend exists. The child runs under JAX_PLATFORMS=cpu."""
    if os.environ.get("_MEM_PROBE_RESPAWNED"):
        return None
    need = _mesh_devices_needed(args)
    env = dict(os.environ)
    env.update({
        "_MEM_PROBE_RESPAWNED": "1",
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": (env.get("XLA_FLAGS", "") +
                      f" --xla_force_host_platform_device_count={need}")
        .strip(),
    })
    return subprocess.run([sys.executable, os.path.abspath(__file__)]
                          + sys.argv[1:], env=env).returncode


def probe_one(cfg, hcg, schedule, n_micro, remat, vpp, batch, seq,
              compute_dtype="bfloat16", param_dtype=None,
              moment_dtype=None, compare_static=False):
    from paddle_tpu.models.gpt import GPTHybridTrainStep

    step = GPTHybridTrainStep.abstract(
        cfg, hcg, n_micro=n_micro, remat=remat,
        pipeline_schedule="1f1b" if schedule in ("1f1b", "interleaved")
        else "gpipe",
        virtual_pp_degree=vpp if schedule == "interleaved" else 1,
        compute_dtype=compute_dtype, param_dtype=param_dtype,
        moment_dtype=moment_dtype)
    compiled = step.lower_step(batch, seq).compile()
    ma = compiled.memory_analysis()
    gb = 1024 ** 3
    rec = {
        "schedule": schedule, "n_micro": n_micro,
        "remat": remat if isinstance(remat, str) else bool(remat),
        "vpp": vpp if schedule == "interleaved" else 1,
        "temp_gb": round(ma.temp_size_in_bytes / gb, 4),
        "argument_gb": round(ma.argument_size_in_bytes / gb, 4),
        "output_gb": round(ma.output_size_in_bytes / gb, 4),
        # donation makes params/opt-state alias in+out, so live HBM is
        # args (params+state+data) + temps, NOT args+outputs+temps
        "peak_hbm_gb": round((ma.argument_size_in_bytes
                              + ma.temp_size_in_bytes) / gb, 4),
    }
    if compare_static:
        # predicted-vs-XLA cross-check: the liveness estimator walks the
        # SAME step's jaxpr (trace only, no second compile) and the
        # relative error column keeps it honest in CI
        from paddle_tpu.analysis.predict import predict_hybrid_step
        pred = predict_hybrid_step(step, batch, seq)
        p = pred["memory"].peak_bytes
        x = ma.argument_size_in_bytes + ma.temp_size_in_bytes
        rec["predicted_peak_gb"] = round(p / gb, 4)
        rec["predicted_temp_gb"] = round(
            pred["memory"].temp_peak_bytes / gb, 4)
        rec["rel_err"] = round((p - x) / x, 4) if x else 0.0
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default="tiny",
                    choices=["tiny", "345m", "1.3b", "13b"])
    ap.add_argument("--dp", type=int, default=1)
    ap.add_argument("--mp", type=int, default=1)
    ap.add_argument("--pp", type=int, default=4)
    ap.add_argument("--sharding", type=int, default=1)
    ap.add_argument("--batch", type=int, default=0)
    ap.add_argument("--seq", type=int, default=0)
    ap.add_argument("--n-micro", type=int, nargs="*", default=None)
    ap.add_argument("--schedules", nargs="*",
                    default=["gpipe", "1f1b", "interleaved"])
    ap.add_argument("--remat", nargs="*", default=["none", "full", "dots"])
    ap.add_argument("--vpp", type=int, default=2)
    ap.add_argument("--param-dtype", default=None)
    ap.add_argument("--moment-dtype", default=None)
    ap.add_argument("--compute-dtype", default="bfloat16",
                    help="activation/compute dtype; use float32 for a "
                         "like-for-like --compare-static run (XLA's CPU "
                         "backend pads bf16 programs with f32 conversion "
                         "buffers a TPU never allocates)")
    ap.add_argument("--compare-static", action="store_true",
                    help="also run the static liveness peak-HBM "
                         "estimator (paddle_tpu.analysis) per combo and "
                         "print predicted_peak_gb + rel_err columns")
    args = ap.parse_args()

    rc = _maybe_respawn(args)
    if rc is not None:
        sys.exit(rc)

    import jax

    from paddle_tpu.distributed import mesh as mesh_mod
    from paddle_tpu.distributed.mesh import HybridCommunicateGroup
    from paddle_tpu.models.gpt import (gpt_tiny_config, gpt_345m_config,
                                       gpt_1p3b_config, gpt_13b_config)

    cfgs = {"tiny": gpt_tiny_config, "345m": gpt_345m_config,
            "1.3b": gpt_1p3b_config, "13b": gpt_13b_config}
    if args.config == "tiny":
        # enough layers for every schedule in the sweep (interleaved
        # needs num_layers % (pp * vpp) == 0)
        cfg = gpt_tiny_config(num_layers=args.pp * max(args.vpp, 2))
    else:
        cfg = cfgs[args.config]()
    batch = args.batch or {"tiny": 8, "345m": 8, "1.3b": 8, "13b": 16}[
        args.config]
    seq = args.seq or min(512, cfg.max_position_embeddings)
    micros = args.n_micro or [args.pp, 4 * args.pp]
    remats = [{"none": False, "full": True, "dots": "dots"}[r]
              for r in args.remat]

    mesh_mod._global_mesh, mesh_mod._hcg = None, None
    hcg = HybridCommunicateGroup(dp_degree=args.dp, mp_degree=args.mp,
                                 pp_degree=args.pp,
                                 sharding_degree=args.sharding)
    meta = {"config": args.config, "hidden": cfg.hidden_size,
            "layers": cfg.num_layers, "batch": batch, "seq": seq,
            "mesh": {"dp": args.dp, "mp": args.mp, "pp": args.pp,
                     "sharding": args.sharding}}
    print(json.dumps({"probe": "mem", **meta}), flush=True)
    for schedule in args.schedules:
        for n_micro in micros:
            if batch % n_micro:
                continue
            for remat in remats:
                try:
                    rec = probe_one(cfg, hcg, schedule, n_micro, remat,
                                    args.vpp, batch, seq,
                                    compute_dtype=args.compute_dtype,
                                    param_dtype=args.param_dtype,
                                    moment_dtype=args.moment_dtype,
                                    compare_static=args.compare_static)
                except Exception as e:
                    rec = {"schedule": schedule, "n_micro": n_micro,
                           "remat": str(remat), "error": repr(e)[:200]}
                print(json.dumps(rec), flush=True)


if __name__ == "__main__":
    main()
