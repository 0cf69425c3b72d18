"""Benchmark harness — ONE JSON line PER BASELINE config for the driver.

Default run covers the BASELINE.md configs: ResNet50 (#1), BERT-base
(#2), ERNIE-MoE (#5), GPT-1.3B (#3), the headline GPT-345M (#4's
single-chip proxy), then the round-5 evidence rows — the 13B stage-shard
proxy + 13B compile-only HBM probe (#4) and the GPTGenerator serving
benchmark. `vs_baseline` is this round's value over the
previous round's recorded value — read from the newest parseable
`BENCH_r*.json` on disk, falling back to the measurement table below for
metrics no artifact captured — so >1.0 is a speedup and first-ever
measurements report 1.0. A CPU smoke run suffixes every metric with
`_cpu_smoke` so its numbers can never become TPU baselines. The
reference publishes no in-tree numbers (BASELINE.json `published: {}`).

A run lands on the chip or fails loudly: ``jax.devices()`` is taken
once, a platform other than ``tpu`` is an error unless the caller set
``JAX_PLATFORMS=cpu`` itself (a CPU smoke run, every metric suffixed
``_cpu_smoke``), a config that cannot run emits a `*_ERROR` line while the
rest of the sweep proceeds, and the exit code is non-zero when any config
raised or timed out.

Run: python bench.py                      # all configs
     python bench.py --model gpt --config 345m   # one config
"""
import argparse
import glob
import json
import os
import re
import sys
import time
import traceback

import numpy as np

# fallback vs_baseline denominators for metrics no BENCH_r*.json artifact
# captured (the driver keeps only the output tail, so older metrics may
# be absent on disk) — measured values, one v5e chip
_PREV_FALLBACK = {
    "gpt_345m_tokens_per_sec_per_chip": 42974.6,   # BENCH_r03.json
    "bert_base_tokens_per_sec_per_chip": 60200.0,  # README 2026-07-30
    "resnet50_imgs_per_sec_per_chip": 1692.0,      # README 2026-07-30
    "ernie_moe_tokens_per_sec_per_chip": 59900.0,  # README 2026-07-30
    "gpt_1p3b_tokens_per_sec_per_chip": 12200.0,   # README 2026-07-31 (r4)
}


def _load_prev(repo_dir=os.path.dirname(os.path.abspath(__file__))):
    """vs_baseline denominators: every metric line recoverable from the
    BENCH_r*.json artifacts on disk, newest round winning; the hardcoded
    fallback table covers metrics whose artifact tail was truncated."""
    prev = dict(_PREV_FALLBACK)
    rounds = []
    for path in glob.glob(os.path.join(repo_dir, "BENCH_r*.json")):
        m = re.search(r"BENCH_r(\d+)\.json$", path)
        if not m:
            continue
        try:
            with open(path) as f:
                doc = json.load(f)
        except (OSError, ValueError):
            continue
        rounds.append((int(m.group(1)), doc))
    for _, doc in sorted(rounds):  # ascending: newer rounds overwrite
        lines = [ln for ln in str(doc.get("tail", "")).splitlines()]
        if isinstance(doc.get("parsed"), dict):
            lines.append(json.dumps(doc["parsed"]))
        for ln in lines:
            ln = ln.strip()
            if not (ln.startswith("{") and '"metric"' in ln):
                continue
            try:
                rec = json.loads(ln)
            except ValueError:
                continue
            metric, value = rec.get("metric"), rec.get("value")
            device = str((rec.get("extras") or {}).get("device", ""))
            if (isinstance(metric, str) and isinstance(value, (int, float))
                    and value > 0
                    and not metric.endswith(("_ERROR", "_SKIPPED"))
                    and "_cpu_smoke" not in metric
                    and "cpu" not in device.lower()):
                # CPU-fallback numbers must never become the TPU
                # denominator (they would fabricate 30-100x "speedups")
                prev[metric] = float(value)
    return prev


_PREV = _load_prev()
_CPU_SMOKE = False  # set when the caller asked for JAX_PLATFORMS=cpu
_CAL_ID = None


def _calibration_id() -> str:
    """Active cost-model calibration id ("default" when none) — stamped
    on every row so bench_compare can refuse to anchor a measured row
    against a predicted row priced under different constants."""
    global _CAL_ID
    if _CAL_ID is None:
        try:
            from paddle_tpu.observability.calibration import \
                active_calibration_id
            _CAL_ID = active_calibration_id()
        except Exception:
            _CAL_ID = "default"
    return _CAL_ID


def emit(metric, value, unit, extras):
    if _CPU_SMOKE:
        metric += "_cpu_smoke"  # never comparable to (or adopted as) TPU
    prev = _PREV.get(metric)
    vs = round(value / prev, 4) if prev else 1.0
    extras = dict(extras or {})
    extras.setdefault("calibration_id", _calibration_id())
    print(json.dumps({"metric": metric, "value": round(value, 1),
                      "unit": unit, "vs_baseline": vs, "extras": extras}),
          flush=True)


def emit_skip(metric, why):
    print(json.dumps({"metric": f"{metric}_SKIPPED", "value": 0.0,
                      "unit": "skipped", "vs_baseline": 0.0,
                      "extras": {"reason": why}}), flush=True)


def emit_predicted_rows(configs=("345m", "1.3b", "13b"), timeout_s=420):
    """Static cost-model stand-ins for the TPU configs this round can't
    run: one ``{name}_predicted`` JSON row each (roofline step_ms / MFU +
    liveness peak-HBM from ``paddle_tpu.analysis``), so a round without a
    TPU still produces artifact-backed numbers instead of only
    ``*_SKIPPED`` lines. Trace-only subprocess on a virtual CPU mesh —
    never touches (or waits on) the TPU. Rows bypass ``emit()`` on
    purpose: predictions must never enter the vs_baseline denominators
    or gain the ``_cpu_smoke`` suffix measured rows get."""
    import subprocess
    name_of = {"345m": "gpt_345m", "1.3b": "gpt_1p3b", "13b": "gpt_13b"}
    try:
        r = subprocess.run(
            [sys.executable, "-m", "paddle_tpu.analysis.predict",
             "--configs", ",".join(configs)],
            capture_output=True, text=True, timeout=timeout_s,
            cwd=os.path.dirname(os.path.abspath(__file__)))
        lines = r.stdout.splitlines()
    except Exception as e:
        print(json.dumps({"metric": "predicted_rows_ERROR", "value": 0.0,
                          "unit": "error", "vs_baseline": 0.0,
                          "extras": {"error": repr(e)[:300]}}), flush=True)
        return
    emitted = 0
    for ln in lines:
        try:
            row = json.loads(ln)
        except ValueError:
            continue
        name = name_of.get(row.pop("config", None), None)
        if name is None:
            continue
        emitted += 1
        if "error" in row:
            print(json.dumps({"metric": f"{name}_predicted_ERROR",
                              "value": 0.0, "unit": "error",
                              "vs_baseline": 0.0, "extras": row}),
                  flush=True)
            continue
        print(json.dumps({
            "metric": f"{name}_predicted",
            "value": row.get("predicted_tokens_per_sec_per_chip", 0.0),
            "unit": "tokens/s/chip (static cost model)",
            "vs_baseline": 0.0, "extras": row}), flush=True)
    if not emitted and r.returncode != 0:
        # the predict child died before printing any JSON — the artifact
        # must still say so, not silently fall back to *_SKIPPED only
        print(json.dumps({"metric": "predicted_rows_ERROR", "value": 0.0,
                          "unit": "error", "vs_baseline": 0.0,
                          "extras": {"returncode": r.returncode,
                                     "stderr": r.stderr[-300:]}}),
              flush=True)
    if "13b" in configs:
        emit_planned_predicted_row()


def emit_planned_predicted_row(devices=16, timeout_s=300):
    """``gpt_13b_planned_predicted``: the parallelism planner's best 13B
    config priced by the SAME cost model as the hand-written
    ``gpt_13b_predicted`` anchor beside it — the two rows together show
    what the cost-model search buys over the hand config (predicted
    MFU), and ``planner_s`` makes plan-time regressions visible.
    Shelled out to ``tools/plan.py --json`` (trace-only on a virtual
    mesh) so a wedged backend can't take the row down."""
    import subprocess
    repo = os.path.dirname(os.path.abspath(__file__))
    metric = "gpt_13b_planned_predicted"
    try:
        r = subprocess.run(
            [sys.executable, os.path.join(repo, "tools", "plan.py"),
             "--model", "gpt_13b", "--devices", str(devices),
             "--chip", "v5e", "--json"],
            capture_output=True, text=True, timeout=timeout_s, cwd=repo)
        doc = json.loads(r.stdout.splitlines()[-1])
        best = doc.get("best")
        if not best:  # plan.py exits 0 with best=null when nothing fits
            raise RuntimeError(
                f"planner found no feasible plan "
                f"({doc.get('n_pruned', '?')} pruned)")
    except Exception as e:
        print(json.dumps({"metric": f"{metric}_ERROR", "value": 0.0,
                          "unit": "error", "vs_baseline": 0.0,
                          "extras": {"error": repr(e)[:300]}}), flush=True)
        return
    print(json.dumps({
        "metric": metric,
        "value": best["tokens_per_sec_per_chip"],
        "unit": "tokens/s/chip (static cost model, planner's best)",
        "vs_baseline": 0.0,
        "extras": {
            "mesh": best["mesh"], "n_micro": best["n_micro"],
            "remat": best["remat"], "wire_dtype": best["wire_dtype"],
            "pipeline_schedule": best["pipeline_schedule"],
            "predicted_step_ms": best["step_ms"],
            "predicted_mfu": best["predicted_mfu"],
            "predicted_peak_hbm_gb": best["peak_hbm_gb"],
            "predicted_bound": best["bound"],
            "batch": best["global_batch"], "seq": best["seq_len"],
            "n_devices": best["n_devices"],
            "chip_assumed": best["chip"],
            "planner_s": doc["planner_s"],
            "n_candidates": doc["n_candidates"],
            "n_traced": doc["n_traced"],
        }}), flush=True)


class _PerModelTimeout(Exception):
    pass


def run_with_timeout(name, fn, budget_s, timed_out=None):
    """Run one config under a SIGALRM budget so a single wedged model can
    no longer starve the rest of the sweep into the driver's rc=124 with
    zero artifacts (VERDICT r5): every prior config's JSON line is
    already flushed, the stuck one reports ``*_TIMEOUT``, and the sweep
    proceeds; its name is appended to ``timed_out`` when given. No-op when
    budget<=0 or SIGALRM is unavailable (non-main thread / Windows)."""
    import signal
    import threading
    if budget_s <= 0 or not hasattr(signal, "SIGALRM") or \
            threading.current_thread() is not threading.main_thread():
        return fn()

    state = {"result": None, "done": False}

    def on_alarm(signum, frame):
        # a late alarm delivered after fn() completed (but before the
        # finally-cancel) must not fabricate a timeout for a finished run
        if not state["done"]:
            raise _PerModelTimeout(name)

    old = signal.signal(signal.SIGALRM, on_alarm)
    signal.alarm(int(budget_s))
    try:
        r = fn()
        # done BEFORE the result store: the only remaining race is the
        # single instruction between fn's return and this flag, which
        # SIGALRM cannot be fully excluded from — if it lands there the
        # worst case is a duplicate *_TIMEOUT line after the real row
        state["done"] = True
        state["result"] = r
    except _PerModelTimeout:
        print(json.dumps({"metric": f"{name}_TIMEOUT", "value": 0.0,
                          "unit": "timeout", "vs_baseline": 0.0,
                          "extras": {"budget_s": budget_s}}), flush=True)
        print(f"bench: {name} exceeded its {budget_s}s budget — "
              f"partial results flushed, continuing", file=sys.stderr,
              flush=True)
        if timed_out is not None:
            timed_out.append(name)
    finally:
        signal.alarm(0)
        signal.signal(signal.SIGALRM, old)
    return state["result"]


def acquire_devices():
    """``jax.devices()``, once, with nothing around it: no probe, no
    retry, no switch to another backend. A benchmark that finds no chip
    fails — a CPU timing is not a slower TPU timing. The one way to run
    on the CPU is to ask for it: ``JAX_PLATFORMS=cpu python bench.py``
    (rows then carry the ``_cpu_smoke`` suffix)."""
    import jax
    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu" \
            and os.environ.get("JAX_PLATFORMS", "").strip().lower() != "cpu":
        raise SystemExit(
            f"bench: JAX found platform {platform!r}, not a TPU, and the "
            f"caller did not set JAX_PLATFORMS=cpu — refusing to measure "
            f"(no fallback)")
    return devices


class _StepTelemetry:
    """Registry-delta + per-step-time collector for bench extras.

    Construct BEFORE the measured run (captures counter baselines), then
    ``extras(step_times)`` yields the telemetry columns every BENCH line
    carries: step-time p50/p95/max, peak device memory, compile seconds,
    and collective bytes moved — the breakdown that makes a tokens/sec
    regression explainable from the artifact alone.
    """

    def __init__(self):
        from paddle_tpu import device
        # peak memory must be THIS bench's peak, not an earlier config's
        # (live-array high-water mark resets; allocator peaks are runtime-
        # owned and process-lifetime — on TPU the number is an upper bound)
        device.reset_max_memory_allocated()
        self._compile_s0, self._coll_bytes0, self._anomalies0, \
            self._skips0 = self._cums()

    @staticmethod
    def _cums():
        from paddle_tpu.observability import get_registry
        compile_s = coll = anomalies = skips = 0.0
        for rec in get_registry().snapshot():
            if rec["name"] == "paddle_jit_compile_seconds_total":
                compile_s += rec.get("value", 0.0)
            elif rec["name"] == "paddle_collective_bytes_total":
                coll += rec.get("value", 0.0)
            elif rec["name"] == "paddle_anomalies_total":
                anomalies += rec.get("value", 0.0)
            elif rec["name"] == "paddle_loss_scale_skips_total":
                skips += rec.get("value", 0.0)
        return compile_s, coll, anomalies, skips

    def extras(self, step_times=None, wall_s=None):
        from paddle_tpu import device
        from paddle_tpu.observability.doctor import quick_verdict
        compile_s1, coll1, anomalies1, skips1 = self._cums()
        compile_s = compile_s1 - self._compile_s0
        out = {
            "peak_mem_mb": round(device.max_memory_allocated() / 2 ** 20, 1),
            "compile_s": round(compile_s, 2),
            "collective_bytes": int(coll1 - self._coll_bytes0),
            # the doctor's compact self-diagnosis: a failed round's
            # artifact says compile-dominated/jittery/anomalous by itself
            "doctor": quick_verdict(
                step_times, compile_s=compile_s,
                anomalies=int(anomalies1 - self._anomalies0),
                skips=int(skips1 - self._skips0), wall_s=wall_s),
        }
        if step_times:
            st = sorted(step_times)
            q = lambda p: st[min(len(st) - 1, int(round(p * (len(st) - 1))))]
            out.update({"step_ms_p50": round(1e3 * q(0.50), 2),
                        "step_ms_p95": round(1e3 * q(0.95), 2),
                        "step_ms_max": round(1e3 * st[-1], 2)})
            # per-step times are host-side; the loops pipeline with one
            # trailing sync, so if most wall time drained in that sync the
            # percentiles reflect dispatch latency, not device step time —
            # flag it rather than publish misleading numbers silently
            if wall_s and sum(step_times) < 0.8 * wall_s:
                out["step_times_host_async"] = True
        return out


def model_flops_per_token(cfg, seq_len):
    """6N + attention FLOPs/token — shared with the static cost model
    (one formula, one answer for measured AND predicted MFU)."""
    from paddle_tpu.models.gpt import model_flops_per_token as f
    return f(cfg, seq_len)


def peak_flops_per_chip():
    """bf16 peak for the attached chip (shared with the framework's MFU
    gauge — one table, one answer)."""
    from paddle_tpu.observability.instrument import peak_flops_per_chip as f
    return f()


def _timed_static_train(build, feed, args):
    """Shared static-path measurement scaffold: build the program under
    AMP bf16, run warmup, then `steps` pipelined runs (device-resident
    feeds, one trailing sync). Returns (seconds, final_loss, extras) where
    extras carries the telemetry columns (_StepTelemetry)."""
    from paddle_tpu import amp, static

    static.enable_static()
    try:
        telemetry = _StepTelemetry()
        t_build0 = time.perf_counter()
        main_prog = static.Program()
        with static.program_guard(main_prog):
            with amp.auto_cast(enable=True, dtype="bfloat16"):
                loss = build()
        exe = static.Executor()
        # --warmup 0 is honored like the GPT path: the first timed step
        # then includes compile
        for _ in range(args.warmup):
            out = exe.run(main_prog, feed=feed, fetch_list=[loss],
                          return_numpy=False)
        if args.warmup:
            float(np.asarray(out[0]._value))  # sync: warmup/compile done
        build_s = time.perf_counter() - t_build0
        step_times = []
        t0 = time.perf_counter()
        for _ in range(args.steps):
            t1 = time.perf_counter()
            out = exe.run(main_prog, feed=feed, fetch_list=[loss],
                          return_numpy=False)
            step_times.append(time.perf_counter() - t1)
        final = float(np.asarray(out[0]._value))
        dt = time.perf_counter() - t0  # BEFORE extras(): the registry
        # snapshot + live-array sweep must not bill into the benchmark
        extras = telemetry.extras(step_times, wall_s=dt)
        # the static path compiles in Executor.run, outside the jit-build
        # counters — report the program build+warmup wall time instead
        if not extras.get("compile_s"):
            extras["compile_s"] = round(build_s, 2)
        return dt, final, extras
    finally:
        static.disable_static()


def bench_resnet50(args):
    """BASELINE config #1: ResNet50 imgs/sec on the compiled static path
    (fluid-executor parity) with static AMP bf16."""
    import jax.numpy as jnp
    import paddle_tpu as paddle
    from paddle_tpu import optimizer, static
    from paddle_tpu.vision.models import resnet50

    # B128 measured best on v5e: 1692 imgs/s vs 1484 @64 and 1491 @256
    B = args.batch or 128

    def build():
        img = static.data("img", [B, 3, 224, 224], "float32")
        label = static.data("label", [B], "int64")
        net = resnet50(num_classes=1000)
        loss = paddle.nn.functional.cross_entropy(net(img), label)
        opt = optimizer.Momentum(learning_rate=0.1, momentum=0.9,
                                 parameters=net.parameters())
        opt.minimize(loss)
        return loss

    rng = np.random.default_rng(0)
    feed = {"img": jnp.asarray(rng.standard_normal(
                (B, 3, 224, 224)).astype(np.float32)),
            "label": jnp.asarray(rng.integers(0, 1000, B).astype(np.int64))}
    dt, final, tele = _timed_static_train(build, feed, args)
    ips = B * args.steps / dt
    # ~4.1 GFLOP/img fwd; x3 for fwd+bwd
    mfu = ips * 3 * 4.1e9 / peak_flops_per_chip()
    emit("resnet50_imgs_per_sec_per_chip", ips, "imgs/s/chip",
         {"mfu": round(mfu, 4), "batch": B, "steps": args.steps,
          "final_loss": round(final, 4), "amp": "bfloat16", **tele})


def bench_bert(args):
    """BASELINE config #2: BERT-base pretrain tokens/sec on the static
    (fluid-executor parity) path with static AMP bf16."""
    import jax.numpy as jnp
    from paddle_tpu import optimizer, static
    from paddle_tpu.models.bert import (BertForPretraining, BertModel,
                                        bert_base_config)

    cfg = bert_base_config()
    B = args.batch or 16
    S = args.seq or 512

    def build():
        ids = static.data("ids", [B, S], "int64")
        labels = static.data("labels", [B, S], "int64")
        model = BertForPretraining(BertModel(cfg))
        # fused MLM head+CE: streams token chunks instead of the [B*S, V]
        # fp32 logits buffer (tested equal to the unfused criterion)
        loss = model.forward_with_mlm_loss(ids, labels)
        opt = optimizer.AdamW(learning_rate=1e-4,
                              parameters=model.parameters())
        opt.minimize(loss)
        return loss

    rng = np.random.default_rng(0)
    feed = {"ids": jnp.asarray(rng.integers(
                0, cfg.vocab_size, (B, S)).astype(np.int64)),
            "labels": jnp.asarray(rng.integers(
                0, cfg.vocab_size, (B, S)).astype(np.int64))}
    dt, final, tele = _timed_static_train(build, feed, args)
    tps = B * S * args.steps / dt
    # adapt the GPT flops helper to BertConfig field names
    gptish = type("C", (), dict(
        hidden_size=cfg.hidden_size, num_layers=cfg.num_hidden_layers,
        vocab_size=cfg.vocab_size,
        intermediate_size=cfg.intermediate_size,
        max_position_embeddings=cfg.max_position_embeddings))
    fpt, n_params = model_flops_per_token(gptish, S)
    mfu = tps * fpt / peak_flops_per_chip()
    emit("bert_base_tokens_per_sec_per_chip", tps, "tokens/s/chip",
         {"mfu": round(mfu, 4), "n_params": n_params, "batch": B,
          "seq": S, "steps": args.steps,
          "final_loss": round(final, 4), "amp": "bfloat16", **tele})


def bench_ernie_moe(args):
    """BASELINE config #5: ERNIE-3.0-style MoE pretrain tokens/sec (static
    path, AMP bf16; single-chip dense experts here — expert parallelism
    rides the sep/sharding mesh axis on real pods)."""
    import jax.numpy as jnp
    import paddle_tpu as paddle
    from paddle_tpu import optimizer, static
    from paddle_tpu.models import (ErnieMoeForPretraining, ErnieMoeModel,
                                   ernie_moe_base_config)

    cfg = ernie_moe_base_config()
    B = args.batch or 16
    S = args.seq or 512

    def build():
        ids = static.data("ids", [B, S], "int64")
        labels = static.data("labels", [B, S], "int64")
        model = ErnieMoeForPretraining(ErnieMoeModel(cfg))
        # fused MLM head+CE (chunked) — same win as the BERT path
        loss = model.forward_with_mlm_loss(ids, labels)
        opt = optimizer.AdamW(learning_rate=1e-4,
                              parameters=model.parameters())
        opt.minimize(loss)
        return loss

    rng = np.random.default_rng(0)
    feed = {"ids": jnp.asarray(rng.integers(
                0, cfg.vocab_size, (B, S)).astype(np.int64)),
            "labels": jnp.asarray(rng.integers(
                0, cfg.vocab_size, (B, S)).astype(np.int64))}
    dt, final, tele = _timed_static_train(build, feed, args)
    tps = B * S * args.steps / dt
    emit("ernie_moe_tokens_per_sec_per_chip", tps, "tokens/s/chip",
         {"batch": B, "seq": S, "steps": args.steps,
          "experts": cfg.num_experts, "top_k": cfg.top_k,
          "moe_every": cfg.moe_every, "final_loss": round(final, 4),
          "amp": "bfloat16", **tele,
          "dispatch_overhead": _moe_dispatch_overhead(cfg)})


def _moe_dispatch_overhead(cfg):
    """Single-chip overhead of the ep all_to_all-dispatch MoE FFN
    (ep_moe_ffn, VERDICT r3 #8) vs the bare batched expert FFN: the
    gate+binning+combine cost the compiled dispatch path adds."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.incubate.distributed.models.moe import ep_moe_ffn

    E, M, H = cfg.num_experts, cfg.hidden_size, cfg.intermediate_size
    S = 4096
    C = S // E * 2
    rng = np.random.default_rng(0)
    bf = jnp.bfloat16
    x = jnp.asarray(rng.standard_normal((S, M)), bf)
    gw = jnp.asarray(rng.standard_normal((M, E)) * 0.1, bf)
    gb = jnp.zeros((E,), bf)
    w1 = jnp.asarray(rng.standard_normal((E, M, H)) * 0.05, bf)
    b1 = jnp.zeros((E, H), bf)
    w2 = jnp.asarray(rng.standard_normal((E, H, M)) * 0.05, bf)
    b2 = jnp.zeros((E, M), bf)

    REPS = 20  # loop INSIDE the jit: one device call per timing, so
               # per-call dispatch cannot dominate the number

    def chain(body):
        def run(x, *rest):
            def it(_, xc):
                return body(xc, *rest)
            return jax.lax.fori_loop(0, REPS, it, x)
        return jax.jit(run)

    moe = chain(lambda xv, *a: ep_moe_ffn(xv, *a, ep_axis=None,
                                          num_expert=E, capacity=C,
                                          top_k=cfg.top_k))

    def dense(xv, w1v, b1v, w2v, b2v, gw=None, gb=None):
        # FLOPs-matched baseline: the MoE path runs E*C = top_k*S slot
        # rows through expert FFNs, so the dense reference processes the
        # SAME row count — the delta is pure gate/bin/all_to_all/combine
        xv2 = jnp.concatenate([xv] * cfg.top_k, axis=0)
        h = jax.nn.gelu(xv2 @ w1v[0] + b1v[0])
        out = h @ w2v[0] + b2v[0]
        return out[:xv.shape[0]]  # keep the loop-carried shape

    dn = chain(dense)

    def timeit(fn, *a):
        # block_until_ready is a true barrier on the installed backend
        jax.block_until_ready(fn(*a))
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*a))
        return (time.perf_counter() - t0) / REPS

    t_moe = timeit(moe, x, gw, gb, w1, b1, w2, b2)
    t_dense = timeit(dn, x, w1, b1, w2, b2)
    out = {"moe_ms": round(t_moe * 1e3, 3),
           "dense_ffn_ms": round(t_dense * 1e3, 3),
           "overhead_x": round(t_moe / max(t_dense, 1e-9), 2)}
    # measured fused-dispatch delta (the moe_fused_dispatch_predicted
    # anchor's measured counterpart) — TPU only: the interpret-mode
    # kernel walk on CPU measures the interpreter, not the dispatch
    if jax.default_backend() != "cpu":
        try:
            fz = chain(lambda xv, *a: ep_moe_ffn(
                xv, *a, ep_axis=None, num_expert=E, capacity=C,
                top_k=cfg.top_k, fused_dispatch=True))
            t_fused = timeit(fz, x, gw, gb, w1, b1, w2, b2)
            out["moe_fused_ms"] = round(t_fused * 1e3, 3)
            out["fused_dispatch_speedup_x"] = round(
                t_moe / max(t_fused, 1e-9), 2)
        except Exception as e:  # Mosaic lowering failure: report, keep row
            out["moe_fused_error"] = repr(e)[:200]
    return out


def bench_gpt(args, config_name=None):
    """BASELINE configs #3/#4 proxy: GPT pretraining tokens/sec/chip on
    the compiled hybrid train step (single-chip mesh on the real TPU)."""
    import jax
    from paddle_tpu.distributed.mesh import HybridCommunicateGroup
    from paddle_tpu.distributed import mesh as mesh_mod
    from paddle_tpu.models.gpt import (
        GPTForPretraining, GPTHybridTrainStep, GPTModel, gpt_tiny_config,
        gpt_345m_config, gpt_1p3b_config,
    )

    on_cpu = jax.devices()[0].platform == "cpu"
    config_name = config_name or args.config
    if on_cpu:
        config_name = "tiny"
    extra = {}
    remat = {"full": True, "dots": "dots", "none": False}[args.remat]
    if config_name == "tiny":
        cfg = gpt_tiny_config()
        B = args.batch or 8
        S = args.seq or 128
        step_kw = {}
    elif config_name == "345m":
        # num_heads=8 (d_head=128): same params and FLOPs as the 16-head
        # Megatron shape, but fills the 128-lane MXU exactly — the TPU-native
        # shape choice (+31% tokens/s on v5e; GPT-3 uses d_head=128 too).
        # The shape is recorded in extras so rounds stay auditable.
        cfg = gpt_345m_config(max_position_embeddings=1024, num_heads=8)
        # B12 + dots-policy remat beats B24 + full remat on v5e (43.3k vs
        # 42.5k tok/s): saving matmul outputs trims the recompute to the
        # elementwise glue; B>=14 with dots OOMs the 16GB chip
        B = args.batch or (12 if args.remat == "dots" else 24)
        S = args.seq or 1024
        step_kw = {}
    else:  # 1.3b — FIRST single-chip measurement (BASELINE #3 proxy):
        # f32 masters + Adam state need 21GB (> the 15.75GB chip), so
        # masters AND moments store in bf16 (update math stays f32);
        # d_head=128 (16 heads @ H=2048) is already the MXU-native shape
        cfg = gpt_1p3b_config()
        # B6 measured best on v5e (12.2k tok/s, 56.5% MFU; B4 12.0k, B2 11.8k)
        B = args.batch or 6
        S = args.seq or 2048
        if remat == "dots":
            remat = True  # dots-policy remat OOMs at 1.3B; full is the default
        step_kw = dict(param_dtype="bfloat16", moment_dtype="bfloat16")
        extra = {"master_dtype": "bfloat16", "moment_dtype": "bfloat16"}

    mesh_mod._global_mesh, mesh_mod._hcg = None, None
    hcg = HybridCommunicateGroup(dp_degree=1, mp_degree=1, pp_degree=1)
    # build the eager f32 weights on the HOST backend: only the step's
    # (possibly bf16) copies ever touch HBM — at 1.3B the f32 eager set
    # plus its f32 stacking temporaries alone would blow the 16GB chip
    try:
        host = jax.devices("cpu")[0]
    except RuntimeError:
        host = None
    import contextlib
    dev_ctx = jax.default_device(host) if host is not None \
        else contextlib.nullcontext()
    with dev_ctx:
        model = GPTForPretraining(GPTModel(cfg))
    step = GPTHybridTrainStep(model, cfg, hcg, n_micro=1, lr=1e-4,
                              remat=remat, compute_dtype="bfloat16",
                              **step_kw)

    rng = np.random.default_rng(0)
    ids = rng.integers(0, cfg.vocab_size, size=(B, S)).astype(np.int32)
    labels = np.roll(ids, -1, axis=1).astype(np.int32)

    fpt, n_params = model_flops_per_token(cfg, S)
    step.flops_per_token = fpt  # feeds the framework MFU gauge too
    telemetry = _StepTelemetry()

    for _ in range(args.warmup):
        loss = step(ids, labels)
    if args.warmup:
        loss.numpy()  # sync; with --warmup 0 the first timed step compiles

    step_times = []
    t0 = time.perf_counter()
    for _ in range(args.steps):
        t1 = time.perf_counter()
        loss = step(ids, labels)
        step_times.append(time.perf_counter() - t1)
    final_loss = float(loss.numpy())  # sync
    dt = time.perf_counter() - t0

    tokens = B * S * args.steps
    tps = tokens / dt
    mfu = tps * fpt / peak_flops_per_chip()

    emit(f"gpt_{config_name.replace('.', 'p')}_tokens_per_sec_per_chip",
         tps, "tokens/s/chip", {
             "mfu": round(mfu, 4),
             "n_params": n_params,
             "batch": B, "seq": S, "steps": args.steps,
             "hidden": cfg.hidden_size, "layers": cfg.num_layers,
             "heads": cfg.num_heads,
             "step_time_ms": round(1000 * dt / args.steps, 2),
             "final_loss": round(final_loss, 4),
             "device": str(jax.devices()[0].device_kind), **extra,
             **telemetry.extras(step_times, wall_s=dt),
         })


def emit_serving_predicted_row(timeout_s=180, quantize=None, mode=None):
    """``serving_predicted`` (``serving_int8_predicted`` with
    ``quantize="int8"``; ``serving_shared_prefix_predicted`` /
    ``serving_disagg_predicted`` with ``mode=``): static cost-model
    serving rows from the PR-5 roofline over the engine's REAL traced
    programs, so a TPU-less round still carries serving numbers — incl.
    the prefix-cache goodput/TTFT anchor and the disaggregated-split
    anchor. Trace-only subprocess; bypasses ``emit()`` like the other
    ``*_predicted`` rows (never a vs_baseline denominator, never
    ``_cpu_smoke``-suffixed)."""
    import subprocess
    metric = {"shared_prefix": "serving_shared_prefix_predicted",
              "disagg": "serving_disagg_predicted",
              "fused_dispatch": "moe_fused_dispatch_predicted",
              "fleet": "serving_fleet_predicted",
              "migration": "serving_fleet_migration_predicted",
              "overload": "serving_overload_predicted"}.get(
        mode, "serving_int8_predicted" if quantize
        else "serving_predicted")
    try:
        r = subprocess.run(
            [sys.executable, "-m", "paddle_tpu.serving.predict",
             "--config", "345m", "--concurrency", "8"]
            + (["--quantize", quantize] if quantize else [])
            + (["--mode", mode] if mode else []),
            capture_output=True, text=True, timeout=timeout_s,
            cwd=os.path.dirname(os.path.abspath(__file__)))
        row = None
        for ln in r.stdout.splitlines():
            try:
                cand = json.loads(ln)
            except ValueError:
                continue
            # only the predict row shape counts — stray JSON-parseable
            # log lines (bare strings/numbers) must not be mistaken
            if isinstance(cand, dict) and (
                    "error" in cand
                    or "predicted_tokens_per_sec" in cand
                    or "predicted_speedup" in cand):
                row = cand
                break
        if row is None:
            raise RuntimeError(
                f"no JSON row (rc={r.returncode}): {r.stderr[-200:]}")
    except Exception as e:
        print(json.dumps({"metric": f"{metric}_ERROR",
                          "value": 0.0, "unit": "error",
                          "vs_baseline": 0.0,
                          "extras": {"error": repr(e)[:300]}}), flush=True)
        return
    if "error" in row:
        print(json.dumps({"metric": f"{metric}_ERROR",
                          "value": 0.0, "unit": "error",
                          "vs_baseline": 0.0, "extras": row}), flush=True)
        return
    if mode == "fused_dispatch":
        value = row.get("predicted_speedup", 0.0)
        unit = ("x step-time speedup (static cost model, fused Pallas "
                "MoE dispatch+combine vs gather chain)")
    elif mode == "migration":
        value = row.get("predicted_speedup", 0.0)
        unit = ("x resume speedup (static cost model, live KV-page "
                "migration over ICI + resume vs full-prompt replay on "
                "a cold cache)")
    else:
        value = row.get("predicted_tokens_per_sec", 0.0)
        unit = ("tokens/s (static cost model, continuous batching"
                + (", int8 weights" if quantize else "")
                + (", prefix cache" if mode == "shared_prefix" else "")
                + (", disaggregated" if mode == "disagg" else "")
                + (", N-replica fleet router" if mode == "fleet" else "")
                + (", deadline-met goodput under overload control at "
                   "2x-capacity arrival" if mode == "overload" else "")
                + ")")
    print(json.dumps({
        "metric": metric,
        "value": value,
        "unit": unit,
        "vs_baseline": 0.0, "extras": row}), flush=True)


def emit_autofusion_predicted_rows(timeout_s=300, export_dir=None):
    """``autofusion_predicted`` plus one ``autofusion_<rule>_predicted``
    row per fired rewrite rule: per-site predicted Δstep-ms of the
    jaxpr auto-fusion pass (``analysis.rewrite``) over the tiny serving
    engines' real traced programs. Trace + interpret-parity work in a
    CPU subprocess, so the anchors land on CPU-smoke AND no-backend
    rounds; calibration_id-stamped so bench_compare can anchor future
    measured fused rows against them. ``export_dir`` (defaults to the
    ``PADDLE_TELEMETRY_DIR`` launch-contract var) also receives the raw
    match records as ``autofusion.json`` for the perf doctor."""
    import subprocess
    export_dir = export_dir or os.environ.get("PADDLE_TELEMETRY_DIR")
    cmd = [sys.executable, "-m", "paddle_tpu.serving.predict",
           "--mode", "autofusion"]
    if export_dir:
        os.makedirs(export_dir, exist_ok=True)
        cmd += ["--export-records",
                os.path.join(export_dir, "autofusion.json")]
    try:
        r = subprocess.run(cmd, capture_output=True, text=True,
                           timeout=timeout_s,
                           cwd=os.path.dirname(os.path.abspath(__file__)))
        row = None
        for ln in r.stdout.splitlines():
            try:
                cand = json.loads(ln)
            except ValueError:
                continue
            if isinstance(cand, dict) and (
                    "error" in cand or "per_rule_delta_ms" in cand):
                row = cand
                break
        if row is None:
            raise RuntimeError(
                f"no JSON row (rc={r.returncode}): {r.stderr[-200:]}")
        if "error" in row:
            raise RuntimeError(row["error"])
    except Exception as e:
        print(json.dumps({"metric": "autofusion_predicted_ERROR",
                          "value": 0.0, "unit": "error",
                          "vs_baseline": 0.0,
                          "extras": {"error": repr(e)[:300]}}), flush=True)
        return
    cal = _calibration_id()
    unit = ("ms/step predicted saving (static cost model, jaxpr "
            "auto-fusion over the tiny serving-engine programs)")
    print(json.dumps({
        "metric": "autofusion_predicted",
        "value": row.get("predicted_total_delta_ms", 0.0),
        "unit": unit, "vs_baseline": 0.0,
        "extras": {**row, "calibration_id": cal}}), flush=True)
    for rule, delta in sorted(
            (row.get("per_rule_delta_ms") or {}).items()):
        sites = [s for s in row.get("sites") or ()
                 if s.get("rule") == rule]
        print(json.dumps({
            "metric": f"autofusion_{rule}_predicted",
            "value": delta, "unit": unit, "vs_baseline": 0.0,
            "extras": {"rule": rule, "sites": sites,
                       "calibration_id": cal}}), flush=True)


def emit_collective_compression_predicted(dp=8, chip="v5e"):
    """``collective_compression_predicted``: ring-model wire bytes of the
    GPT-345M gradient all_reduce (the dp grad-sync — one full parameter
    set of f32 grads per step) at fp32 vs int8-compressed wire. Pure
    arithmetic over the shared ring/compression formulas — zero device
    work, zero run-to-run noise, so bench_compare treats it as an
    anchor. The row VALUE is the predicted wire-bytes reduction
    (>= ~3.9x for f32 -> int8 with 256-element chunk scales)."""
    try:
        from paddle_tpu.distributed.compress import (compressed_nbytes,
                                                     wire_reduction)
        from paddle_tpu.models.gpt import (gpt_345m_config,
                                           model_flops_per_token)
        from paddle_tpu.observability.instrument import CHIP_SPECS
        cfg = gpt_345m_config(max_position_embeddings=1024, num_heads=8)
        _, n_params = model_flops_per_token(cfg, 1024)
        grad_bytes = 4.0 * n_params          # f32 grads, one step
        ring = lambda b: 2.0 * (dp - 1) / dp * b
        wire_fp = ring(grad_bytes)
        wire_i8 = ring(compressed_nbytes(grad_bytes, 4, "int8"))
        wire_bf = ring(compressed_nbytes(grad_bytes, 4, "bf16"))
        spec = dict(CHIP_SPECS.get(chip, CHIP_SPECS["v5e"]), name=chip)
        to_ms = lambda b: 1e3 * b / spec["ici_bw"]
        print(json.dumps({
            "metric": "collective_compression_predicted",
            "value": round(wire_fp / wire_i8, 3),
            "unit": "x wire-bytes reduction (int8 all_reduce, ring "
                    "model, GPT-345M grad sync)",
            "vs_baseline": 0.0,
            "extras": {
                "config": "gpt_345m", "dp": dp, "chip": chip,
                "n_params": int(n_params),
                "grad_mb": round(grad_bytes / 2 ** 20, 1),
                "wire_mb_fp32": round(wire_fp / 2 ** 20, 1),
                "wire_mb_int8": round(wire_i8 / 2 ** 20, 1),
                "wire_mb_bf16": round(wire_bf / 2 ** 20, 1),
                "bf16_reduction": round(wire_fp / wire_bf, 3),
                "comm_ms_fp32": round(to_ms(wire_fp), 3),
                "comm_ms_int8": round(to_ms(wire_i8), 3),
                "chunk_scale_overhead": round(
                    1.0 - wire_reduction(4, "int8") / 4.0, 4),
            }}), flush=True)
    except Exception as e:  # the artifact must say why, not go silent
        print(json.dumps({"metric": "collective_compression_"
                                    "predicted_ERROR",
                          "value": 0.0, "unit": "error",
                          "vs_baseline": 0.0,
                          "extras": {"error": repr(e)[:300]}}), flush=True)


def bench_collective_compression(args):
    """``collective_compression`` row: MEASURED wire-bytes reduction and
    step-time delta of an int8-compressed eager all_reduce vs the fp32
    one on a gradient-shard payload, where the backend has >= 2 devices
    to ring over; the ring-model prediction for the full GPT-345M
    grad-sync config is always emitted alongside (anchor row)."""
    import jax
    emit_collective_compression_predicted()
    devices = jax.devices()
    if len(devices) < 2:
        emit_skip("collective_compression",
                  f"needs >=2 devices for a real collective "
                  f"(have {len(devices)})")
        return
    import paddle_tpu as paddle
    import paddle_tpu.distributed as dist
    from paddle_tpu.distributed import collective as coll
    from paddle_tpu.distributed.mesh import (build_mesh, get_global_mesh,
                                             set_global_mesh)
    from paddle_tpu.observability import get_registry

    on_cpu = devices[0].platform == "cpu"
    prev_mesh = get_global_mesh()
    prev_default = coll._default_group
    n = min(len(devices), 8)
    set_global_mesh(build_mesh(dp=n, devices=list(devices)[:n]))
    coll._set_default_group(None)
    # a grad-shard-sized payload (full 345M grads would be 1.4 GB; the
    # reduction RATIO is payload-size independent — the predicted row
    # carries the full-model numbers)
    elems = (1 << 20) if on_cpu else (16 << 20)
    data = np.random.default_rng(0).normal(size=(elems,)) \
        .astype(np.float32)

    def coll_bytes():
        total = 0.0
        for rec in get_registry().snapshot():
            if rec["name"] == "paddle_collective_bytes_total":
                total += rec.get("value", 0.0)
        return total

    def run(group, reps=3):
        t = paddle.to_tensor(data)
        dist.all_reduce(t, group=group)        # compile + warm
        np.asarray(t.numpy()[:1])
        b0 = coll_bytes()
        t0 = time.perf_counter()
        for _ in range(reps):
            t = paddle.to_tensor(data)
            dist.all_reduce(t, group=group)
        np.asarray(t.numpy()[:1])              # host readback barrier
        return ((coll_bytes() - b0) / reps,
                (time.perf_counter() - t0) / reps)

    telemetry = _StepTelemetry()
    try:
        bytes_fp, t_fp = run(dist.new_group())
        bytes_i8, t_i8 = run(dist.new_group(compress="int8"))
        # the headline reduction comes from the TRACED programs' actual
        # collective operand avals (int8 shard + f32 scale arrays as
        # lowered, ring-priced per eqn) — independent of the ledger's
        # closed-form accounting, so an implementation that ever ships
        # extra exchanges or fatter scales moves this number
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        from paddle_tpu._jax_compat import shard_map
        from paddle_tpu.analysis.passes.cost import estimate_jaxpr_cost
        from paddle_tpu.distributed import compress as C
        mesh = dist.get_global_mesh()
        sizes = {k: int(v) for k, v in dict(mesh.shape).items()}
        x_aval = jax.ShapeDtypeStruct((elems,), jnp.float32)

        def traced_comm(body):
            f = shard_map(body, mesh=mesh, in_specs=P(), out_specs=P(),
                          check_vma=False)
            return estimate_jaxpr_cost(jax.make_jaxpr(f)(x_aval),
                                       axis_sizes=sizes).comm_bytes

        traced_fp = traced_comm(lambda v: jax.lax.psum(v, "dp"))
        traced_i8 = traced_comm(
            lambda v: C.all_reduce_compressed(v, "dp", "int8"))
    finally:
        set_global_mesh(prev_mesh)
        coll._set_default_group(prev_default)
    reduction = traced_fp / max(traced_i8, 1.0)
    emit("collective_compression", reduction,
         "x wire-bytes reduction (traced program payloads, int8 vs "
         "fp32 all_reduce)", {
             "dp": n,
             "payload_mb": round(data.nbytes / 2 ** 20, 1),
             "traced_comm_bytes_fp32": int(traced_fp),
             "traced_comm_bytes_int8": int(traced_i8),
             "ledger_wire_bytes_fp32": int(bytes_fp),
             "ledger_wire_bytes_int8": int(bytes_i8),
             "ledger_reduction": round(bytes_fp / max(bytes_i8, 1.0), 3),
             "step_ms_fp32": round(1e3 * t_fp, 2),
             "step_ms_int8": round(1e3 * t_i8, 2),
             "step_time_delta_pct": round(
                 100.0 * (t_i8 - t_fp) / t_fp, 1) if t_fp else 0.0,
             "note": "traced bytes price the ACTUAL lowered collectives "
                     "(int8 shards + f32 scales); ledger bytes are the "
                     "eager accounting; CPU smoke step times measure "
                     "the emulated quantize+exchange, not ICI wire time",
             **telemetry.extras(),
         })


def bench_serving(args):
    """Serving benchmark: (a) GPTGenerator at 345M — flash prefill
    tokens/sec (ragged prompt length exercises the pad-to-block path)
    and per-token cached-decode latency (VERDICT r4 #6); (b) the
    continuous-batching ServingEngine — tok/s at N concurrent streams
    with p50/p95 per-token latency over the paged KV pool. The serving
    role of reference inference/api/analysis_predictor.cc + its fused
    decode attention."""
    import jax
    from paddle_tpu.models.gpt import (GPTForPretraining, GPTGenerator,
                                       GPTModel, gpt_345m_config,
                                       gpt_tiny_config)

    on_cpu = jax.devices()[0].platform == "cpu"
    if on_cpu:
        cfg, B, S_prompt, max_new = gpt_tiny_config(), 1, 48, 8
    else:
        cfg = gpt_345m_config(max_position_embeddings=1024, num_heads=8)
        # ragged prompt (not a 128-multiple): rides the padded flash path
        B, S_prompt, max_new = 4, 937, 64

    import contextlib
    try:
        host = jax.devices("cpu")[0] if not on_cpu else None
    except RuntimeError:
        host = None
    with jax.default_device(host) if host is not None \
            else contextlib.nullcontext():
        model = GPTForPretraining(GPTModel(cfg))
    gen = GPTGenerator(model, temperature=0.0)

    rng = np.random.default_rng(0)
    ids = rng.integers(0, cfg.vocab_size, (B, S_prompt)).astype(np.int32)

    def timed(max_new_tokens, reps):
        out = gen(ids, max_new_tokens=max_new_tokens)  # compile + warm
        np.asarray(out.numpy()[0, -1])
        t0 = time.perf_counter()
        for _ in range(reps):
            out = gen(ids, max_new_tokens=max_new_tokens)
        np.asarray(out.numpy()[0, -1])  # host readback = true barrier
        return (time.perf_counter() - t0) / reps

    telemetry = _StepTelemetry()
    reps = 3
    t_prefill = timed(1, reps)          # prefill + 1 sampled token
    t_full = timed(max_new, reps)       # prefill + max_new tokens
    decode_ms = 1e3 * (t_full - t_prefill) / max(max_new - 1, 1)
    prefill_tps = B * S_prompt / t_prefill
    tele = telemetry.extras()  # no step loop: doctor sees compile/anomalies
    emit("gpt_345m_prefill_tokens_per_sec_per_chip", prefill_tps,
         "tokens/s/chip",
         {"batch": B, "prompt_len": S_prompt, "ragged": S_prompt % 128 != 0,
          "reps": reps, **tele})
    emit("gpt_345m_decode_ms_per_token", decode_ms, "ms/token",
         {"batch": B, "prompt_len": S_prompt, "max_new": max_new,
          "note": "lower is better; vs_baseline>1 means SLOWER", **tele})

    bench_serving_engine(args, model, cfg, on_cpu)
    bench_serving_shared_prefix(args, model, cfg, on_cpu)
    if on_cpu:
        # the measured rows above are _cpu_smoke; the artifact still owes
        # TPU-comparable serving numbers — the static cost model's, fp,
        # int8, prefix-cache, disaggregated-split and fused-dispatch
        # anchors
        emit_serving_predicted_row()
        emit_serving_predicted_row(quantize="int8")
        emit_serving_predicted_row(mode="shared_prefix")
        emit_serving_predicted_row(mode="disagg")
        emit_serving_predicted_row(mode="fused_dispatch")
        # the auto-fusion rewrite's predicted per-rule Δstep-ms anchors
        emit_autofusion_predicted_rows()


def bench_serving_shared_prefix(args, model, cfg, on_cpu):
    """``serving_shared_prefix`` row: the prefix-cache + chunked-prefill
    engine on a shared-prefix workload (the millions-of-users shape:
    one system prompt, many suffixes), vs the PR 8 engine on the SAME
    workload. Value = end-to-end goodput tokens/s with the cache; the
    extras carry the baseline, the TTFT split, pool stats proving page
    reuse (>0 shared pages, hit rate), the SLO verdict under the load,
    and the chunked-prefill stall bound (per-token p99 under a
    long-prompt+decode mix, chunked vs not)."""
    from paddle_tpu.observability.reqtrace import quantile as pq
    from paddle_tpu.observability.slo import SLOConfig
    from paddle_tpu.serving import ContinuousBatchingScheduler, ServingEngine
    from paddle_tpu.serving.prefix_cache import make_shared_prefix_workload

    if on_cpu:
        n_req, prefix_len, suffix_len, max_new = 6, 48, 8, 4
        page_size, chunk, buckets = 8, 16, (1, 2, 4, 8)
        slo_cfg = SLOConfig(ttft_p95_s=30.0, per_token_p99_s=30.0,
                            queue_wait_p95_s=30.0)
    else:
        n_req, prefix_len, suffix_len, max_new = 8, 768, 128, 64
        page_size, chunk, buckets = 64, 256, (1, 2, 4, 8)
        slo_cfg = SLOConfig()
    prompts = make_shared_prefix_workload(
        cfg.vocab_size, n_req, prefix_len, suffix_len, seed=2)

    def run_one(prefix_cache):
        engine = ServingEngine(model, cfg, page_size=page_size,
                               decode_buckets=buckets, temperature=0.0,
                               prefix_cache=prefix_cache,
                               prefill_chunk=chunk if prefix_cache
                               else None)
        # whole-prompt budget: this row measures CACHING, not the
        # stall bound (stall_mix below measures that) — throttling
        # prefill to one chunk/tick would only blur the TTFT delta
        sched = ContinuousBatchingScheduler(
            engine, slo=slo_cfg,
            prefill_token_budget=prefix_len + suffix_len)
        t0 = time.perf_counter()
        for p in prompts:
            sched.submit(p, max_new_tokens=max_new)
        max_shared = 0
        while sched.pending:
            sched.step()
            max_shared = max(max_shared,
                             engine.pool.stats()["pages_shared"])
        finished = sched.finished
        dt = time.perf_counter() - t0
        toks = sum(len(r.tokens) for r in finished)
        ttfts = [r.summary()["ttft_s"] for r in finished]
        pool = engine.pool.stats()
        pool["max_pages_shared_in_flight"] = max_shared
        return {
            "tps": toks / dt if dt > 0 else 0.0,
            "ttft_mean_s": float(np.mean(ttfts)),
            "ttft_p95_s": pq(sorted(ttfts), 0.95),
            "pool": pool,
            "cache": engine.prefix_cache.stats()
            if engine.prefix_cache else None,
            "cached": [r.cached_prefix_len for r in finished],
            "slo": sched.slo.snapshot() if sched.slo else None,
        }

    telemetry = _StepTelemetry()
    t0 = time.perf_counter()
    base = run_one(False)
    cached = run_one(True)
    dt = time.perf_counter() - t0
    violations = int((cached["slo"] or {}).get("violations", 0))

    # chunked-prefill stall bound: a long prompt admitted mid-decode; the
    # running stream's per-token p99 must not absorb the whole prefill
    def stall_mix(chunked):
        engine = ServingEngine(model, cfg, page_size=page_size,
                               decode_buckets=(1, 2), temperature=0.0,
                               prefill_chunk=chunk if chunked else None)
        sched = ContinuousBatchingScheduler(engine)
        rng = np.random.default_rng(5)
        short = rng.integers(0, cfg.vocab_size,
                             (suffix_len,)).astype(np.int32)
        # the long prompt spans many chunks, so the unchunked engine's
        # single-tick prefill is a real stall for the running stream
        long_p = rng.integers(
            0, cfg.vocab_size,
            (min(8 * chunk, engine.max_seq_len - 3 * max_new - 1),)
        ).astype(np.int32)
        r = sched.submit(short, max_new_tokens=max_new * 3)
        sched.step(); sched.step()
        sched.submit(long_p, max_new_tokens=2)
        # wall-clock gaps between the short stream's token emissions:
        # THE stall metric — an unchunked engine parks the whole long
        # prefill inside one gap, the chunked one spreads it
        gaps, n_prev, t_prev = [], len(r.tokens), time.perf_counter()
        while sched.pending:
            sched.step()
            if len(r.tokens) > n_prev:
                now = time.perf_counter()
                gaps.append(now - t_prev)
                n_prev, t_prev = len(r.tokens), now
        return 1e3 * pq(sorted(gaps or [0.0]), 0.99)

    p99_unchunked = stall_mix(False)
    p99_chunked = stall_mix(True)
    emit("serving_shared_prefix", cached["tps"],
         "tokens/s (end-to-end goodput, prefix cache + chunked "
         "prefill)", {
             "requests": n_req, "prefix_len": prefix_len,
             "suffix_len": suffix_len, "max_new": max_new,
             "page_size": page_size, "prefill_chunk": chunk,
             "tokens_per_sec_no_cache": round(base["tps"], 2),
             "goodput_speedup": round(
                 cached["tps"] / base["tps"], 3) if base["tps"] else 0.0,
             "ttft_mean_s_cached": round(cached["ttft_mean_s"], 4),
             "ttft_mean_s_no_cache": round(base["ttft_mean_s"], 4),
             "ttft_speedup": round(
                 base["ttft_mean_s"] / cached["ttft_mean_s"], 3)
             if cached["ttft_mean_s"] else 0.0,
             "cached_prefix_lens": cached["cached"],
             "kv_pool_stats": cached["pool"],
             "prefix_cache_stats": cached["cache"],
             "slo_violations": violations,
             "slo_clean": violations == 0,
             "chunked_prefill": {
                 "per_token_p99_ms_chunked": round(p99_chunked, 2),
                 "per_token_p99_ms_unchunked": round(p99_unchunked, 2),
                 "stall_reduction": round(
                     p99_unchunked / p99_chunked, 3) if p99_chunked
                 else 0.0,
             },
             **telemetry.extras(wall_s=dt),
         })


def bench_serving_fleet(args):
    """``serving_fleet_tokens_per_sec`` row: the multi-replica router —
    aggregate tok/s + TTFT at M streams across N ``ServingEngine``
    replica PROCESSES behind the prefix-affinity ``FleetRouter``, on a
    shared-prefix workload (2 prefix groups). The SAME workload runs
    again under round-robin routing, so the row carries the acceptance
    A/B inline: affinity must show a HIGHER aggregate prefix hit rate
    and a LOWER mean TTFT than round-robin (both from the federated
    fleet summary). Extras also carry per-replica decode skew, the SLO
    verdict, and the fleet-predicted anchor's inputs.

    Replica processes always run on the CPU backend — one host cannot
    share its (exclusive-per-process) TPU across N engines — so the
    measured row is emitted on CPU rounds (``_cpu_smoke``); TPU rounds
    still carry the ``serving_fleet_predicted`` anchor."""
    import tempfile
    import jax
    from paddle_tpu.observability.reqtrace import quantile as pq

    on_cpu = jax.devices()[0].platform == "cpu"
    emit_serving_predicted_row(mode="fleet")
    emit_serving_predicted_row(mode="migration")
    if not on_cpu:
        emit_skip("serving_fleet",
                  "fleet replicas are separate processes and cannot "
                  "share this host's one TPU; measured row runs on CPU "
                  "rounds (serving_fleet_predicted anchor emitted)")
        return
    from paddle_tpu.models.gpt import gpt_tiny_config
    from paddle_tpu.serving.fleet import FleetRouter
    from paddle_tpu.serving.prefix_cache import make_shared_prefix_workload

    cfg = gpt_tiny_config(num_layers=2, hidden_size=32, num_heads=2,
                          max_position_embeddings=128)
    n_replicas, n_req, max_new = 2, 12, 6
    # 4 prefix groups over 2 replicas, SHUFFLED arrival order: the
    # shuffle stops round-robin from aliasing onto the group structure
    # (it then smears ~every group across both caches — the honest
    # baseline), while affinity routing is arrival-order-independent
    # and keeps each group whole. seed=5 rendezvous-splits the 4
    # groups 2/2 across 2 replicas, so the comparison isolates ROUTING
    # (cache hits), not load imbalance. Long prefix, short suffix: a
    # cache hit skips most of the prefill, so TTFT shows it too.
    n_groups, prefix_len, suffix_len = 4, 40, 8
    prompts = make_shared_prefix_workload(
        cfg.vocab_size, n_req, prefix_len, suffix_len,
        n_prefixes=n_groups, seed=5)
    order = np.random.default_rng(7).permutation(n_req)
    prompts = [prompts[i] for i in order]
    engine_kwargs = dict(page_size=8, decode_buckets=(1, 2, 4, 8),
                         prefill_chunk=8, prefix_cache=True)

    def run_fleet(policy):
        fleet = FleetRouter(
            cfg, n_replicas=n_replicas,
            engine_kwargs=dict(engine_kwargs), policy=policy,
            # whole-prompt budget, same as the shared-prefix row: this
            # row measures ROUTING (cache hits), not the chunked-stall
            # bound — one-chunk-per-tick serialization would drown the
            # TTFT delta in decode-tick interleaving at tiny scale
            scheduler_kwargs=dict(
                prefill_token_budget=prefix_len + suffix_len),
            run_dir=tempfile.mkdtemp(prefix=f"fleet_bench_{policy}_"),
            slo={"ttft_p95_s": 30.0, "queue_wait_p95_s": 30.0}, seed=0)
        t0 = time.perf_counter()
        fleet.start()
        fleet.warmup()                   # cold-start off the clock
        startup_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        rids = [fleet.submit(p, max_new_tokens=max_new) for p in prompts]
        drained = fleet.run(timeout=300)
        wall = time.perf_counter() - t0
        status = fleet.fleet_status()
        # shutdown() returns None when federation failed — the row must
        # degrade, not crash the lane
        summary = fleet.shutdown() or {}
        fl = summary.get("fleet") or {}
        sv = summary.get("serving") or {}
        recs = [fleet.results[r] for r in rids
                if fleet.results.get(r, {}).get("state") == "finished"]
        ttfts = sorted(
            float((r.get("summary") or {}).get("ttft_s") or 0.0)
            + float((r.get("summary") or {}).get("router_wait_s") or 0.0)
            for r in recs)
        new_tokens = sum(len(r["tokens"]) for r in recs)
        per_rep = sv.get("per_replica") or {}
        means = [d["per_token_s_mean"] for d in per_rep.values()
                 if d.get("per_token_s_mean")]
        skew = (max(means) / (sorted(means)[len(means) // 2])) \
            if len(means) >= 2 and sorted(means)[len(means) // 2] else None
        agg = status["pool_aggregate"]
        return {
            "drained": drained,
            "tps": new_tokens / wall if wall > 0 else 0.0,
            "ttft_mean_s": float(np.mean(ttfts)) if ttfts else None,
            "ttft_p95_s": pq(ttfts, 0.95) if ttfts else None,
            "prefix_hit_rate": agg["prefix_hit_rate"],
            "tokens_reused": agg["tokens_reused"],
            "routing": status["routing"],
            "per_replica": per_rep,
            "per_replica_skew": round(skew, 3) if skew else None,
            "slo_violations": {
                k: v for k, v in
                (sv.get("slo_violations") or {}).items() if v},
            "requeued": fl.get("requeued_rids", []),
            "restarts": fl.get("restarts", 0),
            "startup_s": round(startup_s, 2),
            "wall_s": round(wall, 3),
        }

    telemetry = _StepTelemetry()
    aff = run_fleet("affinity")
    rr = run_fleet("round_robin")
    viol = aff["slo_violations"]
    emit("serving_fleet_tokens_per_sec", aff["tps"],
         f"tokens/s (aggregate, {n_replicas} engine replicas, "
         f"prefix-affinity router)", {
             "replicas": n_replicas,
             "streams": n_req,
             "max_new": max_new,
             "prefix_len": prefix_len,
             "prefix_groups": n_groups,
             "drained": aff["drained"] and rr["drained"],
             "ttft_mean_s": round(aff["ttft_mean_s"], 4)
             if aff["ttft_mean_s"] is not None else None,
             "ttft_p95_s": round(aff["ttft_p95_s"], 4)
             if aff["ttft_p95_s"] is not None else None,
             "prefix_hit_rate": aff["prefix_hit_rate"],
             "tokens_reused": aff["tokens_reused"],
             "routing": aff["routing"],
             "per_replica_skew": aff["per_replica_skew"],
             "startup_s": aff["startup_s"],
             "restarts": aff["restarts"],
             "requeued": aff["requeued"],
             "slo_clean": not viol,
             "slo_violations": viol,
             # the acceptance A/B: same workload, same fleet size,
             # round-robin routing — affinity must win on hit rate AND
             # mean TTFT
             "round_robin": {
                 "tokens_per_sec": round(rr["tps"], 2),
                 "ttft_mean_s": round(rr["ttft_mean_s"], 4)
                 if rr["ttft_mean_s"] is not None else None,
                 "prefix_hit_rate": rr["prefix_hit_rate"],
                 "tokens_reused": rr["tokens_reused"],
             },
             "affinity_beats_round_robin": bool(
                 aff["prefix_hit_rate"] > rr["prefix_hit_rate"]
                 and aff["ttft_mean_s"] is not None
                 and rr["ttft_mean_s"] is not None
                 and aff["ttft_mean_s"] < rr["ttft_mean_s"]),
             "note": "tiny-model CPU smoke: tok/s is dominated by "
                     "fixed per-tick host overheads, so the routing "
                     "win shows in prefix_hit_rate and TTFT (the "
                     "acceptance pair); the serving_fleet_predicted "
                     "anchor carries the at-scale throughput story",
             **telemetry.extras(),
         })


def bench_serving_overload(args):
    """``serving_overload_goodput_tokens_per_sec`` row: deadline-met
    goodput at ~2× the tiny engine's measured admission capacity,
    overload control ON (per-request deadlines + brownout + priced
    admission) vs OFF (no deadlines, brownout threshold parked at ∞) on
    the SAME paced arrival stream — the in-row acceptance A/B. Extras
    carry the deadline-miss rate, p99 TTFT, brownout time share, and
    the no-control baseline; the ``serving_overload_predicted`` anchor
    (emitted first, so it lands on no-backend rounds too) prices the
    same story from the roofline.

    Tiny-model CPU smoke: arrival pacing rides the wall clock, so the
    headline tok/s is noise-bound — the acceptance signal is the
    control-vs-baseline goodput RATIO and the bounded TTFT tail, both
    dominated by queueing (seconds) rather than per-tick jitter (ms)."""
    import contextlib
    import jax
    from paddle_tpu.observability.reqtrace import quantile as pq

    emit_serving_predicted_row(mode="overload")
    on_cpu = jax.devices()[0].platform == "cpu"
    if not on_cpu:
        emit_skip("serving_overload",
                  "overload A/B is a wall-clock queueing experiment on "
                  "the tiny CPU engine; TPU rounds carry the "
                  "serving_overload_predicted anchor")
        return
    from paddle_tpu.models.gpt import (GPTForPretraining, GPTModel,
                                       gpt_tiny_config)
    from paddle_tpu.serving import ContinuousBatchingScheduler, \
        ServingEngine
    from paddle_tpu.serving.prefix_cache import make_shared_prefix_workload

    cfg = gpt_tiny_config(num_layers=2, hidden_size=32, num_heads=2,
                          max_position_embeddings=128)
    model = GPTForPretraining(GPTModel(cfg))
    n_req, max_new = 64, 8
    prompts = make_shared_prefix_workload(
        cfg.vocab_size, n_req, 24, 8, n_prefixes=2, seed=3)
    engine_kwargs = dict(page_size=8, decode_buckets=(1, 2, 4),
                         prefill_chunk=8, prefix_cache=True,
                         temperature=0.0)

    @contextlib.contextmanager
    def _env(**kv):
        old = {k: os.environ.get(k) for k in kv}
        os.environ.update(kv)
        try:
            yield
        finally:
            for k, v in old.items():
                if v is None:
                    os.environ.pop(k, None)
                else:
                    os.environ[k] = v

    # ---- calibrate capacity: burst the FULL workload (same prefix mix,
    # same cache warm-up trajectory the arms see) and divide — the rate
    # the engine sustains with a full backlog is the admission capacity
    # the 2x arrival stream must beat. Two passes, keep the SECOND: the
    # first pass eats the process-wide jit compiles, so a single cold
    # burst under-reads capacity vs the warm arms and the "2x" stream
    # never actually overloads them
    cap_rps = 1.0
    for _ in range(2):
        engine = ServingEngine(model, cfg, **engine_kwargs)
        sched = ContinuousBatchingScheduler(engine)
        t0 = time.perf_counter()
        for p in prompts:
            sched.submit(np.asarray(p, np.int32), max_new_tokens=max_new)
        cal = sched.run()
        cal_wall = time.perf_counter() - t0
        cap_rps = len(cal) / cal_wall if cal_wall > 0 else 1.0
    # deadline = the time capacity needs to serve ~8 queued requests,
    # floored well above a single OS-scheduling/GC hiccup (at ~10ms
    # service times a 60ms deadline dies to one 100ms stall — the
    # floor keeps the A/B about queueing, not jitter): at 2x arrival
    # the uncontrolled FIFO backlog (n_req/2 requests by end of
    # stream, ~350ms of work) crosses it mid-window, so the
    # baseline's tail misses while controlled admissions stay inside
    deadline_s = max(8.0 / cap_rps, 0.15)
    lam = 2.0 * cap_rps                 # 2x admission capacity
    slo = {"ttft_p95_s": deadline_s / 3.0,
           "queue_wait_p95_s": deadline_s / 3.0,
           "window": 8, "min_requests": 4}
    del sched, engine

    def run_arm(control):
        burn = "1.0" if control else "1000000000"
        with _env(PADDLE_FLEET_BROWNOUT_BURN=burn):
            engine = ServingEngine(model, cfg, **engine_kwargs)
            sched = ContinuousBatchingScheduler(engine, slo=dict(slo),
                                                max_queue=64)
        t_start = time.perf_counter()
        next_t = t_start
        for p in prompts:
            while time.perf_counter() < next_t:
                if not sched.step():
                    time.sleep(0.0005)
            sched.submit(np.asarray(p, np.int32),
                         max_new_tokens=max_new,
                         deadline_s=deadline_s if control else None)
            next_t += 1.0 / lam
        sched.run()
        wall = time.perf_counter() - t_start
        fin = list(sched.finished)
        met = [r for r in fin
               if (r.finish_time - r.submit_time) <= deadline_s]
        good_tokens = sum(len(r.tokens) for r in met)
        ttfts = sorted(r.first_token_time - r.submit_time for r in fin
                       if r.first_token_time is not None)
        n_dl = len(sched.deadline_exceeded)
        n_rej = len(sched.rejected)
        ov = (sched.status().get("overload") or {})
        ms = ov.get("mode_seconds") or {}
        mode_total = sum(ms.values()) or wall
        return {
            "goodput_tps": good_tokens / wall if wall > 0 else 0.0,
            "finished": len(fin),
            "met_deadline": len(met),
            "deadline_exceeded": n_dl,
            "rejected": n_rej,
            # miss = cancelled + finished-late, over the work the
            # scheduler actually took on (rejects were told to retry)
            "deadline_miss_rate": round(
                (n_dl + len(fin) - len(met))
                / max(len(fin) + n_dl, 1), 4),
            "ttft_p99_s": round(pq(ttfts, 0.99), 4) if ttfts else None,
            "brownout_share": round(
                (ms.get("brownout", 0.0) + ms.get("shedding", 0.0))
                / mode_total, 4),
            "mode_transitions": ov.get("mode_transitions", 0),
            "retry_after_s": ov.get("retry_after_s"),
            "wall_s": round(wall, 3),
        }

    telemetry = _StepTelemetry()
    ctl = run_arm(control=True)
    base = run_arm(control=False)
    emit("serving_overload_goodput_tokens_per_sec", ctl["goodput_tps"],
         "tokens/s deadline-met goodput (tiny engine, 2x-capacity "
         "arrival, overload control on)", {
             "requests": n_req,
             "max_new": max_new,
             "arrival_rps": round(lam, 3),
             "capacity_rps": round(cap_rps, 3),
             "deadline_s": round(deadline_s, 4),
             "finished": ctl["finished"],
             "met_deadline": ctl["met_deadline"],
             "deadline_exceeded": ctl["deadline_exceeded"],
             "rejected": ctl["rejected"],
             "deadline_miss_rate": ctl["deadline_miss_rate"],
             "ttft_p99_s": ctl["ttft_p99_s"],
             "ttft_p99_bounded": bool(
                 ctl["ttft_p99_s"] is not None
                 and ctl["ttft_p99_s"] <= deadline_s),
             "brownout_share": ctl["brownout_share"],
             "mode_transitions": ctl["mode_transitions"],
             "retry_after_s": ctl["retry_after_s"],
             "wall_s": ctl["wall_s"],
             # the acceptance A/B: same paced workload, control off
             "no_control": {
                 "goodput_tokens_per_sec": round(base["goodput_tps"], 2),
                 "deadline_miss_rate": base["deadline_miss_rate"],
                 "ttft_p99_s": base["ttft_p99_s"],
                 "finished": base["finished"],
                 "wall_s": base["wall_s"],
             },
             "control_beats_baseline": bool(
                 ctl["goodput_tps"] >= base["goodput_tps"]),
             **telemetry.extras(),
         })


def bench_serving_engine(args, model, cfg, on_cpu):
    """Continuous-batching engine rows: N concurrent ragged streams
    through the paged-KV scheduler; tok/s + per-token p50/p95 (a decode
    step emits one token per active stream, so step walltimes ARE the
    per-token latencies at the stream level). Runs twice — float
    weights, then the weight-only-int8 deploy path
    (``quantize="int8"``) — so the artifact carries the int8 serving
    delta next to the fp row."""
    from paddle_tpu.serving import ContinuousBatchingScheduler, ServingEngine

    if on_cpu:
        n_streams, max_new, page_size = 2, 4, 8
        buckets, prefill_buckets = (1, 2), None
        prompt_lens = [24, 40]
    else:
        n_streams, max_new, page_size = 8, 64, 64
        buckets = (1, 2, 4, 8)
        # few prefill buckets: each is one AOT compile (20-40s on TPU)
        prefill_buckets = (256, 512, 1024)
        # ragged mix: every prompt a different non-aligned length
        prompt_lens = [937, 512, 701, 233, 864, 129, 395, 620]

    def one(metric, quantize=None, extra_extras=None):
        engine = ServingEngine(model, cfg, page_size=page_size,
                               decode_buckets=buckets,
                               prefill_buckets=prefill_buckets,
                               temperature=0.0, quantize=quantize)
        # telemetry baseline AFTER the engine build: the AOT bucket
        # compiles are reported separately (engine_compile_s) and must
        # not make quick_verdict call a healthy serving run
        # compile-dominated
        telemetry = _StepTelemetry()
        sched = ContinuousBatchingScheduler(engine)
        rng = np.random.default_rng(1)
        t0 = time.perf_counter()
        for s in prompt_lens:
            sched.submit(
                rng.integers(0, cfg.vocab_size, (s,)).astype(np.int32),
                max_new_tokens=max_new)
        finished = sched.run()
        dt = time.perf_counter() - t0
        new_tokens = sum(len(r.tokens) for r in finished)
        tps = new_tokens / dt if dt > 0 else 0.0
        from paddle_tpu.observability.reqtrace import quantile as pq
        st = sorted(sched.step_times) or [0.0]
        q = lambda p: pq(st, p)
        ttfts = [r.summary()["ttft_s"] for r in finished]
        # request-scoped percentiles from the per-request records (NOT
        # step walltimes): queue wait across requests, per-token tail
        # pooled over every request's decode-tick samples
        recs = sched.request_records()
        qw = sorted(r["queue_wait_s"] for r in recs
                    if r.get("queue_wait_s") is not None)
        tok_samples = sorted(s for r in finished
                             for s in (r.trace.token_samples
                                       if r.trace is not None else []))
        emit(metric, tps, "tokens/s (decode, continuous batching"
             + (", int8 weights" if quantize else "") + ")", {
                 "concurrent_streams": n_streams,
                 "requests": len(finished),
                 "new_tokens": new_tokens,
                 "per_token_ms_p50": round(1e3 * q(0.50), 2),
                 "per_token_ms_p95": round(1e3 * q(0.95), 2),
                 "per_token_ms_p99": round(1e3 * pq(tok_samples, 0.99), 2),
                 "queue_wait_ms_p50": round(1e3 * pq(qw, 0.50), 2),
                 "queue_wait_ms_p95": round(1e3 * pq(qw, 0.95), 2),
                 "ttft_s_mean": round(float(np.mean(ttfts)), 4),
                 "page_size": page_size,
                 "decode_buckets": list(buckets),
                 "kv_pool_stats": engine.pool.stats(),
                 "engine_compile_s": round(engine.compile_s, 2),
                 "prompt_lens": prompt_lens,
                 "max_new": max_new,
                 "weights_mb": round(engine.weight_bytes() / 2 ** 20, 1),
                 **(extra_extras or {}),
                 **telemetry.extras(sched.step_times, wall_s=dt),
             })
        return engine

    eng_fp = one("serving_engine_tokens_per_sec")
    fp_bytes = eng_fp.weight_bytes()
    del eng_fp  # free the float weights before the int8 build
    try:
        one("serving_engine_int8_tokens_per_sec", quantize="int8",
            extra_extras={"fp_weights_mb": round(fp_bytes / 2 ** 20, 1)})
    except Exception as e:  # the fp row must survive an int8 failure
        emit_skip("serving_engine_int8", f"int8 engine failed: "
                                         f"{repr(e)[:200]}")


def bench_gpt_13b_stage_proxy(args):
    """BASELINE #4 single-chip evidence (VERDICT r4 #2a): one pp-stage x
    mp-slice of gpt_13b_config under mp=4 x pp=4 — 10 layers of H=5120
    with this chip's 10-of-40 heads (d=128) and F/4 FFN slice, ~0.79B
    params/chip — run as the 1F1B per-tick compute (fwd + per-tick vjp,
    per-block remat) + the AdamW slice update. Excludes the CE head and
    inter-chip collectives (mid-stage chip; noted in extras)."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.models.gpt import gpt_13b_config, gpt_block

    cfg = gpt_13b_config()
    mp, pp = 4, 4
    L_stage = cfg.num_layers // pp           # 10
    nh_loc = cfg.num_heads // mp             # 10 heads (d=128)
    d = cfg.head_dim
    H = cfg.hidden_size                      # 5120 (global)
    F_loc = cfg.intermediate_size // mp      # 5120
    mb = args.batch or 1
    S = args.seq or cfg.max_position_embeddings

    on_cpu = jax.devices()[0].platform == "cpu"
    if on_cpu:
        L_stage, H, nh_loc, d, F_loc, S = 2, 64, 2, 32, 128, 128

    rng = np.random.default_rng(0)
    bf = jnp.bfloat16
    mk = lambda *shape: jnp.asarray(
        rng.standard_normal(shape).astype(np.float32) * 0.02, bf)
    blocks = {
        "ln1_w": jnp.ones((L_stage, H), bf),
        "ln1_b": jnp.zeros((L_stage, H), bf),
        "wqkv": mk(L_stage, H, 3, nh_loc, d),
        "bqkv": jnp.zeros((L_stage, 3, nh_loc, d), bf),
        "wo": mk(L_stage, nh_loc, d, H),
        "bo": jnp.zeros((L_stage, H), bf),
        "ln2_w": jnp.ones((L_stage, H), bf),
        "ln2_b": jnp.zeros((L_stage, H), bf),
        "w1": mk(L_stage, H, F_loc), "b1": jnp.zeros((L_stage, F_loc), bf),
        "w2": mk(L_stage, F_loc, H), "b2": jnp.zeros((L_stage, H), bf),
    }
    moments = {k: (jnp.zeros_like(v), jnp.zeros_like(v))
               for k, v in blocks.items()}
    eps = cfg.layer_norm_epsilon
    use_flash = not on_cpu

    def stage_fwd(bl, x):
        blk = jax.checkpoint(  # per-block remat: the 1F1B+remat config
            lambda p, xx: gpt_block(p, xx, eps, use_flash=use_flash),
            prevent_cse=False)
        out, _ = jax.lax.scan(lambda h, p: (blk(p, h), None), x, bl)
        return out

    @jax.jit
    def tick(bl, mom, x, cot):
        # the 1F1B steady-state per-tick work: one stage forward AND one
        # stage backward (vjp from the saved input), then the Adam update
        y, vjp = jax.vjp(stage_fwd, bl, x)
        db, dx = vjp(cot)
        def upd(p, g, mv):
            m, v = mv
            g32 = g.astype(jnp.float32)
            m2 = 0.9 * m.astype(jnp.float32) + 0.1 * g32
            v2 = 0.95 * v.astype(jnp.float32) + 0.05 * jnp.square(g32)
            p2 = p.astype(jnp.float32) - 1e-4 * m2 / (jnp.sqrt(v2) + 1e-8)
            return p2.astype(p.dtype), (m2.astype(m.dtype),
                                        v2.astype(v.dtype))
        new_bl, new_mom = {}, {}
        for k in bl:
            new_bl[k], new_mom[k] = upd(bl[k], db[k], mom[k])
        return y, new_bl, new_mom

    x = jnp.asarray(rng.standard_normal((mb, S, H)).astype(np.float32), bf)
    cot = jnp.ones((mb, S, H), bf)

    telemetry = _StepTelemetry()
    y, blocks, moments = tick(blocks, moments, x, cot)  # compile
    np.asarray(y[0, 0, 0])
    steps = args.steps
    step_times = []
    t0 = time.perf_counter()
    for _ in range(steps):
        t1 = time.perf_counter()
        y, blocks, moments = tick(blocks, moments, x, cot)
        step_times.append(time.perf_counter() - t1)
    np.asarray(y[0, 0, 0])
    dt = time.perf_counter() - t0

    tps = mb * S * steps / dt
    per_layer = (H * 3 * nh_loc * d) + (nh_loc * d * H) \
        + (H * F_loc) + (F_loc * H)
    n_params = L_stage * per_layer
    # 6N matmul flops (fwd 2N + bwd 4N) + remat refwd 2N = 8N, + attention
    flops_per_token = 8 * n_params + 12 * L_stage * nh_loc * d * S
    mfu = tps * flops_per_token / peak_flops_per_chip()
    emit("gpt_13b_stage_proxy_tokens_per_sec_per_chip", tps,
         "tokens/s/chip",
         {"mfu": round(mfu, 4), "params_per_chip": n_params,
          "mesh": "mp4 x pp4 slice", "layers_per_stage": L_stage,
          "micro_batch": mb, "seq": S, "steps": steps,
          "remat": "full", "dtype": "bf16 params+moments",
          "excludes": "CE head + inter-chip collectives (mid-stage)",
          **telemetry.extras(step_times, wall_s=dt)})


def bench_gpt_13b_compile(args):
    """BASELINE #4 compile-only evidence (VERDICT r4 #2b): the FULL 13B
    hybrid step (mp=4 x pp=4, 1F1B + remat, bf16 storage) lowered and
    compiled on a 16-way virtual mesh via tools/mem_probe.py; emits XLA's
    per-device memory_analysis."""
    import subprocess
    repo = os.path.dirname(os.path.abspath(__file__))
    cmd = [sys.executable, os.path.join(repo, "tools", "mem_probe.py"),
           "--config", "13b", "--mp", "4", "--pp", "4",
           "--batch", "16", "--seq", "2048", "--n-micro", "16",
           "--schedules", "1f1b", "--remat", "full",
           "--param-dtype", "bfloat16", "--moment-dtype", "bfloat16"]
    # bounded by its own subprocess timeout (the ~25-min AOT compile is
    # exempt from the per-model SIGALRM budget — see _config_budget)
    r = subprocess.run(cmd, capture_output=True, text=True, timeout=1500)
    rec = None
    for ln in r.stdout.splitlines():
        try:
            doc = json.loads(ln)
        except ValueError:
            continue
        if doc.get("schedule") == "1f1b" and "peak_hbm_gb" in doc:
            rec = doc
    if rec is None:
        raise RuntimeError(
            f"mem_probe produced no 13B record: rc={r.returncode} "
            f"stderr={r.stderr[-400:]}")
    emit("gpt_13b_hybrid_peak_hbm_gb_per_device", rec["peak_hbm_gb"],
         "GiB/device",
         {"temp_gb": rec["temp_gb"], "argument_gb": rec["argument_gb"],
          "mesh": "mp4 x pp4 (16 virtual devices)", "n_micro": 16,
          "batch": 16, "seq": 2048, "schedule": "1f1b", "remat": True,
          "dtype": "bf16 masters+moments",
          "fits_16gb_chip": bool(rec["peak_hbm_gb"] <= 15.75),
          "note": "compile-only (AOT memory_analysis); lower is better"})


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default="all",
                    choices=["all", "gpt", "resnet50", "bert", "ernie-moe",
                             "serving", "serving-fleet", "serving-overload",
                             "collectives", "13b-proxy", "13b-compile"])
    ap.add_argument("--config", default="345m",
                    choices=["tiny", "345m", "1.3b"])
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument("--warmup", type=int, default=3)
    ap.add_argument("--batch", type=int, default=0)
    ap.add_argument("--seq", type=int, default=0)
    ap.add_argument("--remat", default="dots",
                    choices=["full", "dots", "none"],
                    help="GPT block rematerialization: full checkpoint, "
                         "dots policy (save matmul outputs), or off")
    ap.add_argument("--smoke", action="store_true",
                    help="telemetry smoke run: tiny GPT, few steps — "
                         "verifies the enriched step-time p50/p95 / "
                         "peak-memory / compile-time columns end to end")
    ap.add_argument("--per-model-timeout", type=int, default=420,
                    help="SIGALRM budget (seconds) per config; a config "
                         "over budget emits a *_TIMEOUT line and the "
                         "sweep continues (0 disables)")
    args = ap.parse_args()
    sys.path.insert(0, ".")

    if args.smoke:
        args.model, args.config = "gpt", "tiny"
        args.steps = min(args.steps, 5)
        args.warmup = min(args.warmup, 1)

    devices = acquire_devices()
    single = {"resnet50": bench_resnet50, "bert": bench_bert,
              "ernie-moe": bench_ernie_moe, "gpt": bench_gpt,
              "serving": bench_serving,
              "serving-fleet": bench_serving_fleet,
              "serving-overload": bench_serving_overload,
              "collectives": bench_collective_compression,
              "13b-proxy": bench_gpt_13b_stage_proxy,
              "13b-compile": bench_gpt_13b_compile}
    global _CPU_SMOKE
    _CPU_SMOKE = devices[0].platform == "cpu"
    from paddle_tpu.utils.compile_cache import enable_compile_cache
    print(f"bench: {devices[0].platform} x{len(devices)} "
          f"({devices[0].device_kind}); compile cache "
          f"{enable_compile_cache()}", file=sys.stderr, flush=True)

    # sweep-consistent metric names for single-model mode, so a timeout
    # line parses the same either way
    single_names = {"resnet50": "resnet50", "bert": "bert",
                    "ernie-moe": "ernie_moe", "serving": "serving",
                    "serving-fleet": "serving_fleet",
                    "serving-overload": "serving_overload",
                    "collectives": "collective_compression",
                    "13b-proxy": "gpt_13b_stage_proxy",
                    "13b-compile": "gpt_13b_compile"}

    def _config_budget(name):
        """Per-config SIGALRM budget: the 13B AOT compile legitimately
        runs ~25 min and is already bounded by its own subprocess
        timeout (1500s), so it is exempt from the default budget."""
        if name == "gpt_13b_compile" and args.per_model_timeout:
            return max(args.per_model_timeout, 1600)
        return args.per_model_timeout

    failed = []  # configs that raised or timed out: the exit code
    if args.model in single:
        name = (f"gpt_{args.config.replace('.', 'p')}"
                if args.model == "gpt" else single_names[args.model])
        # a raise goes straight through to a traceback and a non-zero exit
        run_with_timeout(name, lambda: single[args.model](args),
                         _config_budget(name), failed)
        if _CPU_SMOKE:
            # every TPU config this CPU round skipped still gets an
            # artifact-backed *_predicted row from the static cost model
            emit_predicted_rows()
        return 1 if failed else 0

    # default: ALL BASELINE configs, one JSON line each; a failing config
    # reports an error line and the rest still run. The driver records
    # only the output TAIL, which truncation eats from the FRONT — so
    # the headline GPT-345M goes LAST (a truncated capture still has it,
    # and last-line parsers see it); the bounded-by-timeout 13B compile
    # probe sits just before it.
    on_cpu = _CPU_SMOKE
    if on_cpu:
        # artifact-backed stand-ins for the TPU-only configs, FIRST: the
        # driver keeps the output tail, truncation eats from the front
        emit_predicted_rows()
    runs = [("resnet50", lambda: bench_resnet50(args)),
            ("bert", lambda: bench_bert(args)),
            ("ernie_moe", lambda: bench_ernie_moe(args))]
    if on_cpu:
        emit_skip("gpt_1p3b", "CPU backend: 1.3B needs the 16GB TPU chip")
    else:
        runs.append(("gpt_1p3b", lambda: bench_gpt(args, "1.3b")))
    runs.append(("gpt_13b_stage_proxy",
                 lambda: bench_gpt_13b_stage_proxy(args)))
    runs.append(("collective_compression",
                 lambda: bench_collective_compression(args)))
    runs.append(("serving", lambda: bench_serving(args)))
    runs.append(("serving_fleet", lambda: bench_serving_fleet(args)))
    runs.append(("serving_overload",
                 lambda: bench_serving_overload(args)))
    if on_cpu:
        emit_skip("gpt_13b_hybrid_peak_hbm",
                  "CPU smoke run: skipping the 25-min 13B AOT compile")
    else:
        runs.append(("gpt_13b_compile", lambda: bench_gpt_13b_compile(args)))
    runs.append(("gpt_345m", lambda: bench_gpt(args, "345m")))
    for name, fn in runs:
        try:
            run_with_timeout(name, fn, _config_budget(name), failed)
        except Exception as e:  # keep the rest of the sweep alive
            traceback.print_exc(file=sys.stderr)
            print(json.dumps({"metric": f"{name}_ERROR",
                              "value": 0.0, "unit": "error",
                              "vs_baseline": 0.0,
                              "extras": {"error": repr(e)[:300]}}),
                  flush=True)
            failed.append(name)
    if failed:
        print(f"bench: failed configs: {failed}", file=sys.stderr,
              flush=True)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
