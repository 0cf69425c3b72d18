"""``serving_predicted``: static cost-model row for the serving engine.

A TPU-less bench round still owes serving numbers (ROADMAP: every perf
claim lands in the artifact, measured or ``*_predicted``). This module
traces the engine's REAL decode step (:func:`..serving.engine.
decode_step_fn`, XLA-reference attention path so every op is modelable)
to a jaxpr — abstract shapes only, no weights materialized, no device —
and prices it with the PR-5 roofline cost model
(:func:`paddle_tpu.analysis.passes.cost.estimate_jaxpr_cost`).

Decode is one token per live stream per step, so

- ``predicted_tokens_per_sec``   = concurrency / step_time,
- per-token latency p50 = p95   = step_time (the decode loop is a
  fixed-shape program; the static model has no jitter term — measured
  rows carry the real spread).

CLI (bench.py shells out here so a wedged backend can't take the row
down with it)::

    python -m paddle_tpu.serving.predict --config 345m --concurrency 8

These rows are also the objective of the serving-side plan search:
``distributed.auto_parallel.plan_serving`` (``tools/plan.py
--serving``) sweeps (decode-batch bucket, page size, ``quantize=``)
over :func:`predicted_serving_row` under the chip HBM budget and
returns the ranked, feasible configurations.
"""
from __future__ import annotations

import argparse
import functools
import json
import math
import sys

__all__ = ["predicted_serving_row", "predicted_shared_prefix_row",
           "predicted_disagg_row", "predicted_fused_dispatch_row",
           "predicted_fleet_row"]


def _gpt_config(config: str):
    from ..models.gpt import (gpt_13b_config, gpt_1p3b_config,
                              gpt_345m_config, gpt_tiny_config)
    cfgs = {
        "tiny": lambda: gpt_tiny_config(),
        # the bench's TPU-native 345M shape (d_head=128)
        "345m": lambda: gpt_345m_config(max_position_embeddings=1024,
                                        num_heads=8),
        "1.3b": lambda: gpt_1p3b_config(),
        "13b": lambda: gpt_13b_config(),
    }
    return cfgs[config]()


def _params_avals(cfg, dtype, quantize):
    """Abstract stacked-GPT weight pytree (quantized form — int8 q +
    f32 per-channel scales, exactly what
    ``quantize_stacked_gpt_weights`` emits — when ``quantize="int8"``),
    so the cost model prices the real decode/prefill programs."""
    import jax
    import jax.numpy as jnp
    L, H, nh, d = (cfg.num_layers, cfg.hidden_size, cfg.num_heads,
                   cfg.head_dim)
    V, F = cfg.vocab_size, cfg.intermediate_size
    wdt = jnp.dtype(dtype)
    sds = jax.ShapeDtypeStruct
    i8, f32 = jnp.int8, jnp.float32

    def w(shape, s_shape=None):
        if quantize == "int8" and s_shape is not None:
            return {"q": sds(shape, i8), "s": sds(s_shape, f32)}
        return sds(shape, wdt)

    return {
        "blocks": {
            "ln1_w": sds((L, H), wdt), "ln1_b": sds((L, H), wdt),
            "wqkv": w((L, H, 3, nh, d), (L, 3, nh, d)),
            "bqkv": sds((L, 3, nh, d), wdt),
            "wo": w((L, nh, d, H), (L, H)), "bo": sds((L, H), wdt),
            "ln2_w": sds((L, H), wdt), "ln2_b": sds((L, H), wdt),
            "w1": w((L, H, F), (L, F)), "b1": sds((L, F), wdt),
            "w2": w((L, F, H), (L, H)), "b2": sds((L, H), wdt),
        },
        "wte": w((V, H), (V,)),
        "wpe": w((cfg.max_position_embeddings, H),
                 (cfg.max_position_embeddings,)),
        "lnf_w": sds((H,), wdt), "lnf_b": sds((H,), wdt),
    }


def predicted_serving_row(config: str = "345m", concurrency: int = 8,
                          page_size: int = 64, chip: str = "v5e",
                          dtype: str = "bfloat16",
                          quantize: str | None = None) -> dict:
    import jax
    import jax.numpy as jnp
    from ..analysis.passes.cost import estimate_jaxpr_cost, site_rows
    from ..observability.instrument import chip_specs
    from .engine import decode_step_fn

    cfg = _gpt_config(config)
    L, nh, d = cfg.num_layers, cfg.num_heads, cfg.head_dim
    B = int(concurrency)
    ps = int(page_size)
    pages_per_seq = math.ceil(cfg.max_position_embeddings / ps)
    num_pages = B * pages_per_seq + 1
    wdt = jnp.dtype(dtype)
    sds = jax.ShapeDtypeStruct
    params = _params_avals(cfg, dtype, quantize)
    kp = sds((L, num_pages, ps, nh, d), wdt)
    i32 = jnp.int32
    fn = functools.partial(decode_step_fn, eps=cfg.layer_norm_epsilon,
                           temperature=0.0, top_k=0, use_kernel=False,
                           compute_dtype=dtype)
    closed = jax.make_jaxpr(fn)(
        params, kp, kp, sds((B,), i32), sds((B,), i32),
        sds((B, pages_per_seq), i32), sds((B,), i32), None)
    spec = chip_specs(chip)
    cost = estimate_jaxpr_cost(closed, chip=spec)
    step_s = cost.step_ms / 1e3
    itemsize = jnp.zeros((), wdt).dtype.itemsize
    pool_bytes = 2 * L * num_pages * ps * nh * d * itemsize

    def _aval_bytes(t):
        import numpy as _np
        return int(_np.prod(t.shape, dtype=_np.int64)
                   * _np.dtype(t.dtype).itemsize)
    weight_bytes = sum(_aval_bytes(t)
                       for t in jax.tree_util.tree_leaves(params))
    # decode-tick time by op family (per-site predicted roofline times,
    # rolled up) — the doctor splits its decode residual bucket along
    # these shares when no measured decode attribution exists
    family_ms: dict[str, float] = {}
    for r in site_rows(cost):
        family_ms[r["family"]] = round(
            family_ms.get(r["family"], 0.0) + r["predicted_ms"], 6)
    return {
        "config": config,
        "concurrency": B,
        "page_size": ps,
        "pages_per_seq": pages_per_seq,
        "dtype": dtype,
        "quantize": quantize,
        "weights_mb": round(weight_bytes / 2 ** 20, 1),
        "predicted_decode_step_ms": round(cost.step_ms, 3),
        "predicted_tokens_per_sec": round(B / step_s, 1) if step_s else 0.0,
        "predicted_per_token_ms_p50": round(cost.step_ms, 3),
        "predicted_per_token_ms_p95": round(cost.step_ms, 3),
        "predicted_bound": cost.bound,
        "predicted_decode_family_ms": family_ms,
        "kv_pool_mb": round(pool_bytes / 2 ** 20, 1),
        "chip_assumed": spec.get("name"),
        "calibration_id": spec.get("calibration_id", "default"),
    }


def _chunk_step_ms(cfg, dtype, quantize, chunk, pages_per_seq, num_pages,
                   page_size, spec):
    """Roofline cost of ONE chunk-program invocation (the real
    :func:`..serving.engine.chunk_prefill_fn` jaxpr — the program both
    chunked prefill and prefix-cache suffix prefill run)."""
    import functools
    import jax
    import jax.numpy as jnp
    from ..analysis.passes.cost import estimate_jaxpr_cost
    from .engine import chunk_prefill_fn

    sds = jax.ShapeDtypeStruct
    i32 = jnp.int32
    L, nh, d = cfg.num_layers, cfg.num_heads, cfg.head_dim
    params = _params_avals(cfg, dtype, quantize)
    kp = sds((L, num_pages, page_size, nh, d), jnp.dtype(dtype))
    fn = functools.partial(chunk_prefill_fn, eps=cfg.layer_norm_epsilon,
                           temperature=0.0, top_k=0, compute_dtype=dtype)
    closed = jax.make_jaxpr(fn)(
        params, kp, kp, sds((1, chunk), i32), sds((), i32),
        sds((), i32), sds((1, pages_per_seq), i32),
        sds((chunk,), i32), None)
    return estimate_jaxpr_cost(closed, chip=spec).step_ms


def predicted_shared_prefix_row(config: str = "345m",
                                concurrency: int = 8,
                                prompt_len: int = 1024,
                                shared_fraction: float = 0.75,
                                max_new: int = 64,
                                prefill_chunk: int = 256,
                                page_size: int = 64, chip: str = "v5e",
                                dtype: str = "bfloat16") -> dict:
    """``serving_shared_prefix_predicted``: the static shared-prefix
    serving anchor. N concurrent requests share ``shared_fraction`` of
    a ``prompt_len`` prompt; the cache-hit engine prefills only the
    suffix (chunk program invocations over ``prompt_len - cached``
    tokens) while the baseline prefills everything. Workload makespan =
    serialized prefills (one prefill lane — the scheduler's budget
    ticks) + the batched decode tail, so the row's VALUE is predicted
    end-to-end goodput tokens/s WITH the cache; the baseline and the
    TTFT split ride in the extras. Zero device work, zero noise —
    ``tools/bench_compare.py`` anchors the measured row on it."""
    from ..observability.instrument import chip_specs
    cfg = _gpt_config(config)
    B = int(concurrency)
    ps = int(page_size)
    chunk = max(int(prefill_chunk) // ps, 1) * ps
    pages_per_seq = math.ceil(cfg.max_position_embeddings / ps)
    num_pages = B * pages_per_seq + 1
    spec = chip_specs(chip)
    cached = int(min(max(shared_fraction, 0.0), 1.0) * prompt_len)
    cached = min(cached, prompt_len - 1)
    suffix = prompt_len - cached
    chunk_ms = _chunk_step_ms(cfg, dtype, None, chunk, pages_per_seq,
                              num_pages, ps, spec)
    decode = predicted_serving_row(config, concurrency, page_size, chip,
                                   dtype)
    step_ms = decode["predicted_decode_step_ms"]
    chunks_hit = math.ceil(suffix / chunk)
    chunks_miss = math.ceil(prompt_len / chunk)
    # first request is always a miss (it fills the cache); the rest hit
    prefill_hit_ms = chunks_hit * chunk_ms
    prefill_miss_ms = chunks_miss * chunk_ms
    total_prefill_ms = prefill_miss_ms + (B - 1) * prefill_hit_ms
    base_prefill_ms = B * prefill_miss_ms
    decode_ms = max_new * step_ms
    makespan_ms = total_prefill_ms + decode_ms
    base_makespan_ms = base_prefill_ms + decode_ms
    tok = B * max_new

    def tps(ms):
        return round(tok / (ms / 1e3), 1) if ms else 0.0

    return {
        "config": config,
        "concurrency": B,
        "prompt_len": int(prompt_len),
        "shared_fraction": round(shared_fraction, 4),
        "cached_prefix_len": cached,
        "prefill_chunk": chunk,
        "page_size": ps,
        "dtype": dtype,
        "predicted_tokens_per_sec": tps(makespan_ms),
        "predicted_tokens_per_sec_no_cache": tps(base_makespan_ms),
        "predicted_goodput_speedup": round(
            base_makespan_ms / makespan_ms, 3) if makespan_ms else 0.0,
        "predicted_ttft_ms_hit": round(prefill_hit_ms, 3),
        "predicted_ttft_ms_miss": round(prefill_miss_ms, 3),
        "predicted_ttft_speedup": round(
            prefill_miss_ms / prefill_hit_ms, 3) if prefill_hit_ms
        else 0.0,
        "predicted_chunk_ms": round(chunk_ms, 3),
        "predicted_decode_step_ms": step_ms,
        "predicted_tokens_reused": (B - 1) * cached,
        "chip_assumed": spec.get("name"),
    }


def predicted_disagg_row(config: str = "345m", concurrency: int = 8,
                         prompt_len: int = 1024, page_size: int = 64,
                         chip: str = "v5e",
                         dtype: str = "bfloat16") -> dict:
    """``serving_disagg_predicted``: price the disaggregated split —
    prefill program (the real :func:`..serving.engine.prefill_kv_fn`
    jaxpr) on the prefill mesh, dense-KV handoff over ICI, decode step
    on the decode mesh. TTFT = prefill + transfer; decode throughput is
    the decode mesh's alone (prefill no longer steals its ticks)."""
    import functools
    import jax
    import jax.numpy as jnp
    from ..analysis.passes.cost import estimate_jaxpr_cost
    from ..observability.instrument import chip_specs
    from .engine import prefill_kv_fn

    cfg = _gpt_config(config)
    L, nh, d = cfg.num_layers, cfg.num_heads, cfg.head_dim
    sds = jax.ShapeDtypeStruct
    i32 = jnp.int32
    spec = chip_specs(chip)
    wdt = jnp.dtype(dtype)
    # bucketize the prompt the way default_prefill_buckets would
    sb = int(page_size)
    while sb < prompt_len:
        sb *= 2
    sb = min(sb, cfg.max_position_embeddings)
    params = _params_avals(cfg, dtype, None)
    fn = functools.partial(prefill_kv_fn, eps=cfg.layer_norm_epsilon,
                           temperature=0.0, top_k=0, use_flash=False,
                           compute_dtype=dtype)
    closed = jax.make_jaxpr(fn)(params, sds((1, sb), i32),
                                sds((), i32), None)
    prefill_ms = estimate_jaxpr_cost(closed, chip=spec).step_ms
    itemsize = jnp.zeros((), wdt).dtype.itemsize
    kv_bytes = 2 * L * prompt_len * nh * d * itemsize
    transfer_ms = 1e3 * kv_bytes / spec["ici_bw"]
    decode = predicted_serving_row(config, concurrency, page_size, chip,
                                   dtype)
    return {
        "config": config,
        "concurrency": int(concurrency),
        "prompt_len": int(prompt_len),
        "prefill_bucket": sb,
        "dtype": dtype,
        "predicted_tokens_per_sec": decode["predicted_tokens_per_sec"],
        "predicted_prefill_ms": round(prefill_ms, 3),
        "predicted_kv_transfer_mb": round(kv_bytes / 2 ** 20, 2),
        "predicted_kv_transfer_ms": round(transfer_ms, 3),
        "predicted_ttft_ms": round(prefill_ms + transfer_ms, 3),
        "predicted_decode_step_ms": decode["predicted_decode_step_ms"],
        "predicted_transfer_share_of_ttft": round(
            transfer_ms / (prefill_ms + transfer_ms), 4)
        if prefill_ms + transfer_ms else 0.0,
        "chip_assumed": spec.get("name"),
    }


def predicted_fleet_row(config: str = "345m", replicas: int = 2,
                        n_requests: int = 16, concurrency: int = 8,
                        prompt_len: int = 1024,
                        shared_fraction: float = 0.75, max_new: int = 64,
                        prefill_chunk: int = 256, page_size: int = 64,
                        chip: str = "v5e", dtype: str = "bfloat16",
                        router_overhead_ms: float = 0.2) -> dict:
    """``serving_fleet_predicted``: the fleet-level static anchor —
    per-replica roofline × N minus router overhead, with a hit-rate-
    split TTFT model.

    Workload model: ``n_requests`` requests in N same-prefix groups
    (one group per replica — the shape prefix-affinity routing
    produces), each prompt ``prompt_len`` tokens sharing
    ``shared_fraction`` with its group. Per replica the makespan is
    serialized prefills (cache-miss chunks for the group's FIRST
    request, cache-hit suffix chunks for the rest, plus
    ``router_overhead_ms`` of routing/RPC per request) followed by the
    batched decode tail; replicas run in parallel, so fleet goodput =
    total new tokens / the per-replica makespan. The same model under
    ROUND-ROBIN routing (every group smeared across all replicas →
    ``min(N, per-replica requests)`` compulsory misses each) is the
    in-row baseline: the value the affinity policy must beat, computed
    from the same roofline so the comparison is noise-free."""
    from ..observability.instrument import chip_specs

    cfg = _gpt_config(config)
    N = max(int(replicas), 1)
    M = max(int(n_requests), N)
    B = int(concurrency)
    ps = int(page_size)
    chunk = max(int(prefill_chunk) // ps, 1) * ps
    pages_per_seq = math.ceil(cfg.max_position_embeddings / ps)
    num_pages = B * pages_per_seq + 1
    spec = chip_specs(chip)
    cached = int(min(max(shared_fraction, 0.0), 1.0) * prompt_len)
    cached = min(cached, prompt_len - 1)
    suffix = prompt_len - cached
    chunk_ms = _chunk_step_ms(cfg, dtype, None, chunk, pages_per_seq,
                              num_pages, ps, spec)
    decode = predicted_serving_row(config, concurrency, page_size, chip,
                                   dtype)
    step_ms = decode["predicted_decode_step_ms"]
    hit_ms = math.ceil(suffix / chunk) * chunk_ms
    miss_ms = math.ceil(prompt_len / chunk) * chunk_ms
    per_replica = math.ceil(M / N)
    tok = M * max_new

    def makespan(n_miss, n_req):
        n_miss = min(n_miss, n_req)
        prefill = (n_miss * miss_ms + (n_req - n_miss) * hit_ms
                   + n_req * float(router_overhead_ms))
        # decode runs at most B streams at once: requests beyond the
        # widest decode bucket take extra batched rounds
        decode = math.ceil(n_req / B) * max_new * step_ms
        return prefill + decode

    ms_aff = makespan(1, per_replica)     # affinity: one group, one miss
    ms_rr = makespan(min(N, per_replica),  # round-robin: N groups each
                     per_replica)
    # the scaling baseline: the SAME router with one replica behind it
    # (like-for-like — router overhead on both sides of the ratio)
    ms_single = makespan(1, M)

    def tps(ms):
        return round(tok / (ms / 1e3), 1) if ms else 0.0

    fleet_tps = tps(ms_aff)
    single_tps = tps(ms_single)
    hit_rate_aff = (per_replica - 1) / per_replica if per_replica else 0.0
    n_miss_rr = min(N, per_replica)
    hit_rate_rr = (per_replica - n_miss_rr) / per_replica \
        if per_replica else 0.0
    return {
        "config": config,
        "replicas": N,
        "n_requests": M,
        "concurrency": B,
        "prompt_len": int(prompt_len),
        "shared_fraction": round(shared_fraction, 4),
        "prefill_chunk": chunk,
        "page_size": ps,
        "dtype": dtype,
        "router_overhead_ms": float(router_overhead_ms),
        "predicted_tokens_per_sec": fleet_tps,
        "predicted_tokens_per_sec_round_robin": tps(ms_rr),
        "predicted_affinity_speedup_vs_round_robin": round(
            ms_rr / ms_aff, 3) if ms_aff else 0.0,
        "predicted_tokens_per_sec_single_replica": single_tps,
        "predicted_scaling_efficiency": round(
            fleet_tps / (N * single_tps), 4) if single_tps else 0.0,
        "predicted_prefix_hit_rate": round(hit_rate_aff, 4),
        "predicted_prefix_hit_rate_round_robin": round(hit_rate_rr, 4),
        # hit-rate-split TTFT: what an affinity-routed request sees vs
        # a compulsory miss (router overhead included in both)
        "predicted_ttft_ms_hit": round(
            hit_ms + float(router_overhead_ms), 3),
        "predicted_ttft_ms_miss": round(
            miss_ms + float(router_overhead_ms), 3),
        "predicted_ttft_ms_mean": round(
            hit_rate_aff * hit_ms + (1 - hit_rate_aff) * miss_ms
            + float(router_overhead_ms), 3),
        "predicted_ttft_ms_mean_round_robin": round(
            hit_rate_rr * hit_ms + (1 - hit_rate_rr) * miss_ms
            + float(router_overhead_ms), 3),
        "predicted_decode_step_ms": step_ms,
        "predicted_chunk_ms": round(chunk_ms, 3),
        "chip_assumed": spec.get("name"),
    }


def predicted_overload_row(config: str = "345m", concurrency: int = 8,
                           prompt_len: int = 1024, max_new: int = 64,
                           prefill_chunk: int = 256, page_size: int = 64,
                           chip: str = "v5e", dtype: str = "bfloat16",
                           overload_factor: float = 2.0,
                           deadline_s: float | None = None,
                           window_s: float = 60.0) -> dict:
    """``serving_overload_predicted``: the overload-control static
    anchor — deadline-met goodput at ``overload_factor``× the engine's
    admission capacity, WITH the control layer (deadlines + cost-aware
    admission + brownout) vs the uncontrolled FIFO baseline, from the
    same roofline both sides share so the ratio is noise-free.

    Workload model: requests (``prompt_len`` prompt, ``max_new`` new
    tokens, each carrying ``deadline_s`` — default 4× the unloaded
    request latency) arrive at rate λ = f × capacity for ``window_s``
    seconds, where capacity is the pipeline's bottleneck stage rate
    (serialized chunk prefills vs the B-wide batched decode).

    WITHOUT control the FIFO queue grows at (f−1)·capacity, so a
    request arriving at time t waits (f−1)·t: only arrivals before
    t* = deadline/(f−1) finish inside their deadline, goodput collapses
    as the window grows, and p99 TTFT tracks the window length — queue
    wait IS the tail. WITH control, admission sheds the excess with a
    priced ``retry_after`` (reject fraction 1−1/f), the brownout clamp
    keeps admitted work inside the token budget, and the deadline sweep
    bounds wasted decode: goodput holds at ~capacity minus a small
    control overhead and p99 TTFT is bounded by the deadline.
    ``predicted_goodput_ratio`` (control / no-control) is the
    acceptance number the measured ``serving_overload`` row must echo
    (≥ 1)."""
    from ..observability.instrument import chip_specs

    cfg = _gpt_config(config)
    B = int(concurrency)
    ps = int(page_size)
    chunk = max(int(prefill_chunk) // ps, 1) * ps
    pages_per_seq = math.ceil(cfg.max_position_embeddings / ps)
    num_pages = B * pages_per_seq + 1
    spec = chip_specs(chip)
    chunk_ms = _chunk_step_ms(cfg, dtype, None, chunk, pages_per_seq,
                              num_pages, ps, spec)
    decode = predicted_serving_row(config, concurrency, page_size, chip,
                                   dtype)
    step_ms = decode["predicted_decode_step_ms"]
    f = max(float(overload_factor), 1.0 + 1e-9)
    T = max(float(window_s), 1.0)
    prefill_ms = math.ceil(prompt_len / chunk) * chunk_ms
    req_ms = prefill_ms + max_new * step_ms        # unloaded latency
    # capacity = the slower pipeline stage: one serialized prefill lane
    # vs B decode streams each holding a slot for max_new steps
    cap_rps = 1e3 * min(1.0 / prefill_ms, B / (max_new * step_ms))
    cap_tps = cap_rps * max_new
    lam = f * cap_rps
    dl = float(deadline_s) if deadline_s else 4.0 * req_ms / 1e3
    # ---- no control: FIFO backlog grows at (f-1)*cap; arrival at t
    # waits (f-1)*t, so the met set is the arrivals before t*
    t_star = dl / (f - 1.0)
    met_frac_nc = min(t_star, T) / T
    goodput_nc_tps = min(lam * met_frac_nc * max_new, cap_tps)
    miss_nc = 1.0 - met_frac_nc
    p99_ttft_nc_ms = (f - 1.0) * 0.99 * T * 1e3 + prefill_ms
    # ---- with control: admission keeps queue wait under the deadline
    # and sheds the rest; brownout/cancel bookkeeping is a small tax
    ctrl_overhead = 0.02
    goodput_c_tps = cap_tps * (1.0 - ctrl_overhead)
    reject_frac = 1.0 - 1.0 / f
    miss_c = 0.01           # boundary admissions the deadline sweep eats
    p99_ttft_c_ms = min(p99_ttft_nc_ms,
                        max(prefill_ms, dl * 1e3 - max_new * step_ms))
    return {
        "config": config,
        "concurrency": B,
        "prompt_len": int(prompt_len),
        "max_new": int(max_new),
        "page_size": ps,
        "dtype": dtype,
        "overload_factor": round(f, 2),
        "window_s": round(T, 1),
        "deadline_s": round(dl, 4),
        "capacity_rps": round(cap_rps, 3),
        "capacity_tokens_per_sec": round(cap_tps, 1),
        # headline value: deadline-met goodput WITH the control layer
        "predicted_tokens_per_sec": round(goodput_c_tps, 1),
        "predicted_goodput_tokens_per_sec_no_control": round(
            goodput_nc_tps, 1),
        "predicted_goodput_ratio": round(
            goodput_c_tps / goodput_nc_tps, 3) if goodput_nc_tps else 0.0,
        "predicted_deadline_miss_rate": round(miss_c, 4),
        "predicted_deadline_miss_rate_no_control": round(miss_nc, 4),
        "predicted_reject_fraction": round(reject_frac, 4),
        "predicted_p99_ttft_ms": round(p99_ttft_c_ms, 3),
        "predicted_p99_ttft_ms_no_control": round(p99_ttft_nc_ms, 3),
        # sustained f x capacity keeps the burn above threshold for the
        # overloaded share of the window
        "predicted_brownout_share": round(1.0 - 1.0 / f, 4),
        # steady-state backlog at the admission cap drains in about one
        # deadline — the hint a priced reject carries
        "predicted_retry_after_s": round(dl, 3),
        "predicted_decode_step_ms": step_ms,
        "predicted_chunk_ms": round(chunk_ms, 3),
        "predicted_request_ms_unloaded": round(req_ms, 3),
        "chip_assumed": spec.get("name"),
        "calibration_id": decode.get("calibration_id", "default"),
    }


def predicted_migration_row(config: str = "345m", prompt_len: int = 1024,
                            decoded: int = 32,
                            cached_fraction: float = 0.5,
                            prefill_chunk: int = 256,
                            page_size: int = 64, chip: str = "v5e",
                            dtype: str = "bfloat16") -> dict:
    """``serving_fleet_migration_predicted``: the live-migration static
    anchor — KV-page payload bytes over the interconnect roofline plus
    resume cost, against the full-prompt replay a plain requeue pays.

    Workload model: one request mid-decode (``prompt_len`` prompt +
    ``decoded`` generated tokens of valid KV) moves replicas. The
    destination's radix cache already holds a page-aligned
    ``cached_fraction`` of the prompt, so only the uncached suffix
    rows travel: gather from the source pool (HBM), stream over the
    interconnect (ICI; a cross-host DCN figure rides along at the
    documented ici_bw/8 assumption — ``chip_specs`` carries no DCN
    number), scatter into the destination pool (HBM), one decode step
    to resume. The baseline is SIGKILL-style failover with a COLD
    destination cache: re-prefill the full sequence through the chunk
    program. ``predicted_speedup`` is replay/migration — the factor
    the robustness machinery is predicted to save per moved request."""
    import jax.numpy as jnp
    from ..observability.instrument import chip_specs

    cfg = _gpt_config(config)
    L, nh, d = cfg.num_layers, cfg.num_heads, cfg.head_dim
    ps = int(page_size)
    chunk = max(int(prefill_chunk) // ps, 1) * ps
    seq_len = int(prompt_len) + max(int(decoded), 1)
    # destination reuse is page-granular (full pages only, capped so at
    # least one KV row always transfers — PrefixCache.match caps at
    # prompt_len - 1)
    cached = int(min(max(cached_fraction, 0.0), 1.0) * prompt_len)
    cached = min(cached, prompt_len - 1) // ps * ps
    payload_tokens = seq_len - cached
    spec = chip_specs(chip)
    itemsize = jnp.zeros((), jnp.dtype(dtype)).dtype.itemsize
    kv_bytes = 2 * L * payload_tokens * nh * d * itemsize
    full_bytes = 2 * L * seq_len * nh * d * itemsize
    gather_ms = 1e3 * kv_bytes / spec["hbm_bw"]     # source pool read
    scatter_ms = 1e3 * kv_bytes / spec["hbm_bw"]    # dest pool write
    transfer_ici_ms = 1e3 * kv_bytes / spec["ici_bw"]
    dcn_bw = spec["ici_bw"] / 8.0
    transfer_dcn_ms = 1e3 * kv_bytes / dcn_bw
    pages_per_seq = math.ceil(cfg.max_position_embeddings / ps)
    num_pages = 8 * pages_per_seq + 1
    chunk_ms = _chunk_step_ms(cfg, dtype, None, chunk, pages_per_seq,
                              num_pages, ps, spec)
    decode = predicted_serving_row(config, 8, page_size, chip, dtype)
    step_ms = decode["predicted_decode_step_ms"]
    migrate_ms = gather_ms + transfer_ici_ms + scatter_ms + step_ms
    migrate_dcn_ms = gather_ms + transfer_dcn_ms + scatter_ms + step_ms
    # plain-requeue baseline: chunked prefill of the FULL sequence on a
    # cold cache, then the same resume step
    replay_ms = math.ceil(seq_len / chunk) * chunk_ms + step_ms
    return {
        "config": config,
        "prompt_len": int(prompt_len),
        "decoded": int(decoded),
        "seq_len": seq_len,
        "cached_fraction": round(cached_fraction, 4),
        "cached_prefix_len": cached,
        "payload_tokens": payload_tokens,
        "page_size": ps,
        "prefill_chunk": chunk,
        "dtype": dtype,
        "predicted_payload_mb": round(kv_bytes / 2 ** 20, 2),
        "predicted_full_kv_mb": round(full_bytes / 2 ** 20, 2),
        "predicted_gather_ms": round(gather_ms, 3),
        "predicted_scatter_ms": round(scatter_ms, 3),
        "predicted_transfer_ms_ici": round(transfer_ici_ms, 3),
        "predicted_transfer_ms_dcn": round(transfer_dcn_ms, 3),
        "dcn_bw_assumption": "ici_bw/8",
        "predicted_migration_ms": round(migrate_ms, 3),
        "predicted_migration_ms_dcn": round(migrate_dcn_ms, 3),
        "predicted_replay_ms": round(replay_ms, 3),
        "predicted_speedup": round(replay_ms / migrate_ms, 3)
        if migrate_ms else 0.0,
        "predicted_speedup_dcn": round(replay_ms / migrate_dcn_ms, 3)
        if migrate_dcn_ms else 0.0,
        "predicted_decode_step_ms": step_ms,
        "predicted_chunk_ms": round(chunk_ms, 3),
        "chip_assumed": spec.get("name"),
    }


def predicted_fused_dispatch_row(tokens: int = 8192, d_model: int = 1024,
                                 num_expert: int = 64, top_k: int = 2,
                                 capacity_factor: float = 1.2,
                                 chip: str = "v5e") -> dict:
    """``moe_fused_dispatch_predicted``: the dispatch+combine STAGE
    priced fused vs unfused — the gate→scatter→combine chain alone (the
    part the Pallas kernels fuse; the expert FFN is identical on both
    paths and would only dilute the ratio). The unfused chain is
    memory-bound on its gather/scatter glue; the fused kernels stream
    tokens in + expert buffers out once. The row's VALUE is the
    predicted stage step-time speedup (>= 1 is the acceptance bar the
    bench artifact carries)."""
    import functools
    import jax
    import jax.numpy as jnp
    from ..analysis.passes.cost import (_moe_fusion_opportunities,
                                        estimate_jaxpr_cost)
    from ..observability.instrument import chip_specs
    from ..kernels.moe_dispatch import (fused_moe_combine,
                                        fused_moe_dispatch,
                                        reference_moe_combine,
                                        reference_moe_dispatch)

    S, M, E, K = int(tokens), int(d_model), int(num_expert), int(top_k)
    C = max(int(capacity_factor * K * S / E), 1)
    sds = jax.ShapeDtypeStruct
    f32 = jnp.float32
    spec = chip_specs(chip)
    avals = (sds((S, M), f32), sds((M, E), f32), sds((E,), f32),
             sds((E * C, M), f32))

    def stage(dispatch, combine):
        def run(x, gw, gb, eo):
            ei, comb, val, _, _ = dispatch(
                x, gw, gb, num_expert=E, capacity=C, top_k=K,
                gate_kind="renorm")
            return ei, combine(eo, val, comb)
        return jax.make_jaxpr(run)(*avals)

    ju = stage(reference_moe_dispatch, reference_moe_combine)
    jf = stage(fused_moe_dispatch, fused_moe_combine)
    cu = estimate_jaxpr_cost(ju, chip=spec)
    cf = estimate_jaxpr_cost(jf, chip=spec)
    fires = _moe_fusion_opportunities(ju.jaxpr)
    clean = _moe_fusion_opportunities(jf.jaxpr)
    return {
        "tokens": S, "d_model": M, "num_experts": E, "top_k": K,
        "capacity": C,
        "predicted_speedup": round(cu.step_ms / cf.step_ms, 3)
        if cf.step_ms else 0.0,
        "predicted_stage_ms_unfused": round(cu.step_ms, 4),
        "predicted_stage_ms_fused": round(cf.step_ms, 4),
        "hbm_mb_unfused": round(cu.hbm_bytes / 2 ** 20, 1),
        "hbm_mb_fused": round(cf.hbm_bytes / 2 ** 20, 1),
        "bound_unfused": cu.bound, "bound_fused": cf.bound,
        # the PTCS004 contract, verified on the very jaxprs priced here:
        # the diagnostic fires on the unfused chain, stays silent on the
        # fused kernels
        "ptcs004_fires_unfused": bool(fires),
        "ptcs004_clean_fused": not clean,
        "chip_assumed": spec.get("name"),
    }


def predicted_autofusion_row(export_path: str | None = None) -> dict:
    """``autofusion_predicted``: per-site predicted Δstep-ms of every
    auto-fusion rewrite that fires on the tiny serving engines' REAL
    traced programs — :mod:`paddle_tpu.analysis.rewrite` over the GPT
    int8 chunked-prefill engine (``ragged_prefill`` +
    ``int8_dequant_matmul``) and the gather-based MoE gate and dispatch
    of ``kernels.moe_dispatch`` (``moe_gate_dispatch``). Trace +
    interpret-mode parity only, so a TPU-less round still carries the
    anchor; future measured fused rows
    anchor on these per-rule predictions via bench_compare.
    ``export_path`` additionally writes the raw match records
    (``autofusion.json``) for the perf doctor."""
    import numpy as np
    import paddle_tpu as paddle
    from ..analysis import rewrite
    from ..kernels.moe_dispatch import reference_moe_dispatch
    from ..models.gpt import GPTForPretraining, GPTModel, gpt_tiny_config
    from .engine import ServingEngine

    rewrite.reset_records()
    paddle.seed(0)
    rng = np.random.default_rng(0)

    cfg = gpt_tiny_config()
    # use_kernel=False: the XLA chunk program, whose dense page gather
    # is what the ragged_prefill rule prices and rewrites (the kernel
    # path calls the ragged kernel itself)
    eng = ServingEngine(GPTForPretraining(GPTModel(cfg)), cfg,
                        page_size=8, decode_buckets=(1, 2), aot=False,
                        prefill_chunk=16, quantize="int8", autofuse=True,
                        use_kernel=False)
    eng.prefill("a", rng.integers(0, cfg.vocab_size,
                                  (23,)).astype(np.int32))
    eng.pool.extend("a")
    eng.decode(["a"])

    # the gather-based gate -> dispatch chain, as an unfused MoE layer
    # traces it (S=64 tokens of 32, 8 experts, top 2, capacity 1.2x)
    S, M, E, K = 64, 32, 8, 2
    rewrite.autofuse(
        lambda x, gw, gb: reference_moe_dispatch(
            x, gw, gb, num_expert=E, capacity=int(1.2 * K * S / E),
            top_k=K, gate_kind="gshard"),
        label="moe.gate_dispatch")(
        rng.standard_normal((S, M)).astype(np.float32),
        0.1 * rng.standard_normal((M, E)).astype(np.float32),
        0.01 * rng.standard_normal((E,)).astype(np.float32))

    sites = [{"label": r.get("label"), "site": r.get("site"),
              "rule": r.get("rule"),
              "predicted_delta_ms": r.get("predicted_delta_ms")}
             for r in rewrite.fired_records()]
    per_rule: dict = {}
    for s in sites:
        per_rule[s["rule"]] = round(
            per_rule.get(s["rule"], 0.0)
            + float(s["predicted_delta_ms"] or 0.0), 6)
    if export_path:
        rewrite.export_records(export_path)
    return {
        "n_fired": len(sites),
        "rules_fired": sorted(per_rule),
        "sites": sites,
        "per_rule_delta_ms": per_rule,
        "predicted_total_delta_ms": round(sum(per_rule.values()), 6),
        "programs": sorted({s["label"] for s in sites}),
    }


def _main(argv=None):
    import os
    import subprocess

    ap = argparse.ArgumentParser(
        description="static serving-decode prediction (one JSON row)")
    ap.add_argument("--config", default="345m",
                    choices=["tiny", "345m", "1.3b", "13b"])
    ap.add_argument("--concurrency", type=int, default=8)
    ap.add_argument("--page-size", type=int, default=64)
    ap.add_argument("--chip", default="v5e")
    ap.add_argument("--quantize", default=None, choices=[None, "int8"],
                    help="price the weight-only-int8 decode program "
                         "(serving engine quantize='int8')")
    ap.add_argument("--mode", default="decode",
                    choices=["decode", "shared_prefix", "disagg",
                             "fused_dispatch", "fleet", "migration",
                             "overload", "autofusion"],
                    help="decode = classic serving_predicted row; "
                         "shared_prefix = prefix-cache goodput/TTFT "
                         "anchor; disagg = disaggregated prefill/"
                         "decode split anchor; "
                         "fused_dispatch = fused-vs-unfused MoE "
                         "dispatch stage speedup anchor; fleet = "
                         "N-replica router anchor (per-replica "
                         "roofline x N minus router overhead, "
                         "hit-rate-split TTFT); migration = live "
                         "KV-page migration anchor (payload over the "
                         "interconnect roofline + resume cost vs "
                         "full-prompt replay); overload = overload-"
                         "control anchor (deadline-met goodput at "
                         "2x-capacity arrival, control vs FIFO "
                         "baseline); autofusion = per-site "
                         "predicted Δstep-ms of the jaxpr auto-fusion "
                         "rewrites over the tiny engines' programs")
    ap.add_argument("--export-records", default=None, metavar="PATH",
                    help="autofusion mode: also write the raw match "
                         "records (autofusion.json) to PATH for the "
                         "perf doctor")
    ap.add_argument("--prompt-len", type=int, default=1024)
    ap.add_argument("--shared-fraction", type=float, default=0.75)
    ap.add_argument("--max-new", type=int, default=64)
    ap.add_argument("--prefill-chunk", type=int, default=256)
    ap.add_argument("--replicas", type=int, default=2,
                    help="fleet mode: engine replicas behind the router")
    ap.add_argument("--n-requests", type=int, default=16,
                    help="fleet mode: total requests in the workload "
                         "model")
    ap.add_argument("--overload-factor", type=float, default=2.0,
                    help="overload mode: arrival rate as a multiple of "
                         "the predicted admission capacity")
    ap.add_argument("--deadline-s", type=float, default=None,
                    help="overload mode: per-request deadline (default "
                         "4x the unloaded request latency)")
    args = ap.parse_args(argv)
    if not os.environ.get("_PREDICT_RESPAWNED"):
        # same contract as analysis.predict: force the CPU backend in a
        # fresh process BEFORE jax initializes — predictions are
        # trace-only and must never hold a chip
        env = dict(os.environ,
                   _PREDICT_RESPAWNED="1", JAX_PLATFORMS="cpu")
        return subprocess.run(
            [sys.executable, "-m", "paddle_tpu.serving.predict"]
            + (argv if argv is not None else sys.argv[1:]),
            env=env).returncode
    import jax
    jax.config.update("jax_platforms", "cpu")
    try:
        if args.mode == "fused_dispatch":
            row = predicted_fused_dispatch_row(chip=args.chip)
        elif args.mode == "autofusion":
            row = predicted_autofusion_row(args.export_records)
        elif args.mode == "fleet":
            row = predicted_fleet_row(
                args.config, args.replicas, args.n_requests,
                args.concurrency, args.prompt_len, args.shared_fraction,
                args.max_new, args.prefill_chunk, args.page_size,
                args.chip)
        elif args.mode == "migration":
            row = predicted_migration_row(
                args.config, args.prompt_len, args.max_new,
                args.shared_fraction, args.prefill_chunk,
                args.page_size, args.chip)
        elif args.mode == "overload":
            row = predicted_overload_row(
                args.config, args.concurrency, args.prompt_len,
                args.max_new, args.prefill_chunk, args.page_size,
                args.chip, overload_factor=args.overload_factor,
                deadline_s=args.deadline_s)
        elif args.mode == "shared_prefix":
            row = predicted_shared_prefix_row(
                args.config, args.concurrency, args.prompt_len,
                args.shared_fraction, args.max_new, args.prefill_chunk,
                args.page_size, args.chip)
        elif args.mode == "disagg":
            row = predicted_disagg_row(
                args.config, args.concurrency, args.prompt_len,
                args.page_size, args.chip)
        else:
            row = predicted_serving_row(args.config, args.concurrency,
                                        args.page_size, args.chip,
                                        quantize=args.quantize)
    except Exception as e:  # noqa: BLE001 — the row must say why
        row = {"config": args.config, "error": repr(e)[:300]}
    print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(_main())
