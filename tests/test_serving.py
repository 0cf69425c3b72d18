"""Serving engine: page pool, ragged paged-attention decode, continuous
batching, and the end-to-end checkpoint → engine path.

The Pallas kernel runs in interpret mode on the CPU mesh — the same
pallas_call compiles on TPU — so kernel == XLA-reference equality and
scheduler == sequential-GPTGenerator equality are tier-1 assertions."""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.serving import (ContinuousBatchingScheduler,
                                EngineShapeError, PagePool, PagePoolError,
                                PagePoolOOM, ServingEngine,
                                simulate_decode_signatures)


def _tiny_model(seed=0):
    from paddle_tpu.models.gpt import (GPTForPretraining, GPTModel,
                                       gpt_tiny_config)
    paddle.seed(seed)
    cfg = gpt_tiny_config()
    return GPTForPretraining(GPTModel(cfg)), cfg


def _prompts(cfg, lens, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab_size, (s,)).astype(np.int32)
            for s in lens]


# ---------------------------------------------------------------- pool

def test_pool_alloc_extend_free_roundtrip():
    pool = PagePool(num_pages=9, page_size=4, num_layers=2,
                    num_kv_heads=2, head_dim=8)
    pages = pool.alloc("a", 5)                 # 2 pages for 5 tokens
    assert len(pages) == 2 and PagePool.SINK not in pages
    assert pool.pages_in_use == 2 and pool.seq_len("a") == 5
    pool.extend("a", 3)                        # 8 tokens: still 2 pages
    assert len(pool.table("a")) == 2
    pool.extend("a", 1)                        # 9th token: page 3
    assert len(pool.table("a")) == 3
    pool.alloc("b", 4)
    assert pool.pages_in_use == 4
    pool.free("a")
    assert pool.pages_in_use == 1 and pool.free_pages == 7
    # freed pages are reused (lowest ids first)
    again = pool.alloc("c", 12)
    assert set(again) & set(pages)


def test_pool_oob_and_oom():
    pool = PagePool(num_pages=4, page_size=4, num_layers=1,
                    num_kv_heads=1, head_dim=4)
    pool.alloc("a", 4)
    with pytest.raises(PagePoolError):
        pool.alloc("a", 2)                     # double alloc
    with pytest.raises(PagePoolError):
        pool.extend("zzz")                     # unknown sequence
    with pytest.raises(PagePoolError):
        pool.free("zzz")
    with pytest.raises(PagePoolError):
        pool.alloc("big", 1000)                # beyond max_seq_len
    with pytest.raises(PagePoolOOM):
        pool.alloc("b", 12)                    # only 2 pages free
    pool.alloc("b", 8)                         # exactly fits
    with pytest.raises(PagePoolOOM):
        pool.extend("b", 1)                    # pool exhausted
    with pytest.raises(ValueError):
        PagePool(num_pages=1, page_size=4, num_layers=1,
                 num_kv_heads=1, head_dim=4)   # sink page needs company


def test_pool_fragmentation_accounting():
    pool = PagePool(num_pages=17, page_size=8, num_layers=1,
                    num_kv_heads=1, head_dim=4)
    pool.alloc("a", 9)    # 2 pages, 7 slots wasted
    pool.alloc("b", 8)    # 1 page, 0 wasted
    st = pool.stats()
    assert st["pages_in_use"] == 3 and st["live_tokens"] == 17
    assert st["utilization"] == round(17 / 24, 4)
    assert st["internal_fragmentation"] == round(1 - 17 / 24, 4)
    pool.free("a")
    pool.free("b")
    assert pool.stats()["internal_fragmentation"] == 0.0


def test_pool_table_and_prefill_rows():
    pool = PagePool(num_pages=9, page_size=4, num_layers=1,
                    num_kv_heads=1, head_dim=4, max_seq_len=16)
    pool.alloc("a", 6)
    tbl = pool.table_array(["a", None])
    assert tbl.shape == (2, 4) and tbl.dtype == np.int32
    assert list(tbl[0, :2]) == pool.table("a")
    assert (tbl[0, 2:] == PagePool.SINK).all()
    assert (tbl[1] == PagePool.SINK).all()      # idle slot: all sink
    assert list(pool.lens_array(["a", None])) == [6, 0]
    rows = pool.prefill_rows("a", 8)
    p0, p1 = pool.table("a")
    assert list(rows[:6]) == [p0 * 4, p0 * 4 + 1, p0 * 4 + 2, p0 * 4 + 3,
                              p1 * 4, p1 * 4 + 1]
    assert (rows[6:] < 4).all()                 # padding rows → sink page


# -------------------------------------------------------------- kernel

def test_paged_decode_kernel_matches_reference_ragged():
    """Pallas ragged paged decode == XLA reference attention on a ragged
    batch (different lengths, idle slot) — acceptance criterion."""
    from paddle_tpu.kernels.paged_attention import (
        paged_attention_decode, paged_attention_reference)
    rng = np.random.default_rng(0)
    B, nh, d, np_, ps, pmax = 4, 4, 16, 13, 8, 4
    q = jnp.asarray(rng.standard_normal((B, nh, d)).astype(np.float32))
    kp = jnp.asarray(rng.standard_normal((np_, ps, nh, d)).astype(
        np.float32))
    vp = jnp.asarray(rng.standard_normal((np_, ps, nh, d)).astype(
        np.float32))
    pt = jnp.asarray(np.array([[1, 2, 3, 4], [5, 0, 0, 0],
                               [6, 7, 0, 0], [0, 0, 0, 0]], np.int32))
    sl = jnp.asarray(np.array([29, 3, 16, 0], np.int32))  # ragged + idle
    out = paged_attention_decode(q, kp, vp, pt, sl)
    ref = paged_attention_reference(q, kp, vp, pt, sl)
    # live slots match exactly; the idle slot only has to stay finite
    np.testing.assert_allclose(np.asarray(out)[:3], np.asarray(ref)[:3],
                               rtol=2e-5, atol=2e-5)
    assert np.isfinite(np.asarray(out)).all()


def test_paged_decode_matches_dense_attention_oracle():
    """Paged gather+mask == plain causal attention over the dense cache:
    scatter a sequence into pages, decode its last token, compare with
    softmax over the raw K/V."""
    from paddle_tpu.kernels.paged_attention import paged_attention_decode
    rng = np.random.default_rng(1)
    nh, d, ps, n = 2, 8, 4, 11
    k_seq = rng.standard_normal((n, nh, d)).astype(np.float32)
    v_seq = rng.standard_normal((n, nh, d)).astype(np.float32)
    q = jnp.asarray(rng.standard_normal((1, nh, d)).astype(np.float32))
    pages = [2, 4, 1]                           # 3 pages hold 11 tokens
    kp = np.zeros((6, ps, nh, d), np.float32)
    vp = np.zeros((6, ps, nh, d), np.float32)
    for t in range(n):
        kp[pages[t // ps], t % ps] = k_seq[t]
        vp[pages[t // ps], t % ps] = v_seq[t]
    out = paged_attention_decode(
        q, jnp.asarray(kp), jnp.asarray(vp),
        jnp.asarray(np.array([pages], np.int32)),
        jnp.asarray(np.array([n], np.int32)))
    s = np.einsum("nd,tnd->nt", np.asarray(q)[0], k_seq) / np.sqrt(d)
    p = np.exp(s - s.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    ref = np.einsum("nt,tnd->nd", p, v_seq)
    np.testing.assert_allclose(np.asarray(out)[0], ref, rtol=2e-5,
                               atol=2e-5)


def test_paged_decode_gqa():
    """num_kv_heads dividing num_heads (MQA/GQA pool layout)."""
    from paddle_tpu.kernels.paged_attention import (
        paged_attention_decode, paged_attention_reference)
    rng = np.random.default_rng(2)
    B, nh, nkv, d, np_, ps = 2, 4, 2, 8, 5, 4
    q = jnp.asarray(rng.standard_normal((B, nh, d)).astype(np.float32))
    kp = jnp.asarray(rng.standard_normal((np_, ps, nkv, d)).astype(
        np.float32))
    vp = jnp.asarray(rng.standard_normal((np_, ps, nkv, d)).astype(
        np.float32))
    pt = jnp.asarray(np.array([[1, 2], [3, 4]], np.int32))
    sl = jnp.asarray(np.array([7, 8], np.int32))
    np.testing.assert_allclose(
        np.asarray(paged_attention_decode(q, kp, vp, pt, sl)),
        np.asarray(paged_attention_reference(q, kp, vp, pt, sl)),
        rtol=2e-5, atol=2e-5)


# ----------------------------------------------------------- scheduler

def test_scheduler_matches_sequential_generator():
    """Continuous batching (ragged prompts, shared pool, bucketed decode)
    reproduces sequential GPTGenerator greedy decode token for token —
    acceptance criterion."""
    from paddle_tpu.models.gpt import GPTGenerator
    model, cfg = _tiny_model()
    gen = GPTGenerator(model, temperature=0.0)
    eng = ServingEngine(model, page_size=8, decode_buckets=(1, 2, 4),
                        aot=True)
    sched = ContinuousBatchingScheduler(eng)
    prompts = _prompts(cfg, (5, 11, 8, 3))
    reqs = [sched.submit(p, max_new_tokens=6) for p in prompts]
    sched.run()
    assert all(r.state == "finished" for r in reqs)
    for p, r in zip(prompts, reqs):
        ref = np.asarray(gen(p[None, :], max_new_tokens=6)._value)[0]
        np.testing.assert_array_equal(r.output_ids, ref,
                                      err_msg=f"prompt len {len(p)}")
    # drained pool: no leaked pages
    assert eng.pool.pages_in_use == 0
    assert sched.steps > 0 and len(sched.step_times) == sched.steps


def test_scheduler_admit_evict_staggered_arrivals():
    """Requests arriving mid-flight join the running batch (admit) and
    finished ones leave (evict) without disturbing other streams."""
    from paddle_tpu.models.gpt import GPTGenerator
    model, cfg = _tiny_model(seed=3)
    gen = GPTGenerator(model, temperature=0.0)
    eng = ServingEngine(model, page_size=8, decode_buckets=(1, 2),
                        aot=False)
    sched = ContinuousBatchingScheduler(eng)
    p1, p2, p3 = _prompts(cfg, (4, 9, 6), seed=3)
    r1 = sched.submit(p1, max_new_tokens=8)
    sched.step(); sched.step()
    r2 = sched.submit(p2, max_new_tokens=3)    # joins mid-flight
    sched.step()
    r3 = sched.submit(p3, max_new_tokens=4)    # queues behind bucket cap
    sched.run()
    for p, r, n in [(p1, r1, 8), (p2, r2, 3), (p3, r3, 4)]:
        ref = np.asarray(gen(p[None, :], max_new_tokens=n)._value)[0]
        np.testing.assert_array_equal(r.output_ids, ref)
    s = r2.summary()
    assert s["state"] == "finished" and s["new_tokens"] == 3
    assert s["queue_wait_s"] >= 0 and s["ttft_s"] > 0


def test_scheduler_page_pressure_queues_requests():
    """Admission reserves the FULL completion: a pool too small for two
    sequences runs them one after the other, both still correct."""
    model, cfg = _tiny_model(seed=4)
    # pool: sink + 4 pages of 8 tokens = room for ONE (prompt 17 + 7)
    eng = ServingEngine(model, page_size=8, num_pages=5,
                        max_seq_len=32, decode_buckets=(1, 2), aot=False)
    sched = ContinuousBatchingScheduler(eng)
    pa, pb = _prompts(cfg, (17, 18), seed=4)
    ra = sched.submit(pa, max_new_tokens=7)
    rb = sched.submit(pb, max_new_tokens=7)
    sched.step()
    assert ra.state == "running" and rb.state == "queued"
    sched.run()
    assert ra.state == rb.state == "finished"
    assert len(ra.tokens) == len(rb.tokens) == 7
    assert eng.pool.pages_in_use == 0


def test_scheduler_rejects_oversized_and_eos():
    model, cfg = _tiny_model(seed=5)
    eng = ServingEngine(model, page_size=8, max_seq_len=32,
                        decode_buckets=(1, 2), aot=False)
    sched = ContinuousBatchingScheduler(eng)
    big = sched.submit(np.zeros(30, np.int32), max_new_tokens=10)
    assert big.state == "rejected"
    # max_new < 1 is unservable (prefill always emits one token) and
    # must bounce at submit, not crash the loop at admission
    zero = sched.submit(np.zeros(32, np.int32), max_new_tokens=0)
    assert zero.state == "rejected"
    # eos: find the greedy first token, then ask for it as the stop id
    (p,) = _prompts(cfg, (6,), seed=5)
    probe = sched.submit(p, max_new_tokens=1)
    sched.run()
    eos = probe.tokens[0]
    r = sched.submit(p, max_new_tokens=10, eos_id=eos)
    sched.run()
    assert r.state == "finished" and r.tokens == [eos]


# shape errors, the AOT closure and "a mixed run compiles nothing" are
# held for every engine in tests/test_engine_contract.py


# ------------------------------------------------- engine from checkpoint

def test_engine_end_to_end_from_checkpoint(tmp_path):
    """checkpoint-load → generator → scheduler: a paddle.save'd state
    dict serves identically to the live model."""
    from paddle_tpu.models.gpt import gpt_tiny_config
    model, cfg = _tiny_model(seed=9)
    path = str(tmp_path / "gpt.pdparams")
    paddle.save(model.state_dict(), path)

    eng = ServingEngine.from_checkpoint(path, gpt_tiny_config(),
                                        page_size=8,
                                        decode_buckets=(1, 2), aot=False)
    live = ServingEngine(model, page_size=8, decode_buckets=(1, 2),
                         aot=False)
    prompts = _prompts(cfg, (7, 12), seed=9)
    outs = []
    for e in (eng, live):
        sched = ContinuousBatchingScheduler(e)
        reqs = [sched.submit(p, max_new_tokens=5) for p in prompts]
        sched.run()
        outs.append([r.output_ids for r in reqs])
    for a, b in zip(*outs):
        np.testing.assert_array_equal(a, b)


# ------------------------------------------------------------ telemetry

def test_serving_telemetry_and_flight_recorder():
    """Serving steps land in the paddle_serving_* metric family AND the
    flight recorder / anomaly path, like train steps."""
    from paddle_tpu.observability import get_registry
    from paddle_tpu.observability.flight import get_flight_recorder
    model, cfg = _tiny_model(seed=10)
    reg = get_registry()

    def val(name, **labels):
        inst = reg.get(name)
        if inst is None:
            return 0.0
        total = 0.0
        for lab, state in inst.collect():
            if all(dict(lab).get(k) == v for k, v in labels.items()):
                total += state.get("value", state.get("count", 0.0))
        return total

    sub0 = val("paddle_serving_requests_total", event="submitted")
    fin0 = val("paddle_serving_requests_total", event="finished")
    tok0 = val("paddle_serving_tokens_out_total")
    eng = ServingEngine(model, page_size=8, decode_buckets=(1, 2),
                        aot=False)
    sched = ContinuousBatchingScheduler(eng)
    reqs = [sched.submit(p, max_new_tokens=4)
            for p in _prompts(cfg, (5, 9), seed=10)]
    sched.run()
    assert val("paddle_serving_requests_total", event="submitted") \
        == sub0 + 2
    assert val("paddle_serving_requests_total", event="finished") \
        == fin0 + 2
    assert val("paddle_serving_tokens_out_total") \
        == tok0 + sum(len(r.tokens) for r in reqs)
    ttft = reg.get("paddle_serving_ttft_seconds")
    assert ttft is not None and ttft.count >= 2
    assert reg.get("paddle_serving_kv_pages_in_use") is not None
    # flight recorder saw serving-path steps
    recs = get_flight_recorder().records()
    serving_steps = [r for r in recs
                     if r.get("kind") == "step"
                     and r.get("path") == "serving"]
    assert len(serving_steps) >= sched.steps


# ----------------------------------------------------------- lint gate

def test_check_program_serving_gate_clean():
    """tools/check_program.py --model serving: the decode-step pass
    suite AND the bucket-closure proof both report clean."""
    import importlib.util
    import os
    spec = importlib.util.spec_from_file_location(
        "check_program", os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "tools", "check_program.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    reports = mod.lint_model("serving", hbm_budget_gb=16)
    assert len(reports) == 4
    for rep in reports:
        assert rep.clean, str(rep)
    assert {r.target_name for r in reports} == {
        "serving.decode_step", "serving.decode_buckets",
        "serving.chunk_prefill", "serving.sdar_block_step"}


# ------------------------------------------------------------- predict

def test_predicted_serving_row_tiny():
    """The serving_predicted row: cost model over the real decode jaxpr,
    abstract shapes only — numbers present and positive."""
    from paddle_tpu.serving.predict import predicted_serving_row
    row = predicted_serving_row("tiny", concurrency=4, page_size=8)
    assert row["predicted_tokens_per_sec"] > 0
    assert row["predicted_decode_step_ms"] > 0
    assert row["predicted_per_token_ms_p95"] >= \
        row["predicted_per_token_ms_p50"]
    assert row["concurrency"] == 4 and row["chip_assumed"] == "v5e"
