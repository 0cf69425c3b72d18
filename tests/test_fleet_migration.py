"""Fleet-level KV-page live migration.

Coverage:

- pool ``token_rows`` (the gather/scatter index primitive);
- in-process source->dest scheduler roundtrips: cold-dest transfer,
  warm-dest suffix-only transfer (radix prefix reuse), abort paths
  (source stays authoritative), corrupt-payload rejection — all
  asserting TOKEN-EXACT post-migration decode vs. the unmigrated
  sequential-GPTGenerator oracle and zero leaked pool pages;
- the hardened control-plane RPC: env-tunable deadline, bounded
  exponential backoff, per-op retry counter, retries=0 passthrough;
- doctor attribution: the ``migration`` bucket still sums EXACTLY to
  delta_ms; fold totals (migrate_seconds/bytes, migrated_requests);
- the ``serving_fleet_migration_predicted`` anchor + bench_compare map;
- router ``migration_target`` policy (pure) and the
  ``pause_replica``/``resume_replica`` fault-injection helpers;
- one REAL 2-replica fleet (replica processes): mid-stream live
  migration (chunked, checksummed, warm-dest prefix reuse), SIGKILL
  failover that replays only the suffix the surviving cache misses,
  and drain-by-migrate scale-in — zero failed requests, token-exact
  vs. the single-replica oracle throughout;
- a slow-marked chaos loop: kill -> migrate -> scale-in cycles under
  sustained load (plus a SIGSTOP straggler shed) with zero failures.
"""
import collections
import json
import signal
import socket
import threading
import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.models.gpt import gpt_tiny_config
from paddle_tpu.serving import (ContinuousBatchingScheduler, PagePool,
                                PagePoolError, ServingEngine)
from paddle_tpu.serving.router import PrefixAffinityRouter


def _fleet_cfg():
    return gpt_tiny_config(num_layers=2, hidden_size=32, num_heads=2,
                           max_position_embeddings=64)


ENGINE_KW = dict(page_size=8, decode_buckets=(1, 2, 4, 8),
                 prefill_chunk=8, prefix_cache=True)


def _tiny_model(seed=0):
    from paddle_tpu.models.gpt import GPTForPretraining, GPTModel
    paddle.seed(seed)
    cfg = gpt_tiny_config()
    return GPTForPretraining(GPTModel(cfg)), cfg


def _oracle(model):
    from paddle_tpu.models.gpt import GPTGenerator
    gen = GPTGenerator(model, temperature=0.0)

    def ref(p, n):
        full = np.asarray(gen(p[None, :], max_new_tokens=n)._value)[0]
        return [int(t) for t in full[len(p):]]
    return ref


def _drain_env(monkeypatch):
    monkeypatch.delenv("PADDLE_TELEMETRY_DIR", raising=False)
    monkeypatch.delenv("PADDLE_REQUESTS_PER_RANK", raising=False)


# ===========================================================================
# pool: token_rows
# ===========================================================================

def test_pool_token_rows_maps_positions_to_page_rows():
    pool = PagePool(num_pages=9, page_size=4, num_layers=2,
                    num_kv_heads=2, head_dim=8)
    pages = pool.alloc("a", 10)                   # 3 pages
    rows = pool.token_rows("a", 0, 10)
    assert rows.dtype == np.int32 and rows.shape == (10,)
    # row i lives in page pages[i // ps] at slot i % ps
    for i, r in enumerate(rows):
        assert r == pages[i // 4] * 4 + i % 4
    # suffix window
    np.testing.assert_array_equal(pool.token_rows("a", 8, 10), rows[8:])
    assert pool.token_rows("a", 4, 4).shape == (0,)
    with pytest.raises(PagePoolError):
        pool.token_rows("a", 0, 11)               # beyond seq_len
    with pytest.raises(PagePoolError):
        pool.token_rows("a", -1, 4)
    with pytest.raises(PagePoolError):
        pool.token_rows("nope", 0, 1)


# ===========================================================================
# in-process scheduler roundtrips (token-exact vs. oracle)
# ===========================================================================

def _mk(model, prefix_cache=False):
    eng = ServingEngine(model, page_size=8, decode_buckets=(1, 2, 4),
                        aot=False, prefix_cache=prefix_cache)
    return ContinuousBatchingScheduler(eng), eng


def _step_to_mid_decode(sched, r, min_tokens=3):
    for _ in range(300):
        if r.state == "running" and len(r.tokens) >= min_tokens \
                and not r.done:
            return
        sched.step()
    pytest.fail(f"request never reached mid-decode: {r.state}")


def test_migration_roundtrip_cold_dest_token_exact():
    model, cfg = _tiny_model()
    ref = _oracle(model)
    src, src_eng = _mk(model)
    dst, dst_eng = _mk(model)
    rng = np.random.default_rng(1)
    p = rng.integers(0, cfg.vocab_size, (13,)).astype(np.int32)
    r = src.submit(p, max_new_tokens=10, rid=101)
    _step_to_mid_decode(src, r)
    assert src.migratable_rids() == [101]

    ck = src.checkpoint_request(101)
    assert ck is not None and r.state == "migrating"
    assert src.status()["migrating_out"] == 1
    assert src.checkpoint_request(101) is None     # not running anymore
    token_ids = ck["prompt"] + ck["tokens"][:-1]
    ok, cached = dst.prepare_migration_in(101, token_ids,
                                          len(ck["prompt"]), ck["max_new"])
    assert ok is True and cached == 0              # no cache: cold transfer
    k, v = src_eng.export_kv(101, start=cached)
    assert k.shape == v.shape
    assert k.shape[1] == len(token_ids)            # every valid KV row moved
    meta = dict(ck, migrate_bytes=k.nbytes + v.nbytes,
                migrate_s=ck["migrate_s"] + 0.002, migrate_window_s=0.002)
    ok2, cached2 = dst.adopt_migrated(meta, k, v)
    assert ok2 is True and cached2 == 0
    src.complete_migration(101)
    assert src.status()["migrations_out"] == 1
    assert src_eng.pool.pages_in_use == 0          # source fully released

    fin = dst.run()
    assert [q.rid for q in fin] == [101] and fin[0].state == "finished"
    assert fin[0].tokens == ref(p, 10)             # token-exact resume
    s = fin[0].summary()
    assert s["migrations"] == 1 and s["migrate_bytes"] == k.nbytes + v.nbytes
    assert dst.status()["migrations_in"] == 1
    assert dst_eng.kv_migrations_in == 1
    assert dst_eng.status()["migration"]["kv_bytes"] > 0
    assert dst_eng.pool.pages_in_use == 0 and dst._reserved_pages == 0


def test_migration_warm_dest_transfers_suffix_only():
    model, cfg = _tiny_model(seed=2)
    ref = _oracle(model)
    src, src_eng = _mk(model)
    dst, dst_eng = _mk(model, prefix_cache=True)
    rng = np.random.default_rng(2)
    p = rng.integers(0, cfg.vocab_size, (17,)).astype(np.int32)
    # destination already served the same prompt: its radix cache holds
    # the prefix (greedy + same weights => identical decode path)
    warm = dst.submit(p, max_new_tokens=6, rid=7)
    dst.run()
    assert warm.state == "finished"

    r = src.submit(p, max_new_tokens=6, rid=8)
    _step_to_mid_decode(src, r, min_tokens=2)
    ck = src.checkpoint_request(8)
    token_ids = ck["prompt"] + ck["tokens"][:-1]
    ok, cached = dst.prepare_migration_in(8, token_ids, len(ck["prompt"]),
                                          ck["max_new"])
    # page-granular prefix reuse: at least one full page is NOT resent
    assert ok is True and cached >= 8 and cached % 8 == 0
    assert cached < len(token_ids)
    k, v = src_eng.export_kv(8, start=cached)
    assert k.shape[1] == len(token_ids) - cached   # uncached suffix only
    ok2, cached2 = dst.adopt_migrated(
        dict(ck, migrate_bytes=k.nbytes + v.nbytes), k, v)
    assert ok2 is True and cached2 == cached
    src.complete_migration(8)

    fin = {q.rid: q for q in dst.run()}
    assert fin[8].state == "finished" and fin[8].tokens == ref(p, 6)
    assert fin[8].tokens == fin[7].tokens          # same greedy stream
    assert dst._reserved_pages == 0


def test_migration_abort_source_stays_authoritative():
    model, cfg = _tiny_model(seed=3)
    ref = _oracle(model)
    src, src_eng = _mk(model)
    rng = np.random.default_rng(3)
    p = rng.integers(0, cfg.vocab_size, (9,)).astype(np.int32)
    r = src.submit(p, max_new_tokens=8, rid=11)
    _step_to_mid_decode(src, r)
    assert src.checkpoint_request(11) is not None
    # transfer failed: restore the checkpoint, resume exactly in place
    assert src.abort_migration(11) is True
    assert src.abort_migration(11) is False        # idempotent
    fin = src.run()
    assert fin[0].tokens == ref(p, 8)
    assert src_eng.pool.pages_in_use == 0
    assert src.status()["migrations_out"] == 0


def test_migration_in_abort_and_corrupt_payload_restore_reservations():
    model, cfg = _tiny_model(seed=4)
    ref = _oracle(model)
    src, src_eng = _mk(model)
    dst, dst_eng = _mk(model)
    rng = np.random.default_rng(4)
    p = rng.integers(0, cfg.vocab_size, (11,)).astype(np.int32)
    r = src.submit(p, max_new_tokens=7, rid=21)
    _step_to_mid_decode(src, r)
    ck = src.checkpoint_request(21)
    token_ids = ck["prompt"] + ck["tokens"][:-1]

    # staged then aborted: reservation + staged import fully unwound
    base = dst._reserved_pages
    ok, _ = dst.prepare_migration_in(21, token_ids, len(ck["prompt"]),
                                     ck["max_new"])
    assert ok and dst._reserved_pages > base
    assert dst.abort_migration_in(21) is True
    assert dst.abort_migration_in(21) is False
    assert dst._reserved_pages == base and not dst_eng._kv_import

    # corrupt payload (wrong row count): rejected, reservation restored,
    # and a fresh begin starts clean afterwards
    ok, cached = dst.prepare_migration_in(21, token_ids, len(ck["prompt"]),
                                          ck["max_new"])
    assert ok is True
    k, v = src_eng.export_kv(21, start=cached)
    bad, reason = dst.adopt_migrated(dict(ck), k[:, :-1], v[:, :-1])
    assert bad is False and "payload" in reason
    assert dst._reserved_pages == base and not dst_eng._kv_import
    assert dst_eng.pool.pages_in_use == 0

    ok, cached = dst.prepare_migration_in(21, token_ids, len(ck["prompt"]),
                                          ck["max_new"])
    assert ok is True
    ok2, _ = dst.adopt_migrated(
        dict(ck, migrate_bytes=k.nbytes + v.nbytes), k, v)
    assert ok2 is True
    src.complete_migration(21)
    fin = dst.run()
    assert fin[0].rid == 21 and fin[0].tokens == ref(p, 7)
    # an unknown rid is refused, not crashed
    assert dst.adopt_migrated(dict(ck, rid=999), k, v) \
        == (False, "no_staged_migration")


def test_prepare_migration_in_admission_reasons():
    from paddle_tpu.serving.scheduler import _ShapeProbeEngine
    eng = _ShapeProbeEngine(decode_buckets=(1, 2), prefill_buckets=(8, 32),
                            page_size=8, num_pages=32, max_seq_len=32)
    sched = ContinuousBatchingScheduler(eng)
    # a device-free probe engine has no KV import surface
    assert sched.prepare_migration_in(1, [1, 2, 3], 3, 4) \
        == (False, "engine_unsupported")

    model, cfg = _tiny_model(seed=5)
    dst, _ = _mk(model)
    toks = list(range(8))
    dst.drain()
    assert dst.prepare_migration_in(1, toks, 8, 4) == (False, "draining")
    dst.draining = False
    assert dst.prepare_migration_in(1, toks, 8, 999)[1] == "too_long"
    ok, _ = dst.prepare_migration_in(1, toks, 8, 4)
    assert ok is True
    assert dst.prepare_migration_in(1, toks, 8, 4) \
        == (False, "duplicate_rid")
    dst.abort_migration_in(1)


# ===========================================================================
# hardened control-plane RPC
# ===========================================================================

def test_rpc_retry_backoff_counter_and_retries_zero(monkeypatch):
    from paddle_tpu.observability import instrument as obs
    from paddle_tpu.serving.fleet import _rpc_request
    monkeypatch.setenv("PADDLE_FLEET_RPC_RETRY_BASE_S", "0.001")
    state = {"fail": 2}
    srv = socket.create_server(("127.0.0.1", 0))
    addr = srv.getsockname()[:2]

    def serve():
        while True:
            try:
                conn, _ = srv.accept()
            except OSError:
                return
            with conn:
                if state["fail"] > 0:
                    state["fail"] -= 1
                    continue                    # slam the door: OSError
                with conn.makefile("rb") as f:
                    msg = json.loads(f.readline().decode())
                conn.sendall(json.dumps(
                    {"ok": True, "echo": msg["op"]}).encode() + b"\n")

    threading.Thread(target=serve, daemon=True).start()
    try:
        c = obs.fleet_rpc_retries_counter().labels(op="ping")
        before = c.value
        t0 = time.monotonic()
        reply = _rpc_request(addr, {"op": "ping"}, timeout=5.0, retries=3)
        assert reply == {"ok": True, "echo": "ping"}
        assert c.value == before + 2            # one inc per retry, by op
        # backoff floor: 0.001*1 + 0.001*2 (jitter can only add)
        assert time.monotonic() - t0 >= 0.003
        # non-replayable ops opt out: first transient error surfaces
        state["fail"] = 1
        with pytest.raises(OSError):
            _rpc_request(addr, {"op": "poll"}, timeout=5.0, retries=0)
        assert c.value == before + 2            # no retry, no inc
        # retry budget exhausted -> the error still surfaces
        state["fail"] = 99
        with pytest.raises(OSError):
            _rpc_request(addr, {"op": "ping"}, timeout=5.0, retries=1)
    finally:
        srv.close()


def test_chunk_blob_respects_env_size(monkeypatch):
    from paddle_tpu.serving.fleet import _chunk_blob
    monkeypatch.setenv("PADDLE_FLEET_MIGRATE_CHUNK_BYTES", "4")
    blob = b"0123456789"
    chunks = _chunk_blob(blob)
    assert chunks == [b"0123", b"4567", b"89"]
    assert b"".join(chunks) == blob
    monkeypatch.setenv("PADDLE_FLEET_MIGRATE_CHUNK_BYTES", "0")
    assert len(_chunk_blob(blob)) == len(blob)   # floor of 1 byte


# ===========================================================================
# doctor / fold: the migration bucket sums exactly
# ===========================================================================

def _fleet_records(migrated=0):
    recs = []
    for rank, mean in ((0, 0.010), (1, 0.030)):
        for i in range(3):
            recs.append({
                "event": "request", "rank": rank, "rid": rank * 3 + i,
                "state": "finished", "new_tokens": 8,
                "router_wait_s": 0.05, "queue_wait_s": 0.01,
                "prefill_s": 0.02, "decode_s": mean * 7,
                "ttft_s": 0.031, "total_s": 0.031 + mean * 7,
                "per_token_s": {"count": 8, "mean": mean, "p50": mean,
                                "p95": mean, "p99": mean, "max": mean},
            })
    for r in recs[:migrated]:
        r.update(migrations=1, migrate_s=0.024, migrate_bytes=4096)
    return recs


def test_fold_migration_totals():
    from paddle_tpu.observability.reqtrace import fold_request_records
    sv = fold_request_records(_fleet_records(migrated=2))
    assert sv["migrate_seconds_total"] == pytest.approx(0.048)
    assert sv["migrate_bytes_total"] == 8192
    assert sv["migrated_requests"] == 2
    clean = fold_request_records(_fleet_records())
    assert clean["migrate_seconds_total"] == 0.0
    assert clean["migrated_requests"] == 0


def test_doctor_migration_bucket_sums_exactly_to_delta():
    from paddle_tpu.observability.doctor import attribute_serving_gap
    from paddle_tpu.observability.reqtrace import fold_request_records
    pred = {"predicted_decode_step_ms": 5.0,
            "predicted_per_token_ms_p50": 5.0}
    summary = {"serving": fold_request_records(_fleet_records(migrated=2)),
               "compile": {"seconds": 0.48}}
    attr = attribute_serving_gap(summary, pred)
    # 2 x 24ms over 48 tokens = 1ms/token carved out of the residual
    assert attr["buckets"]["migration"] == pytest.approx(
        0.048 / 48 * 1e3, abs=1e-6)
    assert "router_queue" in attr["buckets"]
    assert sum(attr["buckets"].values()) == pytest.approx(
        attr["delta_ms"], abs=1e-6)
    # no migrations -> no bucket (classic shape preserved)
    attr0 = attribute_serving_gap(
        {"serving": fold_request_records(_fleet_records())}, pred)
    assert "migration" not in attr0["buckets"]
    assert sum(attr0["buckets"].values()) == pytest.approx(
        attr0["delta_ms"], abs=1e-6)


# ===========================================================================
# predicted anchor + bench_compare mapping
# ===========================================================================

def test_predicted_migration_row_payload_and_speedup():
    from paddle_tpu.serving.predict import predicted_migration_row
    row = predicted_migration_row("tiny", prompt_len=64, decoded=8,
                                  cached_fraction=0.5, prefill_chunk=16,
                                  page_size=16)
    # cached prefix is page-aligned: 32 of 64 prompt tokens reused
    assert row["cached_prefix_len"] == 32
    assert row["payload_tokens"] == 64 + 8 - 32
    assert row["predicted_payload_mb"] < row["predicted_full_kv_mb"]
    # migrating beats a cold full-prompt replay, on ICI and (less so) DCN
    assert row["predicted_speedup"] > 1.0
    assert row["predicted_speedup"] >= row["predicted_speedup_dcn"] > 0
    assert row["predicted_migration_ms"] < row["predicted_replay_ms"]
    assert row["dcn_bw_assumption"] == "ici_bw/8"
    # less destination reuse -> bigger payload -> smaller win
    cold = predicted_migration_row("tiny", prompt_len=64, decoded=8,
                                   cached_fraction=0.0, prefill_chunk=16,
                                   page_size=16)
    assert cold["cached_prefix_len"] == 0
    assert cold["payload_tokens"] == 72
    assert cold["predicted_speedup"] <= row["predicted_speedup"]
    # at least one KV row always travels even at cached_fraction=1
    full = predicted_migration_row("tiny", prompt_len=64, decoded=1,
                                   cached_fraction=1.0, prefill_chunk=16,
                                   page_size=16)
    assert full["payload_tokens"] >= 1


def test_bench_compare_anchors_migration_row():
    from tools.bench_compare import _ANCHOR_MAP, _predicted_anchor
    assert _ANCHOR_MAP["serving_fleet_migration"] \
        == "serving_fleet_migration_predicted"
    rows = {"serving_fleet_migration_predicted":
            {"metric": "serving_fleet_migration_predicted", "value": 3.0}}
    assert _predicted_anchor("serving_fleet_migration_ms", rows) \
        is rows["serving_fleet_migration_predicted"]


# ===========================================================================
# router policy + fault injection helpers (pure)
# ===========================================================================

def _snap(**kw):
    d = {"healthy": True, "draining": False, "queue_depth": 0,
         "pending": 0, "free_pages": 50, "num_pages": 64}
    d.update(kw)
    return d


def test_migration_target_policy():
    r = PrefixAffinityRouter(max_queue_depth=4)
    snaps = {0: _snap(pending=3), 1: _snap(pending=1),
             2: _snap(draining=True), 3: _snap(healthy=False)}
    assert r.migration_target(snaps) == 1           # least-loaded healthy
    assert r.migration_target(snaps, exclude=(1,)) == 0
    assert r.migration_target(snaps, exclude=(0, 1)) is None
    # saturated (queue at cap) loses to a loaded-but-open peer
    snaps2 = {0: _snap(queue_depth=4), 1: _snap(pending=5)}
    assert r.migration_target(snaps2) == 1
    # everyone saturated: least-loaded of the bad set, never None
    snaps3 = {0: _snap(queue_depth=4, pending=9), 1: _snap(queue_depth=4)}
    assert r.migration_target(snaps3) == 1
    # page pressure with a queue in front counts as saturation
    snaps4 = {0: _snap(free_pages=1, queue_depth=1), 1: _snap(pending=7)}
    assert r.migration_target(snaps4, pages_needed=6) == 1


def test_pause_resume_replica_delegate_signals():
    from paddle_tpu.distributed.fleet.elastic import (pause_replica,
                                                      resume_replica)

    class _FakeRouter:
        def __init__(self):
            self.calls = []

        def kill_replica(self, rid, sig=signal.SIGKILL):
            self.calls.append((rid, sig))
            return 4242

    r = _FakeRouter()
    assert pause_replica(r, 1) == 4242
    assert resume_replica(r, 2) == 4242
    assert r.calls == [(1, signal.SIGSTOP), (2, signal.SIGCONT)]


# ===========================================================================
# real fleet: live migration + SIGKILL failover + drain-by-migrate
# ===========================================================================

def _shared_prompts(cfg, n, rng, prefix_len=12, suffix_len=4):
    prefix = rng.integers(0, cfg.vocab_size, (prefix_len,)).astype(np.int32)
    return [np.concatenate(
        [prefix, rng.integers(0, cfg.vocab_size,
                              (suffix_len,)).astype(np.int32)])
        for _ in range(n)]


def test_fleet_live_migration_failover_and_drain_by_migrate(
        tmp_path, monkeypatch):
    """ACCEPTANCE: one real 2-replica fleet. (1) a mid-stream request
    live-migrates (chunked + checksummed; only the suffix the warm
    destination cache misses travels) and resumes TOKEN-EXACT; (2) a
    SIGKILLed replica's in-flight work replays only the suffix the
    surviving prefix cache misses; (3) scale-in drains by migrating.
    Zero failed requests; every output identical to the single-replica
    greedy oracle; /status + federation surface the migration counts."""
    from paddle_tpu.distributed.fleet.elastic.fault_injection import \
        kill_replica
    from paddle_tpu.models.gpt import GPTForPretraining, GPTModel
    from paddle_tpu.serving.fleet import FleetRouter
    _drain_env(monkeypatch)
    # force multi-chunk streaming on tiny payloads (replicas inherit env)
    monkeypatch.setenv("PADDLE_FLEET_MIGRATE_CHUNK_BYTES", "2048")
    cfg = _fleet_cfg()
    paddle.seed(7)
    model = GPTForPretraining(GPTModel(cfg))
    ref = _oracle(model)
    ckpt = str(tmp_path / "gpt.pdparams")
    paddle.save(model.state_dict(), ckpt)
    rng = np.random.default_rng(5)
    prompts = _shared_prompts(cfg, 12, rng)
    ps = ENGINE_KW["page_size"]

    # round_robin so BOTH replicas warm the shared prefix in phase 0
    fleet = FleetRouter(cfg, checkpoint=ckpt, n_replicas=2,
                        policy="round_robin",
                        engine_kwargs=dict(ENGINE_KW),
                        run_dir=str(tmp_path / "run"), seed=7,
                        max_restarts=1)
    expected = {}

    def submit(p, n):
        rid = fleet.submit(p, max_new_tokens=n)
        expected[rid] = (p, n)
        return rid

    try:
        fleet.start()
        # ---- phase 0: warm both replica caches with the shared prefix
        for p in prompts[:4]:
            submit(p, 4)
        assert fleet.run(timeout=240)

        # ---- phase 1: live-migrate a mid-decode request
        # The request is as long as the engine's max_seq_len allows and
        # `migrate` is asked again as soon as it answers (a tick of the
        # router only where the request is not dispatched yet, and now
        # and then to see a result), so that it is caught on its first
        # tokens. Every try has a prompt of its own: a retry of the same
        # one would find its pages in the destination's cache and send a
        # payload too small to stream in two chunks.
        mig_rid, rep = None, None
        n_new = cfg.max_position_embeddings - len(prompts[4])
        refused = collections.Counter()     # why every `migrate` said no
        for mig_prompt in [prompts[4]] + _shared_prompts(cfg, 5, rng):
            rid = submit(mig_prompt, n_new)
            deadline = time.monotonic() + 90
            while rid not in fleet.results \
                    and time.monotonic() < deadline:
                r2 = fleet.migrate(rid)
                if r2.get("migrated"):
                    mig_rid, rep = rid, r2
                    break
                why = str(r2.get("reason") or r2.get("error"))
                refused[why] += 1
                if why != "not_running" or not refused[why] % 8:
                    fleet.tick()
            if mig_rid is not None:
                break
            assert rid in fleet.results, dict(refused)  # too fast; again
        assert mig_rid is not None, (
            f"could not catch a request mid-decode: {dict(refused)}")
        # the only refusals are the two benign races
        assert set(refused) <= {"not_inflight", "not_running"}, refused
        assert fleet.run(timeout=240)
        assert rep["bytes"] > 0 and rep["chunks"] >= 2
        # warm destination: at least one full page was NOT resent
        assert rep["cached_len"] >= ps
        assert rep["payload_tokens"] < len(mig_prompt) + n_new
        res = fleet.results[mig_rid]
        assert res["state"] == "finished" and res["replica"] == rep["to"]
        summ = res["summary"]
        assert summ["migrations"] == 1
        assert summ["migrate_bytes"] == rep["bytes"]
        assert summ["migrate_s"] > 0
        assert fleet.migrations_completed >= 1
        assert mig_rid in fleet.migrated_rids
        st = fleet.fleet_status()["migrations"]
        assert st["completed"] >= 1 and st["bytes"] > 0 and st["recent"]

        # ---- phase 2: SIGKILL failover replays only the uncached suffix
        for p in prompts[5:11]:
            submit(p, 8)
        killed = None
        deadline = time.monotonic() + 240
        while killed is None and time.monotonic() < deadline:
            fleet.tick()
            target = next(
                (rec["replica"] for rec in fleet._inflight.values()
                 if rec.get("replica") is not None), None)
            if target is not None:
                kill_replica(fleet, target)
                killed = target
            time.sleep(0.005)
        assert killed is not None
        assert fleet.run(timeout=240)
        assert fleet.requeued_rids          # work WAS in flight
        for rid in set(fleet.requeued_rids):
            s = fleet.results[rid]
            assert s["state"] == "finished"
            # zero cached prefill work replayed: the surviving cache
            # covers the shared prefix, so the re-prefill is suffix-only
            # (strictly fewer replayed tokens than a full-prompt replay)
            assert s["summary"]["cached_prefix_len"] >= ps

        # ---- phase 3: drain-by-migrate scale-in
        deadline = time.monotonic() + 180
        while time.monotonic() < deadline and len(
                [h for h in fleet.replicas.values()
                 if h.alive() and not h.retired]) < 2:
            fleet.tick()
            time.sleep(0.05)     # wait for the relaunched replacement
        before_mig = fleet.migrations_completed
        drained = False
        for attempt in range(3):   # slow boxes: decode can outrun the drain
            for i in range(3):
                submit(prompts[(5 + attempt * 3 + i) % len(prompts)], 40)
            victim = None
            deadline = time.monotonic() + 90
            while victim is None and time.monotonic() < deadline:
                fleet.tick()
                for rid_, h in fleet.replicas.items():
                    if getattr(h, "retired", False):
                        continue
                    if int((h.last_status or {}).get("running") or 0) > 0:
                        victim = rid_
                        break
                time.sleep(0.005)
            assert victim is not None, {
                "status": {k: {f: (h.last_status or {}).get(f) for f in
                               ("running", "prefilling", "queue_depth",
                                "finished", "draining", "healthy")}
                           for k, h in fleet.replicas.items()},
                "queued": len(fleet._queue),
                "inflight": {k: v.get("replica")
                             for k, v in fleet._inflight.items()},
                "done": len(fleet.results), "of": len(expected)}
            assert fleet.scale_in(victim, reason="test") == victim
            assert fleet.run(timeout=240)
            deadline = time.monotonic() + 120
            while victim in fleet.replicas and time.monotonic() < deadline:
                fleet.tick()
                time.sleep(0.05)
            assert victim not in fleet.replicas
            if fleet.migrations_completed > before_mig:
                drained = True
                break
            # the victim's work finished before a migration could land;
            # restore two-replica capacity and try again with fresh work
            fleet.scale_out(reason="test_retry")
            deadline = time.monotonic() + 180
            while time.monotonic() < deadline and len(
                    [h for h in fleet.replicas.values()
                     if h.alive() and not h.retired]) < 2:
                fleet.tick()
                time.sleep(0.05)
        # the retiring replica's running work MOVED, not re-ran
        assert drained, "scale-in never migrated running work off the victim"

        # ---- every request finished, token-exact vs. the oracle
        for rid, (p, n) in expected.items():
            res = fleet.results[rid]
            assert res["state"] == "finished", (rid, res)
            assert res["tokens"] == ref(p, n), f"rid {rid} diverged"
        summary = fleet.shutdown()
    finally:
        fleet.shutdown(federate=False)
    sv = summary["serving"]
    assert sv["migrated_requests"] >= 1
    assert sv["migrate_seconds_total"] > 0
    assert sv["migrate_bytes_total"] > 0
    fm = summary["fleet"]["migrations"]
    assert fm["completed"] >= 2 and fm["failed"] >= 0
    assert fm["bytes"] > 0 and mig_rid in fm["migrated_rids"]


@pytest.mark.slow
def test_fleet_chaos_kill_migrate_scale_cycles_zero_failed(
        tmp_path, monkeypatch):
    """Chaos loop: kill -> migrate -> scale-in cycles (plus a SIGSTOP
    straggler that gets shed) under sustained load. Zero failed
    requests, no stuck scheduler/pool state on any survivor, and every
    greedy output identical to the single-replica oracle."""
    from paddle_tpu.distributed.fleet.elastic.fault_injection import (
        kill_replica, pause_replica, resume_replica)
    from paddle_tpu.models.gpt import GPTForPretraining, GPTModel
    from paddle_tpu.serving.fleet import FleetRouter
    _drain_env(monkeypatch)
    monkeypatch.setenv("PADDLE_FLEET_MIGRATE_CHUNK_BYTES", "4096")
    monkeypatch.setenv("PADDLE_FLEET_POLL_TIMEOUT_S", "1")
    monkeypatch.setenv("PADDLE_FLEET_STRAGGLER_POLLS", "2")
    cfg = _fleet_cfg()
    paddle.seed(13)
    model = GPTForPretraining(GPTModel(cfg))
    ref = _oracle(model)
    ckpt = str(tmp_path / "gpt.pdparams")
    paddle.save(model.state_dict(), ckpt)
    rng = np.random.default_rng(9)
    prompts = _shared_prompts(cfg, 8, rng)

    fleet = FleetRouter(cfg, checkpoint=ckpt, n_replicas=2,
                        policy="round_robin",
                        engine_kwargs=dict(ENGINE_KW),
                        run_dir=str(tmp_path / "run"), seed=13,
                        max_restarts=6)
    expected = {}

    def submit_batch(n_new):
        for i in range(n_new):
            p = prompts[i % len(prompts)]
            rid = fleet.submit(p, max_new_tokens=12)
            expected[rid] = (p, 12)

    def live_replicas():
        return [r for r, h in fleet.replicas.items()
                if h.alive() and not h.retired and not h.draining]

    try:
        fleet.start()
        submit_batch(4)
        assert fleet.run(timeout=240)      # warm both caches
        for cycle in range(2):
            # kill a loaded replica
            submit_batch(5)
            deadline = time.monotonic() + 240
            killed = None
            while killed is None and time.monotonic() < deadline:
                fleet.tick()
                target = next(
                    (rec["replica"] for rec in fleet._inflight.values()
                     if rec.get("replica") is not None), None)
                if target is not None:
                    kill_replica(fleet, target)
                    killed = target
                time.sleep(0.005)
            assert killed is not None
            assert fleet.run(timeout=300)
            # best-effort live migration of a fresh mid-decode request
            deadline = time.monotonic() + 180
            while len(live_replicas()) < 2 \
                    and time.monotonic() < deadline:
                fleet.tick()
                time.sleep(0.05)
            rid = fleet.submit(prompts[cycle], max_new_tokens=24)
            expected[rid] = (prompts[cycle], 24)
            deadline = time.monotonic() + 90
            while rid not in fleet.results \
                    and time.monotonic() < deadline:
                fleet.tick()
                if fleet.migrate(rid).get("migrated"):
                    break
                time.sleep(0.005)
            assert fleet.run(timeout=240)
            # scale-in (drain-by-migrate) then restore the pair
            if len(live_replicas()) >= 2:
                submit_batch(3)
                retired = fleet.scale_in(reason="chaos")
                assert retired is not None
                assert fleet.run(timeout=300)
                deadline = time.monotonic() + 120
                while retired in fleet.replicas \
                        and time.monotonic() < deadline:
                    fleet.tick()
                    time.sleep(0.05)
                assert retired not in fleet.replicas
            if len(live_replicas()) < 2:
                fleet.scale_out(reason="chaos")
        # straggler: SIGSTOP one replica under load; supervision sheds
        # its in-flight work after consecutive poll misses, SIGCONT
        # makes any duplicate completion harmless (rid idempotency)
        deadline = time.monotonic() + 180
        while len(live_replicas()) < 2 and time.monotonic() < deadline:
            fleet.tick()
            time.sleep(0.05)
        if len(live_replicas()) >= 2:
            submit_batch(4)
            fleet.tick()
            wedged = live_replicas()[0]
            pause_replica(fleet, wedged)
            deadline = time.monotonic() + 60
            while not fleet.shed_events \
                    and time.monotonic() < deadline:
                fleet.tick()
                time.sleep(0.05)
            resume_replica(fleet, wedged)
            assert fleet.shed_events
            assert fleet.shed_events[-1]["reason"] == "wedged"
            assert fleet.run(timeout=300)

        # zero failed requests, token-exact vs. the oracle
        assert len(fleet.results) >= len(expected)
        for rid, (p, n) in expected.items():
            res = fleet.results[rid]
            assert res["state"] == "finished", (rid, res)
            assert res["tokens"] == ref(p, n)
        # no stuck migration/scheduler state or leaked work anywhere
        fleet.tick()
        for h in fleet.replicas.values():
            st = h.last_status or {}
            if not st:
                continue
            assert st.get("queue_depth") == 0
            assert st.get("running") == 0 and st.get("prefilling") == 0
            assert st.get("migrating_out") == 0
            assert st.get("migrating_in") == 0
        fleet.shutdown()
    finally:
        fleet.shutdown(federate=False)
