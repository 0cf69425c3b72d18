"""Fleet serving: prefix-affinity router over N engine replicas.

Coverage:

- routing policy (pure): affinity key / rendezvous stability under
  membership change, least-loaded fallback, round-robin baseline;
- autoscaler policy (pure, fake clock): scale-out fires only on a
  SUSTAINED recorded burn series, cooldown gates, idle scale-in;
- scheduler drain state + /healthz "draining" (satellite);
- federated folding + doctor fleet section: router_queue bucket sums
  exactly, straggler replica named, fixture-dir CLI gate rc=0;
- the fleet-predicted anchor (per-replica roofline x N);
- REAL fleets (replica processes via distributed.spawn): end-to-end
  shared-prefix serving with from_checkpoint warm start + federation +
  fleet /status + federated /metrics + drain-then-retire scale-in, and
  the ACCEPTANCE replica-SIGKILL-under-load test (goodput recovers,
  zero failed requests, requeued rids in the fleet requests stream).
"""
import glob
import json
import os
import shutil
import time
import urllib.request

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.models.gpt import gpt_tiny_config
from paddle_tpu.serving.router import (PrefixAffinityRouter, SLOAutoscaler,
                                       affinity_key, rendezvous_order)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURE = os.path.join(REPO, "tests", "fixtures", "fleet_doctor_run")


def _fleet_cfg():
    return gpt_tiny_config(num_layers=2, hidden_size=32, num_heads=2,
                           max_position_embeddings=64)


ENGINE_KW = dict(page_size=8, decode_buckets=(1, 2, 4, 8),
                 prefill_chunk=8, prefix_cache=True)


# ===========================================================================
# routing policy (pure)
# ===========================================================================

def _snap(**kw):
    d = {"healthy": True, "draining": False, "queue_depth": 0,
         "pending": 0, "free_pages": 50, "num_pages": 64}
    d.update(kw)
    return d


def test_affinity_key_is_page_block_granular():
    a = affinity_key([1, 2, 3, 4, 5, 6], 4)
    b = affinity_key([1, 2, 3, 4, 9, 9, 9], 4)   # same first block
    c = affinity_key([1, 2, 3, 5, 5, 6], 4)      # diverges inside block
    assert a == b and a != c


def test_rendezvous_stable_under_membership_change():
    """Removing a replica must only remap keys IT owned — every other
    key keeps its winner (the property that preserves cache affinity
    through elastic scale-in/out)."""
    keys = [affinity_key([i, i + 1, i + 2], 3) for i in range(64)]
    owner4 = {k: rendezvous_order(k, [0, 1, 2, 3])[0] for k in keys}
    owner3 = {k: rendezvous_order(k, [0, 1, 2])[0] for k in keys}
    moved = [k for k in keys if owner4[k] != owner3[k]]
    # only keys owned by the removed replica 3 may move
    assert all(owner4[k] == 3 for k in moved)
    assert any(owner4[k] == 3 for k in keys)
    # and they move to their rendezvous runner-up
    for k in moved:
        assert owner3[k] == rendezvous_order(k, [0, 1, 2, 3])[1]


def test_affinity_routes_same_prefix_together_and_falls_back():
    r = PrefixAffinityRouter(block_tokens=4, max_queue_depth=4)
    snaps = {0: _snap(), 1: _snap()}
    prompt = np.arange(10)
    first = r.route(prompt, snaps)
    assert all(r.route(prompt, snaps) == first for _ in range(5))
    assert r.last_outcome == "affinity"
    # saturate the preferred replica: fall back to the least-loaded one
    snaps[first]["queue_depth"] = 4
    other = 1 - first
    snaps[other]["pending"] = 1
    assert r.route(prompt, snaps) == other
    assert r.last_outcome == "fallback" and r.fallbacks == 1
    # draining replicas are never routed to; none eligible -> None
    snaps[first]["queue_depth"] = 0
    snaps[first]["draining"] = True
    snaps[other]["draining"] = True
    assert r.route(prompt, snaps) is None
    st = r.stats()
    assert st["routed"] == 7 and st["affinity_hits"] == 6


def test_least_loaded_and_round_robin_policies():
    rr = PrefixAffinityRouter(policy="round_robin")
    snaps = {0: _snap(), 1: _snap(), 2: _snap()}
    assert [rr.route([1], snaps) for _ in range(6)] == [0, 1, 2, 0, 1, 2]
    ll = PrefixAffinityRouter(policy="least_loaded")
    snaps[0]["pending"] = 5
    snaps[1]["pending"] = 1
    snaps[2]["pending"] = 1
    snaps[2]["free_pages"] = 60          # emptier pool breaks the tie
    assert ll.route([1], snaps) == 2
    with pytest.raises(ValueError):
        PrefixAffinityRouter(policy="bogus")


# ===========================================================================
# autoscaler policy (pure, fake clock / recorded burn series)
# ===========================================================================

def test_autoscaler_scale_out_on_sustained_burn_only():
    """ACCEPTANCE (policy half): a recorded burn series on a fake clock
    — one hot sample does NOT scale; a burn sustained past sustain_s
    does, exactly once per cooldown window."""
    a = SLOAutoscaler(min_replicas=1, max_replicas=4, scale_out_burn=1.0,
                      sustain_s=2.0, idle_s=10.0, cooldown_s=5.0,
                      clock=lambda: 0.0)
    # blip: hot for one sample, then cool — never fires
    assert a.observe(2, 3.0, True, now=0.0)["action"] is None
    assert a.observe(2, 0.1, True, now=1.0)["action"] is None
    # recorded sustained-burn series: hot from t=2 .. t=5
    actions = []
    for t, burn in [(2.0, 1.5), (3.0, 1.8), (4.0, 2.2), (4.5, 2.0),
                    (5.0, 1.9)]:
        actions.append(a.observe(2, burn, True, now=t)["action"])
    assert actions[:2] == [None, None]          # window not covered yet
    assert "scale_out" in actions
    fired_at = actions.index("scale_out")
    # cooldown: everything after the firing within 5s stays None
    assert all(x is None for x in actions[fired_at + 1:])
    # still burning after cooldown: fires again, capped at max_replicas
    d = a.observe(3, 2.0, True, now=11.0)
    assert d["action"] == "scale_out"
    assert a.observe(4, 2.0, True, now=17.0)["action"] is None  # at max
    assert len(a.decisions) == 2


def test_autoscaler_router_queue_counts_as_burn():
    """A saturated router queue is future burn — scale-out must fire
    even before the replica SLO windows have enough samples."""
    a = SLOAutoscaler(max_replicas=2, sustain_s=1.0, cooldown_s=99.0)
    a.observe(1, 0.0, True, router_queue_depth=5, now=0.0)
    d = a.observe(1, 0.0, True, router_queue_depth=5, now=1.1)
    assert d["action"] == "scale_out"


def test_autoscaler_scale_in_after_idle_window():
    a = SLOAutoscaler(min_replicas=1, max_replicas=4, idle_s=4.0,
                      idle_burn=0.25, cooldown_s=1.0)
    assert a.observe(2, 0.0, False, now=0.0)["action"] is None
    assert a.observe(2, 0.0, False, now=2.0)["action"] is None
    d = a.observe(2, 0.1, False, now=4.5)
    assert d["action"] == "scale_in" and "idle" in d["reason"]
    # at min_replicas: never scales below the floor
    a2 = SLOAutoscaler(min_replicas=1, idle_s=1.0, cooldown_s=0.0)
    a2.observe(1, 0.0, False, now=0.0)
    assert a2.observe(1, 0.0, False, now=2.0)["action"] is None
    # busy samples inside the window block scale-in
    a3 = SLOAutoscaler(min_replicas=1, idle_s=4.0, cooldown_s=0.0)
    a3.observe(2, 0.0, False, now=0.0)
    a3.observe(2, 0.0, True, now=2.0)
    assert a3.observe(2, 0.0, False, now=4.5)["action"] is None
    with pytest.raises(ValueError):
        SLOAutoscaler(min_replicas=3, max_replicas=2)


# ===========================================================================
# scheduler drain + /healthz draining (satellite)
# ===========================================================================

def test_scheduler_drain_rejects_new_and_healthz_reports_draining():
    from paddle_tpu.serving.scheduler import (ContinuousBatchingScheduler,
                                              _ShapeProbeEngine)
    eng = _ShapeProbeEngine(decode_buckets=(1, 2), prefill_buckets=(8, 32),
                            page_size=8, num_pages=32, max_seq_len=32)
    sched = ContinuousBatchingScheduler(eng)
    r0 = sched.submit(np.zeros(6, np.int32), 3)
    sched.drain()
    r1 = sched.submit(np.zeros(6, np.int32), 3)
    assert r1.state == "rejected" and r1.reject_reason == "draining"
    assert sched.status()["draining"] is True
    srv = sched.serve_http(port=0)
    try:
        with urllib.request.urlopen(srv.url + "/healthz", timeout=5) as rsp:
            assert rsp.status == 200
            assert rsp.read().decode().strip() == "draining"
    finally:
        srv.close()
    # draining still FINISHES in-flight work (drain-then-retire contract)
    sched.run()
    assert r0.state == "finished" and len(r0.tokens) == 3


def test_scheduler_submit_threads_global_rid_and_router_wait():
    from paddle_tpu.serving.scheduler import (ContinuousBatchingScheduler,
                                              _ShapeProbeEngine)
    eng = _ShapeProbeEngine(decode_buckets=(1, 2), prefill_buckets=(8, 32),
                            page_size=8, num_pages=32, max_seq_len=32)
    sched = ContinuousBatchingScheduler(eng)
    r = sched.submit(np.zeros(4, np.int32), 2, rid=1234,
                     router_wait_s=0.25)
    assert r.rid == 1234
    sched.run()
    s = r.summary()
    assert s["rid"] == 1234 and s["router_wait_s"] == 0.25


# ===========================================================================
# federated folding + doctor fleet section
# ===========================================================================

def _fleet_records():
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
    return recs


def test_fold_per_replica_and_router_wait_totals():
    from paddle_tpu.observability.reqtrace import fold_request_records
    sv = fold_request_records(_fleet_records())
    assert sv["router_wait_seconds_total"] == pytest.approx(0.3)
    per = sv["per_replica"]
    assert set(per) == {"0", "1"}
    assert per["0"]["requests"] == 3 and per["0"]["new_tokens"] == 24
    assert per["1"]["per_token_s_mean"] == pytest.approx(0.030)
    # single-replica records: no per_replica section
    single = fold_request_records(
        [r for r in _fleet_records() if r["rank"] == 0])
    assert "per_replica" not in single


def test_serving_attribution_router_queue_bucket_sums_exactly():
    from paddle_tpu.observability.doctor import attribute_serving_gap
    from paddle_tpu.observability.reqtrace import fold_request_records
    summary = {"serving": fold_request_records(_fleet_records()),
               "compile": {"seconds": 0.48}}
    pred = {"predicted_decode_step_ms": 5.0,
            "predicted_per_token_ms_p50": 5.0}
    attr = attribute_serving_gap(summary, pred)
    assert "router_queue" in attr["buckets"]
    assert attr["buckets"]["router_queue"] == pytest.approx(
        0.3 / 48 * 1e3, abs=1e-6)
    assert sum(attr["buckets"].values()) == pytest.approx(
        attr["delta_ms"], abs=1e-6)
    # fleet section names the straggler replica (0.030 vs median 0.020)
    fleet = attr["fleet"]
    assert fleet["replicas"] == 2
    assert fleet["straggler"]["replica"] == "1"
    assert fleet["straggler"]["skew"] == pytest.approx(1.5)
    # a router-less single-replica run keeps the classic 4-bucket shape
    solo = [dict(r, router_wait_s=0.0) for r in _fleet_records()
            if r["rank"] == 0]
    attr1 = attribute_serving_gap(
        {"serving": fold_request_records(solo)}, pred)
    assert set(attr1["buckets"]) == {"queue", "prefill", "compile",
                                     "decode"}
    assert "fleet" not in attr1


def test_perf_doctor_cli_fleet_fixture_gate(tmp_path, capsys):
    """Tier-1 gate: the checked-in federated fleet fixture diagnoses
    rc=0 with the router_queue bucket, the named straggler replica, and
    the relaunch accounted — without writing into the fixture."""
    from tools.perf_doctor import main as doctor_main
    assert doctor_main([FIXTURE, "--no-write"]) == 0
    out = capsys.readouterr().out
    assert "router_queue" in out
    assert "straggler_replica" in out or "fleet straggler" in out
    assert not os.path.exists(os.path.join(FIXTURE, "run_summary.json"))
    assert doctor_main([FIXTURE, "--no-write", "--json"]) == 0
    doc = json.loads(capsys.readouterr().out)
    sattr = doc["serving_attribution"]
    assert sum(sattr["buckets"].values()) == pytest.approx(
        sattr["delta_ms"], abs=0.01)
    assert sattr["fleet"]["straggler"]["replica"] == "2"
    assert doc["summary"]["restarts"] == 1
    kinds = {f["kind"] for f in doc["findings"]}
    assert "straggler_replica" in kinds


# ===========================================================================
# fleet-predicted anchor
# ===========================================================================

def test_predicted_fleet_row_shape_and_orderings():
    from paddle_tpu.serving.predict import predicted_fleet_row
    row = predicted_fleet_row("tiny", replicas=2, n_requests=16,
                              concurrency=8, prompt_len=48,
                              shared_fraction=0.75, max_new=8,
                              prefill_chunk=16, page_size=16)
    assert row["predicted_tokens_per_sec"] > 0
    # affinity >= round robin (more cache hits, same roofline)
    assert row["predicted_tokens_per_sec"] \
        >= row["predicted_tokens_per_sec_round_robin"]
    assert row["predicted_affinity_speedup_vs_round_robin"] >= 1.0
    assert row["predicted_prefix_hit_rate"] \
        > row["predicted_prefix_hit_rate_round_robin"]
    assert row["predicted_ttft_ms_mean"] \
        <= row["predicted_ttft_ms_mean_round_robin"]
    assert row["predicted_ttft_ms_hit"] < row["predicted_ttft_ms_miss"]
    # N replicas beat one replica on the same workload
    assert row["predicted_tokens_per_sec"] \
        > row["predicted_tokens_per_sec_single_replica"]
    assert 0 < row["predicted_scaling_efficiency"] <= 1.2


# ===========================================================================
# real fleets (replica processes)
# ===========================================================================

def _drain_env(monkeypatch, tmp_path):
    # fleet replicas inherit the parent env; make sure a pytest-level
    # telemetry dir never leaks into the fleet run dir
    monkeypatch.delenv("PADDLE_TELEMETRY_DIR", raising=False)
    monkeypatch.delenv("PADDLE_REQUESTS_PER_RANK", raising=False)


def test_fleet_end_to_end_warm_start_federation_and_drain_retire(
        tmp_path, monkeypatch):
    """One real 2-replica fleet, end to end: from_checkpoint warm start,
    shared-prefix workload routed with affinity (aggregate prefix hit
    rate > 0 in the FEDERATED pool stats), fleet /status + federated
    /metrics over HTTP, then scale-in mid-load — drain-then-retire
    finishes every in-flight request before the replica goes away."""
    from paddle_tpu.models.gpt import GPTForPretraining, GPTModel
    from paddle_tpu.serving.fleet import FleetRouter
    from paddle_tpu.serving.prefix_cache import make_shared_prefix_workload
    _drain_env(monkeypatch, tmp_path)
    cfg = _fleet_cfg()
    paddle.seed(7)
    ckpt = str(tmp_path / "gpt.pdparams")
    paddle.save(GPTForPretraining(GPTModel(cfg)).state_dict(), ckpt)

    fleet = FleetRouter(cfg, checkpoint=ckpt, n_replicas=2,
                        engine_kwargs=dict(ENGINE_KW),
                        run_dir=str(tmp_path / "run"),
                        slo={"ttft_p95_s": 60.0}, seed=7)
    try:
        fleet.start()
        prompts = make_shared_prefix_workload(cfg.vocab_size, 9, 16, 4,
                                              n_prefixes=3, seed=2)
        rids = [fleet.submit(p, max_new_tokens=4) for p in prompts]
        assert fleet.run(timeout=180)
        assert all(fleet.results[r]["state"] == "finished" for r in rids)
        assert all(len(fleet.results[r]["tokens"]) == 4 for r in rids)

        status = fleet.fleet_status()
        assert status["healthy"] and status["n_replicas"] == 2
        # affinity fed the prefix caches: federated hit accounting
        agg = status["pool_aggregate"]
        assert agg["prefix_hits"] > 0 and agg["prefix_hit_rate"] > 0.5
        assert status["routing"]["policy"] == "affinity"
        assert status["routing"]["routed"] >= 9

        # fleet endpoint: /status JSON + federated /metrics with
        # replica-relabeled series
        srv = fleet.serve_http()
        try:
            with urllib.request.urlopen(srv.url + "/status",
                                        timeout=10) as rsp:
                doc = json.loads(rsp.read().decode())
            assert set(doc["replicas"]) == {"0", "1"}
            assert doc["pool_aggregate"]["prefix_hits"] > 0
            with urllib.request.urlopen(srv.url + "/metrics",
                                        timeout=10) as rsp:
                expo = rsp.read().decode()
            assert 'replica="0"' in expo and 'replica="1"' in expo
            assert "paddle_serving_requests_total" in expo
            with urllib.request.urlopen(srv.url + "/healthz",
                                        timeout=10) as rsp:
                assert rsp.read().decode().strip() == "ok"
        finally:
            srv.close()

        # scale-in WITH work in flight: drain-then-retire must complete
        # everything before the replica retires
        more = [fleet.submit(p, max_new_tokens=4) for p in prompts]
        retired = fleet.scale_in(reason="test")
        assert retired is not None
        assert fleet.run(timeout=180)
        assert all(fleet.results[r]["state"] == "finished" for r in more)
        deadline = time.monotonic() + 60
        while retired in fleet.replicas and time.monotonic() < deadline:
            fleet.tick()
            time.sleep(0.05)
        assert retired not in fleet.replicas
        assert len(fleet.replicas) == 1

        summary = fleet.shutdown()
    finally:
        fleet.shutdown(federate=False)
    # federation: one run_summary over every replica's streams
    assert os.path.exists(os.path.join(fleet.run_dir, "run_summary.json"))
    sv = summary["serving"]
    assert sv["finished"] == 18
    assert sv["cached_prefix_tokens_total"] > 0
    assert summary["fleet"]["replicas_launched"] == 2
    assert summary["fleet"]["router"]["policy"] == "affinity"
    assert summary["fleet"]["router_results"] == {"finished": 18}
    assert summary["fleet"]["restarts"] == 0
    ev = summary["events"]
    assert ev.get("replica_start") == 2
    assert ev.get("fleet_scale") == 1 and ev.get("replica_retired") == 1


def test_fleet_replica_sigkill_under_load_zero_failed_requests(
        tmp_path, monkeypatch):
    """ACCEPTANCE: SIGKILL a replica under sustained load. Goodput
    recovers (every submitted request finishes), ZERO failed requests,
    the re-enqueued rids are visible in the fleet requests stream, and
    the federated summary counts the relaunch."""
    from paddle_tpu.distributed.fleet.elastic.fault_injection import \
        kill_replica
    from paddle_tpu.serving.fleet import FleetRouter
    _drain_env(monkeypatch, tmp_path)
    cfg = _fleet_cfg()
    fleet = FleetRouter(cfg, n_replicas=2,
                        engine_kwargs=dict(ENGINE_KW),
                        run_dir=str(tmp_path / "run"), seed=0,
                        max_restarts=3)
    rng = np.random.default_rng(0)
    try:
        fleet.start()
        rids, killed = [], False
        n_total = 14
        deadline = time.monotonic() + 240
        while len(fleet.results) < n_total:
            assert time.monotonic() < deadline, (
                f"stalled: {len(fleet.results)}/{n_total} done, "
                f"outstanding={fleet.outstanding}")
            if len(rids) < n_total:
                p = rng.integers(0, cfg.vocab_size, (12,)).astype(np.int32)
                rids.append(fleet.submit(p, max_new_tokens=6))
            fleet.tick()
            if not killed and len(fleet.results) >= 2 and fleet._inflight:
                target = next(
                    (rec["replica"] for rec in fleet._inflight.values()
                     if rec.get("replica") is not None), None)
                if target is not None:
                    kill_replica(fleet, target)
                    killed = True
            time.sleep(0.01)
        assert killed
        states = {fleet.results[r]["state"] for r in rids}
        assert states == {"finished"}          # zero failed requests
        assert all(len(fleet.results[r]["tokens"]) == 6 for r in rids)
        assert fleet.restarts >= 1
        assert fleet.requeued_rids              # work WAS in flight
        summary = fleet.shutdown()
    finally:
        fleet.shutdown(federate=False)
    assert summary["restarts"] >= 1
    assert summary["fleet"]["requeued_rids"] == sorted(
        set(fleet.requeued_rids))
    assert summary["fleet"]["router_results"] == {"finished": 14}
    # the federated run dir carries every request's terminal record and
    # the requeue black-box lines naming the survived rids
    lines = []
    for path in glob.glob(os.path.join(fleet.run_dir, "requests*.jsonl")):
        with open(path) as f:
            lines += [json.loads(ln) for ln in f if ln.strip()]
    requeue = [r for r in lines if r.get("event") == "request_requeue"]
    assert {r["rid"] for r in requeue} == set(fleet.requeued_rids)
    finished = {r["rid"] for r in lines
                if r.get("event") == "request" and
                r.get("state") == "finished"}
    assert finished == set(rids)
