"""Overload control & graceful degradation (ISSUE 19).

Coverage:

- request deadlines: expiry cancels wherever the request lives
  (queued / mid-prefill / mid-decode) through ONE terminal path, with
  exact page reclamation (zero leaked pages) and its own terminal
  state + counter; explicit ``cancel(rid)`` takes the same path;
- cost-aware admission: the old binary ``queue_full`` is gone — a
  capacity reject is priced against the observed drain rate and
  carries a machine-readable ``retry_after_s`` (env-cappable);
- brownout state machine: ``healthy → brownout → shedding`` on SLO
  burn rates with hysteretic exits; brownout halves completion
  budgets, prefers cache hits at admission, pauses background hooks;
  shedding rejects cache-miss traffic with ``shed`` + retry hint;
- SLO / folding / doctor: ``deadline_exceeded`` and priced rejects
  are their OWN terminal outcomes (never goodput), degraded decode
  time becomes the doctor's ``degraded`` bucket and the buckets still
  sum EXACTLY; the checked-in fleet fixture gates it at rc=0;
- router circuit breaker: consecutive RPC failures open it, routing
  skips the replica, the supervision poll is the half-open probe;
- ChaosProxy: deterministic seeded fault schedule, scripted fault
  behaviors (drop / delay / duplicate / truncate / bitflip);
- ACCEPTANCE: a real 2-replica fleet behind ChaosProxy (seeded drops
  + delays + one corrupted migration chunk) with deadlines on every
  request — every request reaches a terminal state, zero hangs, zero
  leaked KV pages, breaker open/close observed;
- a slow-marked chaos loop combining proxy faults with SIGSTOP /
  SIGKILL process faults.
"""
import json
import os
import socket
import threading
import time
import types

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.distributed.fleet.elastic.fault_injection import ChaosProxy
from paddle_tpu.models.gpt import gpt_tiny_config
from paddle_tpu.serving.scheduler import (ContinuousBatchingScheduler,
                                          _ShapeProbeEngine)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURE = os.path.join(REPO, "tests", "fixtures", "fleet_doctor_run")


def _probe_sched(max_queue=1024, slo=None, num_pages=40, max_seq_len=64,
                 prefill_chunk=None, **kw):
    eng = _ShapeProbeEngine(decode_buckets=(1, 2, 4),
                            prefill_buckets=(8, 64), page_size=8,
                            num_pages=num_pages, max_seq_len=max_seq_len,
                            prefill_chunk=prefill_chunk)
    return ContinuousBatchingScheduler(eng, max_queue=max_queue, slo=slo,
                                       **kw)


class _FakeSLO:
    """Controllable burn-rate source with the tracker surface the
    scheduler touches."""

    def __init__(self, burn=0.0):
        self.burn = burn
        self.terminal_states = []

    def burn_rates(self):
        return {"ttft_p95_s": self.burn}

    def observe_request(self, summary):
        self.terminal_states.append(summary.get("state"))
        return summary.get("state") == "finished"

    def observe_admission(self, *a, **kw):
        pass

    def observe_tokens(self, *a, **kw):
        pass

    def snapshot(self):
        return {"burn_rates": self.burn_rates()}


# ===========================================================================
# deadlines: expiry + explicit cancel, exact page reclamation
# ===========================================================================

def test_deadline_expires_queued_request():
    sched = _probe_sched()
    free0 = sched.engine.pool.free_pages
    r = sched.submit(np.zeros(8, np.int32), 4, deadline_s=0.005)
    assert r.deadline_s == 0.005
    time.sleep(0.02)
    sched.step()
    assert r.state == "deadline_exceeded"
    assert r.finish_time is not None
    assert sched.engine.pool.free_pages == free0
    assert sched._reserved_pages == 0
    assert sched.deadline_cancelled == 1
    assert sched.status()["deadline_exceeded"] == 1
    # the terminal record reaches request_records() like any other
    recs = sched.request_records()
    assert recs[-1]["state"] == "deadline_exceeded"


def test_deadline_expires_running_request_and_reclaims_pages():
    sched = _probe_sched()
    pool = sched.engine.pool
    free0 = pool.free_pages
    r = sched.submit(np.zeros(8, np.int32), 30, deadline_s=0.03)
    sched.step()                               # admit + prefill + decode
    assert r.state == "running" and len(r.tokens) >= 1
    time.sleep(0.05)
    sched.step()                               # sweep cancels mid-decode
    assert r.state == "deadline_exceeded"
    assert pool.free_pages == free0            # zero leaked pages
    assert sched._reserved_pages == 0
    assert not sched.pending
    # the span records where the cancel landed and the wasted tokens
    last = r.trace.spans[-1]
    assert last["phase"] == "deadline_exceeded"
    assert last["cancelled_in"] == "running"


def test_deadline_expires_mid_prefill_chunked():
    sched = _probe_sched(prefill_chunk=8, prefill_token_budget=8)
    pool = sched.engine.pool
    free0 = pool.free_pages
    r = sched.submit(np.zeros(40, np.int32), 4, deadline_s=0.03)
    sched.step()                               # one 8-token chunk of 40
    assert r.state == "prefilling"
    time.sleep(0.05)
    sched.step()
    assert r.state == "deadline_exceeded"
    assert r.trace.spans[-1]["cancelled_in"] == "prefilling"
    assert pool.free_pages == free0
    assert sched._reserved_pages == 0


def test_default_deadline_env_knob(monkeypatch):
    monkeypatch.setenv("PADDLE_FLEET_DEADLINE_DEFAULT_S", "2.5")
    sched = _probe_sched()
    r = sched.submit(np.zeros(8, np.int32), 2)
    assert r.deadline_s == 2.5
    # explicit deadline wins over the default
    r2 = sched.submit(np.zeros(8, np.int32), 2, deadline_s=9.0)
    assert r2.deadline_s == 9.0


def test_explicit_cancel_in_each_phase_and_unknown_rid():
    sched = _probe_sched(prefill_chunk=8, prefill_token_budget=8)
    pool = sched.engine.pool
    free0 = pool.free_pages
    rq = sched.submit(np.zeros(8, np.int32), 4)     # stays queued
    assert sched.cancel(rq.rid) is True
    assert rq.state == "deadline_exceeded"
    rp = sched.submit(np.zeros(40, np.int32), 4)
    sched.step()                                    # first chunk only
    assert rp.state == "prefilling"
    assert sched.cancel(rp.rid) is True
    rr = sched.submit(np.zeros(8, np.int32), 30)
    sched.step()
    sched.step()
    assert rr.state == "running"
    assert sched.cancel(rr.rid) is True
    assert pool.free_pages == free0
    assert sched._reserved_pages == 0
    # unknown / already-terminal rids refuse
    assert sched.cancel(99999) is False
    assert sched.cancel(rr.rid) is False
    assert sched.deadline_cancelled == 3


# ===========================================================================
# cost-aware admission: priced retry_after replaces queue_full
# ===========================================================================

def test_full_queue_reject_is_priced_retry_after():
    sched = _probe_sched(max_queue=0)
    r = sched.submit(np.zeros(8, np.int32), 4)
    assert r.state == "rejected" and r.reject_reason == "retry_after"
    assert isinstance(r.retry_after_s, float)
    assert 0.05 <= r.retry_after_s <= 30.0
    s = r.summary()
    assert s["reject_reason"] == "retry_after"
    assert s["retry_after_s"] == pytest.approx(r.retry_after_s, abs=1e-3)
    ov = sched.status()["overload"]
    assert ov["retry_after_s"] > 0
    assert "drain_rate_rps" in ov["admission_cost"]


def test_retry_after_tracks_observed_drain_rate():
    sched = _probe_sched()
    for i in range(5):
        sched.submit(np.zeros(8, np.int32), 2)      # backlog of 5
    t0 = time.perf_counter()
    sched._finish_ts.extend(t0 + 0.1 * i for i in range(5))
    # 4 completions over 0.4s -> 10 rps; 5 queued -> ~0.5s to drain
    assert sched._drain_rate() == pytest.approx(10.0, rel=0.01)
    assert sched._retry_after_estimate() == pytest.approx(0.5, abs=0.01)
    # an SLO burning its budget scales the hint up
    sched.slo = _FakeSLO(burn=3.0)
    assert sched._retry_after_estimate() == pytest.approx(1.5, abs=0.05)


def test_retry_after_cap_env_knob(monkeypatch):
    monkeypatch.setenv("PADDLE_FLEET_RETRY_AFTER_CAP_S", "0.25")
    sched = _probe_sched(max_queue=0)
    r = sched.submit(np.zeros(8, np.int32), 4)
    # no drain history: the estimate saturates at the cap, not at 30s
    assert r.retry_after_s == pytest.approx(0.25)


# ===========================================================================
# brownout state machine
# ===========================================================================

def test_brownout_mode_machine_with_hysteresis():
    sched = _probe_sched()
    fake = _FakeSLO(0.0)
    sched.slo = fake
    sched.step()
    assert sched.mode == "healthy"
    fake.burn = 1.0                     # at the brownout line
    sched.step()
    assert sched.mode == "brownout" and sched.mode_transitions == 1
    fake.burn = 0.8                     # above the 0.5 exit: holds
    sched.step()
    assert sched.mode == "brownout"
    fake.burn = 2.0                     # 2x: shedding
    sched.step()
    assert sched.mode == "shedding"
    fake.burn = 1.5                     # above brownout entry: holds
    sched.step()
    assert sched.mode == "shedding"
    fake.burn = 0.9                     # below entry: back to brownout
    sched.step()
    assert sched.mode == "brownout"
    fake.burn = 0.4                     # below half: healthy again
    sched.step()
    assert sched.mode == "healthy" and sched.mode_transitions == 4
    ms = sched.status()["overload"]["mode_seconds"]
    assert set(ms) == {"healthy", "brownout", "shedding"}


def test_brownout_burn_env_knob(monkeypatch):
    monkeypatch.setenv("PADDLE_FLEET_BROWNOUT_BURN", "3.0")
    sched = _probe_sched()
    sched.slo = _FakeSLO(2.0)
    sched.step()
    assert sched.mode == "healthy"      # 2.0 < the raised threshold
    sched.slo.burn = 3.5
    sched.step()
    assert sched.mode == "brownout"


def test_brownout_clamps_completion_budget_and_tracks_degraded_time():
    sched = _probe_sched()
    sched.slo = _FakeSLO(1.0)           # held in brownout throughout
    r = sched.submit(np.zeros(8, np.int32), 8)
    sched.run()
    assert r.state == "finished"
    assert len(r.tokens) == 4           # (8+1)//2: halved, floor 1
    assert sched.degraded_s_total > 0
    assert r.summary()["degraded_s"] > 0


def test_brownout_prefers_cache_hits_and_pauses_background():
    sched = _probe_sched()
    sched.max_concurrency = 1
    hits = types.SimpleNamespace(
        match=lambda prompt: (None, None, 8 if prompt[0] == 7 else 0),
        reclaim=lambda n_pages: 0)
    sched.engine.prefix_cache = hits
    calls = []
    sched.background_hooks.append(lambda: calls.append(1))
    sched.slo = _FakeSLO(1.0)           # brownout
    miss = sched.submit(np.zeros(8, np.int32), 2)
    hit = sched.submit(np.full(8, 7, np.int32), 2)
    sched.step()
    # the cached-prefix request jumped the (older) miss
    assert hit.state in ("running", "finished")
    assert miss.state == "queued"
    assert calls == []                  # background paused off-healthy
    sched.slo.burn = 0.0
    sched.run()
    assert miss.state == "finished"
    assert calls                        # resumed once healthy


def test_shedding_rejects_cache_misses_with_retry_hint():
    sched = _probe_sched()
    sched.slo = _FakeSLO(2.5)
    sched.step()                        # drive the mode machine
    assert sched.mode == "shedding"
    r = sched.submit(np.zeros(8, np.int32), 4)
    assert r.state == "rejected" and r.reject_reason == "shed"
    assert r.retry_after_s is not None
    # cache hits still get in: shedding protects goodput, not uptime
    sched.engine.prefix_cache = types.SimpleNamespace(
        match=lambda prompt: (None, None, 8), reclaim=lambda n_pages: 0)
    r2 = sched.submit(np.zeros(8, np.int32), 4)
    assert r2.state == "queued"


# ===========================================================================
# SLO / folding / doctor terminal accounting
# ===========================================================================

def test_slo_tracker_counts_new_terminal_outcomes_outside_goodput():
    from paddle_tpu.observability.slo import SLOConfig, SLOTracker
    t = SLOTracker(SLOConfig())
    assert t.observe_request({"state": "deadline_exceeded",
                              "new_tokens": 5}) is False
    assert t.observe_request({"state": "rejected", "new_tokens": 0,
                              "retry_after_s": 1.5}) is False
    snap = t.snapshot()
    assert snap["requests_deadline_exceeded"] == 1
    assert snap["requests_rejected"] == 1
    # wasted tokens count toward total, never toward goodput
    assert snap["total_tokens"] == 5
    assert snap["goodput_tokens"] == 0
    assert snap["requests_met"] == 0 and snap["requests_missed"] == 0


def test_fold_request_records_new_outcomes():
    from paddle_tpu.observability.reqtrace import fold_request_records
    recs = [
        {"event": "request", "state": "finished", "new_tokens": 8,
         "degraded_s": 0.2},
        {"event": "request", "state": "deadline_exceeded",
         "new_tokens": 3, "degraded_s": 0.1},
        {"event": "request", "state": "rejected",
         "reject_reason": "retry_after", "retry_after_s": 1.5,
         "new_tokens": 0},
    ]
    sv = fold_request_records(recs)
    assert sv["deadline_exceeded"] == 1
    assert sv["deadline_exceeded_tokens_total"] == 3
    assert sv["degraded_seconds_total"] == pytest.approx(0.3)
    assert sv["retry_after_s"]["count"] == 1
    assert sv["retry_after_s"]["p50"] == pytest.approx(1.5)
    assert sv["reject_reasons"] == {"retry_after": 1}


def test_doctor_degraded_bucket_sums_exactly():
    from paddle_tpu.observability.doctor import attribute_serving_gap
    sv = {"new_tokens_total": 100, "request_seconds_total": 2.0,
          "queue_wait_seconds_total": 0.1,
          "prefill_seconds_total": 0.2,
          "degraded_seconds_total": 0.35,
          "per_token_s": {"p50": 0.02}}
    attr = attribute_serving_gap({"serving": sv},
                                 {"predicted_per_token_ms_p50": 5.0})
    assert "degraded" in attr["buckets"]
    assert attr["buckets"]["degraded"] == pytest.approx(3.5)
    assert sum(attr["buckets"].values()) == pytest.approx(
        attr["delta_ms"], abs=1e-9)
    # without degraded time the bucket never appears
    sv2 = dict(sv, degraded_seconds_total=0.0)
    attr2 = attribute_serving_gap({"serving": sv2},
                                  {"predicted_per_token_ms_p50": 5.0})
    assert "degraded" not in attr2["buckets"]
    assert sum(attr2["buckets"].values()) == pytest.approx(
        attr2["delta_ms"], abs=1e-9)


def test_perf_doctor_cli_fixture_gates_overload_buckets(capsys):
    """The checked-in fleet fixture now carries deadline_exceeded +
    degraded-time records; the CLI gate stays rc=0 and surfaces both
    as findings without writing into the fixture."""
    from tools.perf_doctor import main as doctor_main
    assert doctor_main([FIXTURE, "--no-write"]) == 0
    out = capsys.readouterr().out
    assert "deadline" in out
    assert "degraded" in out
    assert not os.path.exists(os.path.join(FIXTURE, "run_summary.json"))
    assert doctor_main([FIXTURE, "--no-write", "--json"]) == 0
    doc = json.loads(capsys.readouterr().out)
    sattr = doc["serving_attribution"]
    assert "degraded" in sattr["buckets"]
    assert sum(sattr["buckets"].values()) == pytest.approx(
        sattr["delta_ms"], abs=0.01)
    assert doc["summary"]["serving"]["deadline_exceeded"] == 2
    kinds = {f["kind"] for f in doc["findings"]}
    assert "deadline_exceeded" in kinds


# ===========================================================================
# closure: cancellation replay adds zero program signatures
# ===========================================================================

def test_cancellation_mix_closure_no_new_signatures():
    from paddle_tpu.serving.scheduler import simulate_decode_signatures
    base_d, base_p, ok_d, ok_p = simulate_decode_signatures(
        (1, 2, 4), (8, 64), 8, 64, 64, n_requests=120, seed=0)
    cd, cp, okd_c, okp_c = simulate_decode_signatures(
        (1, 2, 4), (8, 64), 8, 64, 64, n_requests=120, seed=0,
        cancel_p=0.3)
    assert (okd_c, okp_c) == (ok_d, ok_p)
    assert cd <= ok_d and cp <= ok_p    # cancel = evict, no recompile
    # cancel_p=0 replays stay byte-identical to the golden stream
    again_d, again_p, _, _ = simulate_decode_signatures(
        (1, 2, 4), (8, 64), 8, 64, 64, n_requests=120, seed=0)
    assert (again_d, again_p) == (base_d, base_p)


# ===========================================================================
# router circuit breaker (unit: no processes)
# ===========================================================================

def test_breaker_opens_after_consecutive_failures_and_closes(
        tmp_path, monkeypatch):
    from paddle_tpu.serving.fleet import FleetRouter
    fr = FleetRouter(gpt_tiny_config(), n_replicas=2,
                     run_dir=str(tmp_path / "run"))
    h = types.SimpleNamespace(replica_id=0, rpc_failures=0,
                              breaker_open=False)
    fr._breaker_failure(h, op="submit")
    fr._breaker_failure(h, op="submit")
    assert not h.breaker_open           # below the default of 3
    fr._breaker_failure(h, op="submit")
    assert h.breaker_open
    assert [e["event"] for e in fr.breaker_events] == ["open"]
    # a success mid-streak resets the consecutive count
    fr._breaker_success(h)
    assert not h.breaker_open and h.rpc_failures == 0
    assert [e["event"] for e in fr.breaker_events] == ["open", "close"]
    # env knob: a single failure can open it
    monkeypatch.setenv("PADDLE_FLEET_BREAKER_FAILS", "1")
    fr._breaker_failure(h, op="poll")
    assert h.breaker_open
    ev = fr.breaker_events[-1]
    assert ev["event"] == "open" and ev["op"] == "poll"


def test_breaker_open_replica_is_not_routable(tmp_path):
    from paddle_tpu.serving.fleet import FleetRouter
    fr = FleetRouter(gpt_tiny_config(), n_replicas=2,
                     run_dir=str(tmp_path / "run"))

    def handle(rid, open_):
        return types.SimpleNamespace(
            replica_id=rid, rpc_failures=0, breaker_open=open_,
            retired=False, draining=False, poll_failures=0,
            alive=lambda: True,
            last_status={"healthy": True, "queue_depth": 0,
                         "kv_pool": {"free_pages": 10, "num_pages": 16}})
    fr.replicas = {0: handle(0, False), 1: handle(1, True)}
    snaps = fr._snapshots()
    assert snaps[0]["healthy"] is True
    assert snaps[1]["healthy"] is False


# ===========================================================================
# ChaosProxy (unit, against a local echo server)
# ===========================================================================

class _EchoServer:
    """One-line-in, one-line-out TCP echo upstream."""

    def __init__(self):
        self._srv = socket.create_server(("127.0.0.1", 0))
        self._srv.settimeout(0.25)
        self.addr = self._srv.getsockname()
        self.payloads = []
        self._closed = False
        self._t = threading.Thread(target=self._serve, daemon=True)
        self._t.start()

    def _serve(self):
        while not self._closed:
            try:
                conn, _ = self._srv.accept()
            except socket.timeout:
                continue
            except OSError:
                break
            threading.Thread(target=self._one, args=(conn,),
                             daemon=True).start()

    def _one(self, conn):
        try:
            with conn, conn.makefile("rwb") as f:
                line = f.readline()
                if line:
                    self.payloads.append(line)
                    f.write(line)
                    f.flush()
                    time.sleep(0.05)   # hold briefly so replies split
        except OSError:
            pass

    def close(self):
        self._closed = True
        self._srv.close()


def _roundtrip(addr, payload=b"hello chaos proxy roundtrip\n",
               timeout=5.0):
    """Client view of one proxied exchange. A dropped connection may
    surface as clean EOF or a reset depending on timing — both mean
    "dead peer, no reply", which is what the RPC layer sees too."""
    chunks = []
    try:
        with socket.create_connection(addr, timeout=timeout) as s:
            s.sendall(payload)
            s.settimeout(timeout)
            while True:
                d = s.recv(65536)
                if not d:
                    break
                chunks.append(d)
    except (socket.timeout, OSError):
        pass
    return b"".join(chunks)


def test_chaos_proxy_schedule_is_deterministic_in_seed():
    echo = _EchoServer()
    seqs = []
    for _ in range(2):
        with ChaosProxy(echo.addr, seed=5, drop_p=0.3, delay_p=0.3,
                        delay_s=0.01) as proxy:
            for _ in range(12):
                _roundtrip(proxy.addr, timeout=3.0)
            seqs.append(list(proxy.faults))
    echo.close()
    assert seqs[0] == seqs[1]
    assert len(seqs[0]) == 12
    drawn = {f for _, f in seqs[0]}
    assert "drop" in drawn or "delay" in drawn


def test_chaos_proxy_scripted_faults_behave():
    echo = _EchoServer()
    payload = b"0123456789abcdef0123456789abcdef\n"
    with ChaosProxy(echo.addr, seed=0, delay_s=0.2,
                    schedule=["ok", "delay", "duplicate", "truncate",
                              "bitflip", "drop"]) as proxy:
        assert _roundtrip(proxy.addr, payload) == payload
        t0 = time.monotonic()
        assert _roundtrip(proxy.addr, payload) == payload
        assert time.monotonic() - t0 >= 0.2            # delayed reply
        assert _roundtrip(proxy.addr, payload) == payload * 2
        got = _roundtrip(proxy.addr, payload)
        assert 0 < len(got) < len(payload)             # torn reply
        upstream_before = len(echo.payloads)
        got = _roundtrip(proxy.addr, payload)
        corrupted = echo.payloads[upstream_before]
        assert corrupted != payload                    # one bit flipped
        assert len(corrupted) == len(payload)
        assert sum(a != b for a, b in zip(corrupted, payload)) == 1
        assert _roundtrip(proxy.addr, payload, timeout=3.0) == b""
        assert [f for _, f in proxy.faults] == [
            "ok", "delay", "duplicate", "truncate", "bitflip", "drop"]
        assert proxy.fault_counts()["ok"] == 1
    echo.close()


# ===========================================================================
# ACCEPTANCE: chaos fleet — every request terminal, zero hangs,
# zero leaked pages, breaker observed, corrupted migration refused
# ===========================================================================

def _drain_env(monkeypatch):
    monkeypatch.delenv("PADDLE_TELEMETRY_DIR", raising=False)
    monkeypatch.delenv("PADDLE_REQUESTS_PER_RANK", raising=False)


def _fleet_cfg():
    return gpt_tiny_config(num_layers=2, hidden_size=32, num_heads=2,
                           max_position_embeddings=128)


CHAOS_ENGINE_KW = dict(page_size=8, decode_buckets=(1, 2, 4, 8),
                       prefill_chunk=8, prefix_cache=False)

TERMINAL = {"finished", "rejected", "deadline_exceeded"}


def test_chaos_fleet_acceptance(tmp_path, monkeypatch):
    """ACCEPTANCE (ISSUE 19): 2 replicas behind seeded ChaosProxies
    (drops + delays on the control plane, one scripted corrupted
    migration chunk), a deadline on EVERY request. Every request
    reaches a terminal state, nothing hangs, the KV pools drain to
    zero pages in use, and the breaker opens and closes."""
    from paddle_tpu.observability import lockwitness
    from paddle_tpu.serving.fleet import FleetRouter, _rpc_request
    _drain_env(monkeypatch)
    monkeypatch.setenv("PADDLE_FLEET_BREAKER_FAILS", "1")
    # ISSUE 20: the whole chaos scenario runs under the runtime lock
    # witness — at the end the witnessed lock-order graph must be
    # acyclic (the runtime complement of the PTCY001 static check).
    # The env must be set BEFORE the router exists so its named locks
    # construct as witnessed.
    monkeypatch.setenv("PADDLE_LOCK_WITNESS", "1")
    lockwitness.reset()
    cfg = _fleet_cfg()
    fleet = FleetRouter(cfg, n_replicas=2,
                        engine_kwargs=dict(CHAOS_ENGINE_KW),
                        run_dir=str(tmp_path / "run"), seed=0,
                        max_restarts=3)
    rng = np.random.default_rng(0)
    proxies = []
    real_addr = {}
    try:
        fleet.start()
        for rid, h in fleet.replicas.items():
            real_addr[rid] = h.rpc_addr
            p = ChaosProxy(h.rpc_addr, seed=100 + rid, drop_p=0.08,
                           delay_p=0.10, delay_s=0.05)
            proxies.append(p)
            h.rpc_addr = p.addr

        rids = []
        # sustained load with generous deadlines + two hopeless ones
        for i in range(10):
            p = rng.integers(0, cfg.vocab_size, (12,)).astype(np.int32)
            rids.append(fleet.submit(p, max_new_tokens=6,
                                     deadline_s=120.0))
        for _ in range(2):
            p = rng.integers(0, cfg.vocab_size, (12,)).astype(np.int32)
            rids.append(fleet.submit(p, max_new_tokens=40,
                                     deadline_s=0.01))
        deadline = time.monotonic() + 240
        while not all(r in fleet.results for r in rids):
            assert time.monotonic() < deadline, (
                f"hang: {sum(r in fleet.results for r in rids)}"
                f"/{len(rids)} terminal, outstanding={fleet.outstanding}")
            fleet.tick()
            time.sleep(0.01)

        states = {r: fleet.results[r]["state"] for r in rids}
        assert set(states.values()) <= TERMINAL
        assert sum(s == "finished" for s in states.values()) >= 8
        assert any(s == "deadline_exceeded" for s in states.values())

        # one corrupted migration chunk: scripted bitflip on the first
        # KV chunk — the checksum refuses it, the source aborts and
        # stays authoritative, the request still finishes
        src, dest = sorted(fleet.replicas)
        mig_refused = False
        long_rids = []
        for attempt in range(12):
            p = rng.integers(0, cfg.vocab_size, (12,)).astype(np.int32)
            gid = fleet.submit(p, max_new_tokens=64, deadline_s=120.0)
            long_rids.append(gid)
            for _ in range(50):
                fleet.tick()
                rec = fleet._inflight.get(gid)
                if rec is not None and rec.get("replica") is not None:
                    break
                if gid in fleet.results:
                    break
                time.sleep(0.01)
            rec = fleet._inflight.get(gid)
            if rec is None or rec.get("replica") is None:
                continue
            s, d = rec["replica"], None
            d = next(r for r in fleet.replicas if r != s)
            with ChaosProxy(real_addr[d],
                            schedule=["ok", "bitflip"]) as mig_proxy:
                reply = _rpc_request(
                    real_addr[s],
                    {"op": "migrate_out", "rid": gid,
                     "dest": list(mig_proxy.addr)},
                    timeout=30.0, retries=0)
            if reply.get("migrated") is False \
                    and reply.get("reason") not in (None, "not_running",
                                                    "engine_unsupported"):
                mig_refused = True
                break
        assert mig_refused, "corrupted-chunk refusal never exercised"
        deadline = time.monotonic() + 240
        while not all(r in fleet.results for r in long_rids):
            assert time.monotonic() < deadline
            fleet.tick()
            time.sleep(0.01)
        assert {fleet.results[r]["state"]
                for r in long_rids} <= TERMINAL

        # chaos actually happened + the breaker both opened and closed
        total_faults = {}
        for p in proxies:
            for k, v in p.fault_counts().items():
                total_faults[k] = total_faults.get(k, 0) + v
        assert total_faults.get("drop", 0) + total_faults.get(
            "delay", 0) > 0
        # the supervision poll is the half-open probe: keep ticking
        # until the opened breaker has also closed
        deadline = time.monotonic() + 60
        while {"open", "close"} - {e["event"]
                                   for e in fleet.breaker_events}:
            assert time.monotonic() < deadline, (
                f"breaker transitions missing: {fleet.breaker_events}")
            fleet.tick()
            time.sleep(0.02)
        st = fleet.fleet_status()
        assert st["overload"]["breakers"]
        assert st["overload"]["deadline_exceeded"] >= 1

        # zero leaked KV pages: with the prefix cache off, a fully
        # terminal fleet must return every page to its pools
        deadline = time.monotonic() + 60
        while True:
            fleet.tick()
            pools = [(h.last_status or {}).get("kv_pool") or {}
                     for h in fleet.replicas.values()]
            if pools and all(p.get("pages_in_use") == 0 for p in pools):
                break
            assert time.monotonic() < deadline, f"leaked pages: {pools}"
            time.sleep(0.05)
        assert fleet.outstanding == 0

        # lock witness: the run exercised real lock nesting, and the
        # witnessed graph has no lock-order cycle
        snap = lockwitness.snapshot()
        assert snap["waits"], "witness observed no lock activity"
        assert lockwitness.cycles() == [], (
            f"witnessed lock-order cycle: {lockwitness.cycles()} "
            f"(edges: {[(e['src'], e['dst']) for e in snap['edges']]})")
    finally:
        for rid, h in fleet.replicas.items():
            if rid in real_addr:
                h.rpc_addr = real_addr[rid]
        fleet.shutdown(federate=False)
        for p in proxies:
            p.close()
        lockwitness.reset()


@pytest.mark.slow
def test_chaos_loop_with_process_faults(tmp_path, monkeypatch):
    """Slow chaos loop: proxy faults + SIGSTOP straggler + SIGKILL,
    deadlines on every request — every request terminal, zero hangs."""
    from paddle_tpu.distributed.fleet.elastic.fault_injection import (
        kill_replica, pause_replica, resume_replica)
    from paddle_tpu.serving.fleet import FleetRouter
    _drain_env(monkeypatch)
    monkeypatch.setenv("PADDLE_FLEET_BREAKER_FAILS", "2")
    cfg = _fleet_cfg()
    fleet = FleetRouter(cfg, n_replicas=2,
                        engine_kwargs=dict(CHAOS_ENGINE_KW),
                        run_dir=str(tmp_path / "run"), seed=1,
                        max_restarts=6)
    rng = np.random.default_rng(1)
    proxies, real_addr = [], {}

    def interpose(rid, h):
        real_addr[rid] = h.rpc_addr
        p = ChaosProxy(h.rpc_addr, seed=200 + rid, drop_p=0.06,
                       delay_p=0.08, delay_s=0.04)
        proxies.append(p)
        h.rpc_addr = p.addr
    try:
        fleet.start()
        for rid, h in fleet.replicas.items():
            interpose(rid, h)
        rids, n_total = [], 30
        paused = killed = False
        pause_at, kill_at = 8, 16
        paused_rid = None
        deadline = time.monotonic() + 420
        while not (len(rids) == n_total
                   and all(r in fleet.results for r in rids)):
            assert time.monotonic() < deadline, (
                f"hang: {sum(r in fleet.results for r in rids)}"
                f"/{len(rids)}, outstanding={fleet.outstanding}")
            if len(rids) < n_total:
                p = rng.integers(0, cfg.vocab_size, (12,)).astype(
                    np.int32)
                rids.append(fleet.submit(p, max_new_tokens=6,
                                         deadline_s=90.0))
            fleet.tick()
            done = sum(r in fleet.results for r in rids)
            if not paused and done >= pause_at and fleet.replicas:
                paused_rid = sorted(fleet.replicas)[0]
                pause_replica(fleet, paused_rid)
                paused = True
            if paused and paused_rid in fleet.replicas \
                    and done >= pause_at + 4:
                try:
                    resume_replica(fleet, paused_rid)
                except Exception:
                    pass                    # already shed / relaunched
                paused_rid = None
            if not killed and done >= kill_at and fleet._inflight:
                target = next(
                    (rec["replica"] for rec in fleet._inflight.values()
                     if rec.get("replica") is not None), None)
                if target is not None:
                    kill_replica(fleet, target)
                    killed = True
            # a relaunched replica gets its own proxy
            for rid, h in fleet.replicas.items():
                if rid not in real_addr and h.rpc_addr is not None:
                    interpose(rid, h)
            time.sleep(0.01)
        assert killed
        states = {fleet.results[r]["state"] for r in rids}
        assert states <= TERMINAL
        assert sum(fleet.results[r]["state"] == "finished"
                   for r in rids) >= n_total // 2
    finally:
        for rid, h in fleet.replicas.items():
            if rid in real_addr:
                h.rpc_addr = real_addr[rid]
        fleet.shutdown(federate=False)
        for p in proxies:
            p.close()


if __name__ == "__main__":
    raise SystemExit(pytest.main([__file__, "-v"]))
