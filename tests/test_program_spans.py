"""The program's phase spans: one recorder (``profiler.utils.RecordEvent``),
kept exactly while a device trace is being taken or a ``Profiler``
records, a tree by ``parent_id``, on the device trace's clock too.

The traced scenario runs once (module fixture): a tiny chunked engine
with the prefix cache behind the scheduler, then a tiny
``GPTHybridTrainStep``, both under one ``jax.profiler.trace`` on the CPU
backend. The benchmark's new per-layer readers are fed from it through a
stand-in ``run``.
"""
import glob
import importlib.util
import json
import math
import os
import sys
import time
import types
from collections import deque

import numpy as np
import pytest
import jax

import paddle_tpu as paddle
from paddle_tpu.distributed import mesh as mesh_mod
from paddle_tpu.distributed.mesh import HybridCommunicateGroup
from paddle_tpu.models.gpt import (GPTForPretraining, GPTHybridTrainStep,
                                   GPTModel, gpt_tiny_config)
from paddle_tpu.profiler import Profiler, ProfilerTarget, utils
from paddle_tpu.profiler.utils import RecordEvent, Span, recorded_spans
from paddle_tpu.serving import ContinuousBatchingScheduler, ServingEngine

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmark")
if BENCH not in sys.path:
    sys.path.append(BENCH)      # the readers import ``harness``

SCHED_TREE = {
    "sched.step": None,
    "sched.expire": "sched.step", "sched.evict": "sched.step",
    "sched.admit": "sched.step", "sched.prefill_tick": "sched.step",
    "sched.hooks": "sched.step", "sched.decode_tick": "sched.step",
    "sched.prepare": "sched.step",
    "engine.prefill_begin": "sched.prefill_tick",
    "prefix.match": "engine.prefill_begin",
    "pool.alloc": "engine.prefill_begin",
    "engine.prefill_step": "sched.prefill_tick",
    "pool.extend": "sched.decode_tick",
    "engine.decode": "sched.decode_tick",
}
# names with more than one possible parent
ENGINE_PHASES = {"engine.host_prep", "engine.dispatch", "engine.readback",
                 "engine.unpack"}
READBACK_PHASES = {"engine.device_wait", "engine.d2h"}
TRAIN_TREE = {"train.step": None, "GPTHybridTrainStep.step": "train.step",
              "train.account": "train.step",
              "train.mem_sample": "train.step"}
SERVE_METRICS = ("sched_self_ms.serve", "decode_host_ms.serve",
                 "decode_wait_ms.serve", "prefill_host_ms.serve",
                 "prefill_chunk_ms.serve", "prefill_begin_ms.serve")
METRICS = SERVE_METRICS + ("dispatch_ms.train",)


def _tiny_engine():
    paddle.seed(0)
    cfg = gpt_tiny_config()
    model = GPTForPretraining(GPTModel(cfg))
    return cfg, ServingEngine(model, page_size=8, decode_buckets=(1, 2, 4),
                              aot=False, prefix_cache=True, prefill_chunk=8)


def _prompts(cfg, lens, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab_size, (s,)).astype(np.int32)
            for s in lens]


def _tiny_train_step():
    mesh_mod._global_mesh, mesh_mod._hcg = None, None
    paddle.seed(0)
    cfg = gpt_tiny_config()
    step = GPTHybridTrainStep(GPTForPretraining(GPTModel(cfg)), cfg,
                              HybridCommunicateGroup(), n_micro=1, lr=1e-3)
    ids = np.random.default_rng(0).integers(
        0, cfg.vocab_size, (2, 16)).astype(np.int32)
    return step, ids, np.roll(ids, -1, axis=1)


def _serve(sched, cfg, seed):
    """A short prompt decodes while a three-chunk prompt prefills, then
    a prompt that shares the first one's prefix."""
    short, long_, = _prompts(cfg, (8, 24), seed)
    reqs = [sched.submit(short, max_new_tokens=10)]
    for _ in range(2):
        sched.step()
    reqs.append(sched.submit(long_, max_new_tokens=3))
    sched.run()
    reqs.append(sched.submit(np.concatenate([short, long_[:5]]),
                             max_new_tokens=2))
    sched.run()
    assert all(r.state == "finished" for r in reqs)
    return reqs


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """Spans, requests and the ``.xplane.pb`` of one traced scenario."""
    saved = (mesh_mod._global_mesh, mesh_mod._hcg)
    cfg, engine = _tiny_engine()
    sched = ContinuousBatchingScheduler(engine)
    sched.background_hooks.append(lambda: None)
    _serve(sched, cfg, seed=0)              # compiles, untraced
    step, ids, labels = _tiny_train_step()
    step(ids, labels)                       # the compile-labelled call
    utils._drain_events()
    trace_dir = str(tmp_path_factory.mktemp("trace"))
    try:
        with jax.profiler.trace(trace_dir):
            lo = time.perf_counter()
            with RecordEvent("test.traced"):
                reqs = _serve(sched, cfg, seed=1)
                t0 = step._t
                losses = [float(step(ids, labels).numpy())
                          for _ in range(3)]
            hi = time.perf_counter()
    finally:
        mesh_mod._global_mesh, mesh_mod._hcg = saved
    assert all(math.isfinite(x) for x in losses)
    return types.SimpleNamespace(
        spans=recorded_spans(), reqs=reqs, window=(lo, hi), first_step=t0,
        xplane=sorted(glob.glob(os.path.join(
            trace_dir, "plugins", "profile", "*", "*.xplane.pb")))[-1])


# ---- (a) off means off ----------------------------------------------------

def test_off_keeps_no_record():
    saved = (mesh_mod._global_mesh, mesh_mod._hcg)
    utils._drain_events()
    assert not utils._collecting and not utils.TraceAnnotation.is_enabled()
    cfg, engine = _tiny_engine()
    sched = ContinuousBatchingScheduler(engine)
    for p in _prompts(cfg, (8, 24)):
        sched.submit(p, max_new_tokens=3)
    sched.run()
    assert sched.steps > 0
    try:
        step, ids, labels = _tiny_train_step()
        for _ in range(2):
            step(ids, labels)
    finally:
        mesh_mod._global_mesh, mesh_mod._hcg = saved
    assert recorded_spans() == []


# ---- (b) the tree, on ------------------------------------------------------

def test_every_phase_is_recorded_under_its_parent(traced):
    spans = traced.spans
    by_id = {s.span_id: s for s in spans}
    names = {s.name for s in spans}
    assert names >= set(SCHED_TREE) | ENGINE_PHASES | READBACK_PHASES \
        | set(TRAIN_TREE) | {"sched.account", "sched.submit"}
    for s in spans:
        if s.name == "test.traced":
            assert s.parent_id == 0
            continue
        parent = by_id[s.parent_id]
        assert parent.tid == s.tid
        assert parent.start_ns <= s.start_ns <= s.end_ns <= parent.end_ns, \
            (s, parent)
        want = {**SCHED_TREE, **TRAIN_TREE}.get(s.name)
        if s.name == "gc":          # a collection, under whatever was open
            continue
        if s.name in READBACK_PHASES:
            assert parent.name == "engine.readback"
        elif s.name in ENGINE_PHASES:
            assert parent.name in ("engine.decode", "engine.prefill_step")
        elif s.name == "sched.account":
            assert parent.name in ("sched.step", "sched.prefill_tick")
        elif want is None:
            assert parent.name == "test.traced"
        else:
            assert parent.name == want, (s.name, parent.name)


def test_engine_spans_carry_the_request_and_the_in_flight_count(traced):
    spans = traced.spans
    rids = {r.rid for r in traced.reqs}
    for name in ("engine.prefill_begin", "engine.prefill_step"):
        assert {s.attrs["rid"] for s in spans if s.name == name} == rids
    begun = {s.attrs["rid"]: s.attrs for s in spans
             if s.name == "engine.prefill_begin"}
    for r in traced.reqs:
        assert begun[r.rid]["prompt_len"] == r.prompt.shape[0]
        assert begun[r.rid]["cached_len"] == r.cached_prefix_len
    assert begun[traced.reqs[2].rid]["cached_len"] > 0     # shared prefix
    # every readback waits for what was dispatched since the last one and
    # leaves nothing in flight; a non-final chunk leaves its program, and
    # so does the copy of a shared boundary page
    engine_calls = sorted((s for s in spans if s.name in (
        "pool.alloc", "engine.prefill_step", "engine.decode")),
        key=lambda s: s.start_ns)
    in_flight, after_chunk = 0, 0
    for s in engine_calls:
        if s.name == "pool.alloc":
            in_flight += s.attrs["cow"]
            continue
        assert s.attrs["in_flight"] == in_flight, s
        kids = [c for c in spans if c.parent_id == s.span_id]
        back = [c for c in kids if c.name == "engine.readback"]
        if s.name == "engine.decode":
            after_chunk += in_flight >= 1
            assert len(back) == 1
        else:
            assert len(back) == (1 if s.attrs["final"] else 0)
        in_flight = 0 if back else in_flight + 1
        if back:
            assert back[0].attrs["in_flight"] == s.attrs["in_flight"] + 1
    assert in_flight == 0 and after_chunk >= 1
    steps = [s for s in spans if s.name == "sched.step"]
    assert [s.attrs["step"] for s in steps] == sorted(
        s.attrs["step"] for s in steps)
    ticks = [s for s in spans if s.name == "sched.decode_tick"]
    assert all(1 <= s.attrs["n_active"] <= s.attrs["bucket"] for s in ticks)
    assert sum(s.attrs["n_evicted"] for s in spans
               if s.name == "sched.evict") == len(traced.reqs)
    assert sum(s.attrs["n_admitted"] for s in spans
               if s.name == "sched.admit") == len(traced.reqs)
    assert sum(s.attrs["tokens"] for s in spans
               if s.name == "sched.prefill_tick") == sum(
        r.prompt.shape[0] - r.cached_prefix_len for r in traced.reqs)


# ---- (c) self time on a hand-made record ---------------------------------

def _span(name, lo_ms, hi_ms, span_id, parent_id, **attrs):
    return Span(name, 1, int(lo_ms * 1e6), int(hi_ms * 1e6), "UserDefined",
                span_id, parent_id, attrs)


HAND_MADE = [
    _span("sched.step", 0, 100, 1, 0),
    _span("sched.prefill_tick", 5, 25, 2, 1),
    _span("engine.prefill_step", 8, 20, 3, 2, final=True, in_flight=0),
    _span("engine.host_prep", 8, 10, 4, 3),
    _span("engine.dispatch", 10, 13, 5, 3),
    _span("engine.readback", 13, 19, 6, 3, in_flight=1),
    _span("sched.decode_tick", 30, 90, 7, 1),
    _span("engine.decode", 35, 85, 8, 7, in_flight=0),
    _span("engine.readback", 40, 84, 9, 8, in_flight=1),
    _span("sched.account", 91, 95, 10, 1),
]


def test_self_time_is_duration_less_children():
    from harness import program_spans as ps
    kids = ps.children(HAND_MADE)
    step = HAND_MADE[0]
    # less its own children (every name): 100 - (20 + 60 + 4)
    assert ps.ms(step) - ps.covered_ms(step, kids, "") == pytest.approx(16)
    # less what the engine covers, at any depth: 100 - (12 + 50)
    assert ps.ms(step) - ps.covered_ms(step, kids, "engine.") == \
        pytest.approx(38)
    assert ps.child_ms(HAND_MADE[2], kids, "engine.host_prep",
                       "engine.dispatch") == pytest.approx(5)


# ---- (d) the same spans on the device trace's clock ----------------------

def test_xplane_holds_the_spans_on_a_host_line(traced):
    from jax.profiler import ProfileData
    profile = ProfileData.from_file(traced.xplane)
    want = set(SCHED_TREE) | ENGINE_PHASES | set(TRAIN_TREE) \
        | {"sched.account", "test.traced"}
    found = {}
    for plane in profile.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name in want:
                    found.setdefault(e.name, []).append(
                        (e.start_ns, e.start_ns + e.duration_ns,
                         dict(e.stats)))
    assert set(found) == want
    (lo, hi, _), = found["test.traced"]
    for name, events in found.items():
        assert all(lo <= s <= e <= hi for s, e, _ in events), name
    # the attributes travel with the annotation
    assert all("in_flight" in stats for _, _, stats in found["engine.decode"])
    nums = [stats["step_num"] for _, _, stats in found["train.step"]]
    assert nums == [traced.first_step + 1 + i for i in range(3)]
    # one offset between the two clocks, whichever span it is read from
    rec = {}
    for s in traced.spans:
        rec.setdefault(s.name, []).append(s)
    offsets = [found[name][i][0] - rec[name][i].start_ns
               for name in ("test.traced", "sched.step", "train.step")
               for i in (0, -1)]
    assert max(offsets) - min(offsets) < 2e6     # nanoseconds


# ---- (e) the benchmark's readers ------------------------------------------

def _reader(name):
    spec = importlib.util.spec_from_file_location(
        "reader_" + name.replace(".", "_"),
        os.path.join(BENCH, "metrics", name + ".py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _stand_in(window, marks=True):
    return types.SimpleNamespace(
        spans=types.SimpleNamespace(
            records=[("traced",) + tuple(window)] if marks else []),
        window=window, counters={})


@pytest.mark.parametrize("name", METRICS)
def test_reader_reads_the_traced_spans(traced, monkeypatch, name):
    monkeypatch.setattr(utils, "_host_events", deque(traced.spans))
    value = _reader(name).read(_stand_in(traced.window))
    assert value is not None and math.isfinite(value) and value > 0
    # nothing inside another window, nothing in an untraced run
    later = (traced.window[1] + 1.0, traced.window[1] + 2.0)
    assert _reader(name).read(_stand_in(later)) is None
    assert _reader(name).read(_stand_in(traced.window, marks=False)) is None
    monkeypatch.setattr(utils, "_host_events", deque())
    assert _reader(name).read(_stand_in(traced.window)) is None


def test_readers_on_the_hand_made_record(monkeypatch):
    monkeypatch.setattr(utils, "_host_events", deque(HAND_MADE))
    run = _stand_in((0.0, 1.0))
    got = {name: _reader(name).read(run) for name in SERVE_METRICS}
    assert got.pop("prefill_begin_ms.serve") is None    # none was begun
    assert got == pytest.approx({
        "sched_self_ms.serve": 38, "decode_host_ms.serve": 6,
        "decode_wait_ms.serve": 44, "prefill_host_ms.serve": 5,
        "prefill_chunk_ms.serve": 12})


def test_manifest_names_the_new_readers():
    manifest = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    entries = {m["name"]: m for m in manifest["per_layer"]}
    for name in METRICS:
        assert entries[name]["source"] == "program_counter"
        assert os.path.exists(os.path.join(BENCH, "metrics", name + ".py"))


# ---- (f) the buffer's bound ------------------------------------------------

def test_buffer_is_bounded_and_counts_what_it_drops(monkeypatch):
    monkeypatch.setattr(utils, "_host_events", deque(maxlen=4))
    before = utils.dropped_spans()
    utils._set_collecting(True)
    try:
        for i in range(6):
            with RecordEvent("tick", i=i):
                pass
    finally:
        utils._set_collecting(False)
    kept = recorded_spans()
    assert [s.attrs["i"] for s in kept] == [2, 3, 4, 5]
    assert utils.dropped_spans() == before + 2
    assert recorded_spans() == kept            # reading does not drain
    assert utils._drain_events() == kept and recorded_spans() == []


# ---- (g) the train step ---------------------------------------------------

def test_train_step_spans(traced):
    steps = [s for s in traced.spans if s.name == "train.step"]
    assert [s.attrs["step_num"] for s in steps] == [
        traced.first_step + 1 + i for i in range(3)]
    for step in steps:
        kids = [s.name for s in traced.spans if s.parent_id == step.span_id]
        assert kids == ["GPTHybridTrainStep.step", "train.account",
                        "train.mem_sample"]
        # the CPU's gate keeps XLA's attention: no forward kernel a layer
        assert step.attrs["flash_fwd_per_layer"] == 0


# ---- (h) the Paddle-style profiler on the widened record -------------------

def test_profiler_export_carries_attrs_and_parents(tmp_path):
    p = Profiler(scheduler=(0, 1), targets=[ProfilerTarget.CPU])
    p.start()
    with RecordEvent("outer", rid=7) as ev:
        with RecordEvent("inner", final=True):
            pass
        ev.set(tokens=3)
    p.step()
    p.stop()
    doc = json.load(open(p.export(str(tmp_path / "t.json"))))
    events = {e["name"]: e for e in doc["traceEvents"] if e["ph"] == "X"}
    assert events["outer"]["args"]["rid"] == 7
    assert events["outer"]["args"]["tokens"] == 3
    assert events["inner"]["args"]["final"] is True
    assert events["inner"]["args"]["parent_id"] == \
        events["outer"]["args"]["span_id"]
    assert events["outer"]["args"]["parent_id"] == 0
    assert p.summary()["outer"]["calls"] == 1
    assert recorded_spans() == []              # the profiler drained them


# ---- Request.prefill_start_time ------------------------------------------

def test_prefill_wait_covers_the_prompt_ahead():
    from paddle_tpu.observability.reqtrace import request_record
    cfg, engine = _tiny_engine()
    sched = ContinuousBatchingScheduler(engine)
    first, second = [sched.submit(p, max_new_tokens=2)
                     for p in _prompts(cfg, (24, 24), seed=3)]
    sched.run()
    assert first.prefill_chunks == second.prefill_chunks == 3
    one, two = first.summary(), second.summary()
    assert first.admit_time <= first.prefill_start_time
    assert 0 <= one["prefill_wait_s"] < one["prefill_s"]
    # the second was admitted in the same tick and began once the first
    # one's three chunks were through
    assert second.prefill_start_time >= first.first_token_time
    assert two["prefill_wait_s"] >= one["prefill_s"]
    assert two["queue_wait_s"] + two["prefill_wait_s"] < two["ttft_s"]
    assert request_record(two, second.trace)["prefill_wait_s"] == \
        two["prefill_wait_s"]
    # a classic engine prefills at admission: nothing to wait for
    paddle.seed(0)
    plain = ServingEngine(GPTForPretraining(GPTModel(cfg)), page_size=8,
                          decode_buckets=(1, 2), aot=False)
    sched = ContinuousBatchingScheduler(plain)
    r = sched.submit(_prompts(cfg, (8,))[0], max_new_tokens=2)
    sched.run()
    assert r.summary()["prefill_wait_s"] == 0.0
    queued = ContinuousBatchingScheduler(plain).submit(
        _prompts(cfg, (8,))[0], max_new_tokens=2)
    assert queued.summary()["prefill_wait_s"] is None
