"""The host between programs: the device-ready stamp in every readback,
a named leaf span for the host's work around it, and the benchmark's
readers of the gaps (``benchmark/harness/host_gaps.py``).

Each of the four serving adapters' tiny engines runs behind the
scheduler on the CPU backend with its spans kept (module fixture, one
per adapter), timed by a clock of the lines of Python executed. The readers are also held to values computed
by hand on a hand-made record.
"""
import collections
import gc
import importlib.util
import json
import linecache
import math
import os
import sys
import types
from collections import deque

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.profiler import utils
from paddle_tpu.profiler.utils import RecordEvent, Span
from paddle_tpu.serving import ContinuousBatchingScheduler

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmark")
if BENCH not in sys.path:
    sys.path.append(BENCH)      # the readers import ``harness``

from harness import host_gaps  # noqa: E402

SERVE_CELLS = ["serve-1.3b-chat-steady", "serve-sdar-reasoning-steady",
               "serve-phi4flash-reasoning-steady",
               "serve-glm52-longctx-steady"]
READERS = ("host_gap_ms.serve", "host_gap_share.serve",
           "readback_tail_ms.serve", "gap_sched_ms.serve",
           "gap_unnamed_share.serve")

# the spans this file is about, and the parents they may have
NEW = {"engine.device_wait": {"engine.readback"},
       "engine.d2h": {"engine.readback"},
       "engine.unpack": {"engine.decode", "engine.prefill_step"},
       "sched.batch": {"sched.decode_tick"},
       "sched.prepare": {"sched.step"},
       "sched.submit": {"test.traced"}}

# every span the adapters recorded before the new ones: attribute keys
# and the spans it may sit under, read through any new span between
_COMMON = {
    "engine.dispatch": ((), {"engine.decode", "engine.prefill_step"}),
    "engine.host_prep": ((), {"engine.decode", "engine.prefill_step"}),
    "engine.prefill_begin": (("cached_len", "prompt_len", "rid"),
                             {"sched.prefill_tick"}),
    "engine.readback": (("in_flight",),
                        {"engine.decode", "engine.prefill_step"}),
    "sched.account": (("path",), {"sched.prefill_tick", "sched.step"}),
    "sched.admit": (("n_admitted",), {"sched.step"}),
    "sched.evict": (("n_evicted",), {"sched.step"}),
    "sched.expire": ((), {"sched.step"}),
    "sched.prefill_tick": (("tokens",), {"sched.step"}),
    "sched.step": (("prefilling", "queued", "running", "step"),
                   {"test.traced"}),
    "pool.alloc": ((), {"engine.prefill_begin"}),
    "pool.extend": ((), {"sched.decode_tick"}),
    "sched.decode_tick": (("bucket", "n_active"), {"sched.step"}),
}
_CHUNK = ("clen", "final", "in_flight", "rid", "start")
OLD = {
    "gpt": {**_COMMON,
            "engine.decode": (("bucket", "in_flight", "n"),
                              {"sched.decode_tick"}),
            "engine.prefill_step": (_CHUNK, {"sched.prefill_tick"}),
            "pool.alloc": (("cow",), {"engine.prefill_begin"}),
            "prefix.match": ((), {"engine.prefill_begin"})},
    "sdar": {**_COMMON,
             "engine.decode": (("bucket", "commit", "in_flight", "n",
                                "n_commit", "pass", "unmasked"),
                               {"sched.decode_tick"}),
             "engine.prefill_step": (_CHUNK, {"sched.prefill_tick"}),
             "pool.alloc": (("cow",), {"engine.prefill_begin"}),
             "pool.extend": (("n",), {"sched.decode_tick"}),
             "prefix.match": ((), {"engine.prefill_begin"}),
             "sched.decode_tick": (("block", "bucket", "masked",
                                    "n_active"), {"sched.step"})},
    "phi": {**_COMMON,
            "engine.decode": (("bucket", "cache_bytes", "in_flight",
                               "live_ctx", "n", "state_slots",
                               "window_rows"), {"sched.decode_tick"}),
            "engine.prefill_step": (_CHUNK + ("cross",),
                                    {"sched.prefill_tick"}),
            "state.alloc": (("slot",), {"engine.prefill_begin"}),
            "state.free": ((), {"sched.evict"})},
    "glm": {**_COMMON,
            "engine.decode": (("bucket", "cache_bytes", "in_flight",
                               "live_ctx", "n", "selected"),
                              {"sched.decode_tick"}),
            "engine.prefill_step": (_CHUNK + ("index_keys", "selected"),
                                    {"sched.prefill_tick"}),
            "experts.held": (("active", "assigned", "program", "tokens"),
                             {"engine.decode", "engine.readback"})},
}


def _gpt():
    from paddle_tpu.models.gpt import (GPTForPretraining, GPTModel,
                                       gpt_tiny_config)
    from paddle_tpu.serving import ServingEngine
    paddle.seed(0)
    cfg = gpt_tiny_config()
    return cfg.vocab_size, (8, 24), ServingEngine(
        GPTForPretraining(GPTModel(cfg)), page_size=8,
        decode_buckets=(1, 2, 4), aot=False, prefix_cache=True,
        prefill_chunk=8)


def _sdar():
    from paddle_tpu.models import sdar
    from paddle_tpu.serving import SdarServingEngine
    cfg = sdar.sdar_moe_tiny_config()
    return cfg.vocab_size, (20, 40), SdarServingEngine(
        sdar.init_sdar_weights(cfg, 17), cfg, page_size=8, num_pages=64,
        max_seq_len=128, decode_buckets=(1, 2, 4), prefill_chunk=16,
        prefix_cache=True, aot=False)


def _phi():
    from paddle_tpu.models import phi4flash as M
    from paddle_tpu.serving.phi4flash_engine import Phi4FlashServingEngine
    cfg = M.phi4flash_tiny_config()
    return cfg.vocab_size, (6, 19), Phi4FlashServingEngine(
        M.init_phi4flash_weights(cfg, 3), cfg, page_size=4, num_pages=96,
        max_seq_len=64, decode_buckets=(1, 2, 4), prefill_chunk=8,
        use_kernel=False, aot=False)


def _glm():
    from paddle_tpu.models import glm5 as M
    from paddle_tpu.serving.glm5_engine import Glm5ServingEngine
    cfg = M.glm5_tiny_config(initializer_range=0.07)
    return cfg.vocab_size, (40, 70), Glm5ServingEngine(
        M.init_glm5_weights(cfg, 3), cfg, page_size=16, num_pages=64,
        max_seq_len=256, decode_buckets=(1, 2, 4), prefill_chunk=32,
        aot=False)


BUILDERS = {"gpt": _gpt, "sdar": _sdar, "phi": _phi, "glm": _glm}


def _serve(sched, vocab, lens, seed):
    """A short prompt decodes, then a prompt of several chunks joins."""
    rng = np.random.default_rng(seed)
    short, long_ = (rng.integers(0, vocab, (n,)).astype(np.int32)
                    for n in lens)
    reqs = [sched.submit(short, max_new_tokens=10)]
    for _ in range(2):
        sched.step()
    reqs.append(sched.submit(long_, max_new_tokens=6))
    sched.run()
    assert all(r.state == "finished" for r in reqs)


class LineClock:
    """A clock that counts the lines of Python this thread executes: the
    host's work, counted alike on every run. On the CPU backend a tiny
    engine's step lasts 2 to 4 ms of wall time, much of it the backend's
    threads sharing the host's cores with this one, and varies with the
    load. ``program[t]`` is how many of the first ``t`` lines were not the
    recorder's (``profiler/utils.py`` and the statement that opens a
    span), so that a stretch can be measured both ways."""

    def __init__(self):
        self.lines = 0
        self.program = [0]
        self.own = {self.perf_counter_ns.__code__, self._trace.__code__,
                    self._line.__code__}

    def perf_counter_ns(self):
        return self.lines

    def _trace(self, frame, event, arg):
        return None if frame.f_code in self.own else self._line

    def _line(self, frame, event, arg):
        if event == "line":
            code = frame.f_code
            recorder = code.co_filename == utils.__file__ \
                or "RecordEvent(" in linecache.getline(code.co_filename,
                                                       frame.f_lineno)
            self.lines += 1
            self.program.append(self.program[-1] + (not recorder))
        return self._line


@pytest.fixture(scope="module", params=sorted(BUILDERS))
def traced(request):
    """The scenario's spans, kept by the recorder alone (a Profiler's
    record state) on the ``LineClock``."""
    vocab, lens, engine = BUILDERS[request.param]()
    sched = ContinuousBatchingScheduler(engine)
    _serve(sched, vocab, lens, seed=0)          # compiles, untraced
    utils._drain_events()
    clock, wall, tracer = LineClock(), utils.time, sys.gettrace()
    utils.time = types.SimpleNamespace(perf_counter_ns=clock.perf_counter_ns)
    utils._set_collecting(True)
    sys.settrace(clock._trace)
    try:
        with RecordEvent("test.traced"):
            _serve(sched, vocab, lens, seed=1)
    finally:
        sys.settrace(tracer)
        utils._set_collecting(False)
        utils.time = wall
    spans = utils._drain_events()
    return types.SimpleNamespace(adapter=request.param, spans=spans,
                                 by_id={s.span_id: s for s in spans},
                                 clock=clock)


def _old_parent(s, by_id):
    """The nearest span above ``s`` that is neither new nor ``gc``."""
    p = by_id.get(s.parent_id)
    while p is not None and (p.name in NEW or p.name == "gc"):
        p = by_id.get(p.parent_id)
    return p.name if p is not None else None


# ---- the tree ---------------------------------------------------------------

def test_new_spans_nest_under_their_parents(traced):
    names = collections.Counter(s.name for s in traced.spans)
    want = set(NEW) - ({"engine.unpack", "sched.batch"}
                       if traced.adapter == "gpt" else set())
    assert want <= set(names), traced.adapter
    for s in traced.spans:
        if s.name not in NEW and s.name != "gc":
            continue
        parent = traced.by_id[s.parent_id]
        assert parent.start_ns <= s.start_ns <= s.end_ns <= parent.end_ns
        if s.name in NEW:
            assert parent.name in NEW[s.name], (s.name, parent.name)
            assert s.attrs == {}
    # one stamp and one copy in every readback, in that order
    for back in (s for s in traced.spans if s.name == "engine.readback"):
        kids = [c.name for c in traced.spans
                if c.parent_id == back.span_id and c.name != "gc"]
        assert kids == ["engine.device_wait", "engine.d2h"]
    # where there is work after the decode's readback, it is named
    unpacked = {traced.by_id[s.parent_id].name for s in traced.spans
                if s.name == "engine.unpack"}
    assert ("engine.decode" in unpacked) == (traced.adapter != "gpt")


def test_existing_spans_keep_name_attrs_and_parent(traced):
    old = OLD[traced.adapter]
    seen = set()
    for s in traced.spans:
        if s.name in NEW or s.name in ("gc", "test.traced"):
            continue
        assert s.name in old, s.name
        keys, parents = old[s.name]
        assert tuple(sorted(s.attrs)) == tuple(sorted(keys)), s
        assert _old_parent(s, traced.by_id) in parents, s
        seen.add(s.name)
    assert seen == set(old)


def test_device_wait_ends_inside_its_readback(traced):
    waits = [s for s in traced.spans if s.name == "engine.device_wait"]
    assert waits
    for w in waits:
        back = traced.by_id[w.parent_id]
        assert back.start_ns <= w.start_ns <= w.end_ns <= back.end_ns
        d2h = [c for c in traced.spans if c.parent_id == back.span_id
               and c.name == "engine.d2h"]
        assert len(d2h) == 1 and w.end_ns <= d2h[0].start_ns


# the least share of a steady step that lies under a naming span on the
# line clock once the recorder's own lines count too (the four adapters
# read 0.64 to 0.70): its constructor, ``begin`` before the stamp and
# ``end`` after it fall between spans, in a container's own time
RECORDED_FLOOR = 0.6


def test_steady_steps_lie_under_leaf_spans(traced):
    """A step that only decodes, on the line clock: 95% or more of the
    program's own lines under a span that names its time (no phase
    container holding phases), and ``RECORDED_FLOOR`` or more of all its
    lines, the recorder's counted."""
    kids = collections.defaultdict(list)
    for s in traced.spans:
        kids[s.parent_id].append(s)
    named = host_gaps.Named(traced.spans)
    program = traced.clock.program
    steady = [s for s in traced.spans if s.name == "sched.step"
              and any(c.name == "sched.decode_tick" for c in kids[s.span_id])
              and not any(c.name == "sched.prefill_tick" and c.attrs["tokens"]
                          for c in kids[s.span_id])]
    assert len(steady) >= 3
    for s in steady:
        lo, hi = s.start_ns, s.end_ns
        under = [(max(a, lo), min(b, hi)) for a, b, _ in named.pieces
                 if a < hi and b > lo]
        own = sum(program[b] - program[a] for a, b in under) \
            / (program[hi] - program[lo])
        every = sum(b - a for a, b in under) / (hi - lo)
        assert own >= 0.95 and every >= RECORDED_FLOOR, (own, every, s)


# every reader of the program's spans that the benchmark had before them
ACCEPTED = ("sched_self_ms.serve", "decode_host_ms.serve",
            "decode_wait_ms.serve", "prefill_host_ms.serve",
            "prefill_chunk_ms.serve", "prefill_begin_ms.serve",
            "denoise_pass_ms.serve", "cache_bytes_per_token.serve",
            "state_slots_peak_share.serve")


def _without_new(spans):
    """The record as a program without the new spans would keep it: they
    and ``gc`` taken out, their children moved up to their parent."""
    gone = {s.span_id: s.parent_id for s in spans
            if s.name in NEW or s.name == "gc"}

    def up(parent_id):
        while parent_id in gone:
            parent_id = gone[parent_id]
        return parent_id
    return [s._replace(parent_id=up(s.parent_id)) for s in spans
            if s.span_id not in gone]


def test_accepted_readers_read_what_they_read(traced, monkeypatch):
    """The new spans add to the tree and take nothing from what the
    benchmark's accepted readers read: each gives the same value on the
    record as on the record without them."""
    top = [s for s in traced.spans if s.name == "test.traced"][0]
    run = _stand_in((top.start_ns / 1e9, top.end_ns / 1e9))
    run.traffic = {"decode_buckets": (1, 2, 4)}
    got = []
    for record in (traced.spans, _without_new(traced.spans)):
        monkeypatch.setattr(utils, "_host_events", deque(record))
        got.append({name: _reader(name).read(run) for name in ACCEPTED})
    with_new, without = got
    for name in ("sched_self_ms.serve", "decode_host_ms.serve",
                 "decode_wait_ms.serve", "prefill_host_ms.serve",
                 "prefill_begin_ms.serve"):
        assert with_new[name] is not None, name
    for name in ACCEPTED:
        if without[name] is None:
            assert with_new[name] is None, name
        else:
            assert with_new[name] == pytest.approx(without[name],
                                                   rel=1e-12), name


def test_readers_read_the_tiny_run(traced, monkeypatch):
    monkeypatch.setattr(utils, "_host_events", deque(traced.spans))
    top = [s for s in traced.spans if s.name == "test.traced"][0]
    run = _stand_in((top.start_ns / 1e9, top.end_ns / 1e9))
    acc = host_gaps.account(run)
    assert acc.gaps and acc.skipped >= 1   # the long prompt's later chunks
    got = {name: _reader(name).read(run) for name in READERS}
    assert all(v is not None and math.isfinite(v) and v >= 0
               for v in got.values()), got
    assert 0 < got["host_gap_share.serve"] < 100
    assert got["gap_unnamed_share.serve"] < 100


# ---- no record, no reading --------------------------------------------------

def _reader(name):
    spec = importlib.util.spec_from_file_location(
        "reader_" + name.replace(".", "_"),
        os.path.join(BENCH, "metrics", name + ".py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _stand_in(window, waits=()):
    return types.SimpleNamespace(
        spans=types.SimpleNamespace(
            records=[("traced",) + tuple(window)]
            + [("wait_for_arrival",) + tuple(w) for w in waits]),
        window=window, counters={})


def _span(name, lo_ms, hi_ms, span_id, parent_id, **attrs):
    return Span(name, 1, int(lo_ms * 1e6), int(hi_ms * 1e6), "UserDefined",
                span_id, parent_id, attrs)


def test_readers_read_nothing_without_the_stamp(monkeypatch):
    """A program without ``engine.device_wait`` (the one before it): every
    reader returns None, and raises nothing."""
    old = [_span("sched.step", 0, 10, 1, 0),
           _span("sched.decode_tick", 1, 9, 2, 1),
           _span("engine.decode", 2, 8, 3, 2, in_flight=0),
           _span("engine.dispatch", 3, 4, 4, 3),
           _span("engine.readback", 4, 8, 5, 3, in_flight=1)]
    monkeypatch.setattr(utils, "_host_events", deque(old))
    for name in READERS:
        assert _reader(name).read(_stand_in((0.0, 1.0))) is None
    monkeypatch.setattr(utils, "_host_events", deque())
    for name in READERS:
        assert _reader(name).read(_stand_in((0.0, 1.0))) is None


# ---- the readers on a hand-made record -------------------------------------

HAND_MADE = [
    # a prompt's last chunk, then a decode tick
    _span("sched.step", 0, 30, 1, 0),
    _span("sched.prefill_tick", 1, 10, 2, 1, tokens=8),
    _span("engine.prefill_step", 2, 9, 3, 2, final=True, in_flight=0),
    _span("engine.host_prep", 2, 4, 4, 3),
    _span("engine.dispatch", 4, 5, 5, 3),
    _span("engine.readback", 5, 8, 6, 3, in_flight=1),
    _span("engine.device_wait", 5, 7, 7, 6),
    _span("engine.d2h", 7, 8, 8, 6),
    _span("engine.unpack", 8, 9, 9, 3),
    _span("sched.prepare", 10, 12, 10, 1),
    _span("gc", 10.5, 11, 43, 10, generation=0),
    _span("sched.decode_tick", 12, 28, 11, 1),
    _span("pool.extend", 12, 13, 12, 11),
    _span("engine.decode", 13, 27, 13, 11, in_flight=0),
    _span("engine.host_prep", 13, 14, 14, 13),
    _span("engine.dispatch", 14, 15, 15, 13),
    _span("engine.readback", 15, 25, 16, 13, in_flight=1),
    _span("engine.device_wait", 15, 24, 17, 16),
    _span("engine.d2h", 24, 25, 18, 16),
    _span("engine.unpack", 25, 26.5, 19, 13),
    _span("sched.account", 28, 29, 20, 1),
    # a new prompt's first chunk, then a decode with the chunk in flight
    _span("sched.step", 30, 60, 21, 0),
    _span("sched.prefill_tick", 31, 36, 22, 21, tokens=8),
    _span("engine.prefill_step", 32, 35, 23, 22, final=False, in_flight=0),
    _span("engine.host_prep", 32, 34, 24, 23),
    _span("engine.dispatch", 34, 35, 25, 23),
    _span("sched.decode_tick", 37, 59, 26, 21),
    _span("engine.decode", 38, 58, 27, 26, in_flight=1),
    _span("engine.host_prep", 38, 39, 28, 27),
    _span("engine.dispatch", 39, 40, 29, 27),
    _span("engine.readback", 40, 55, 30, 27, in_flight=2),
    _span("engine.device_wait", 40, 54, 31, 30),
    _span("engine.d2h", 54, 55, 32, 30),
    _span("engine.unpack", 55, 57, 33, 27),
    # nothing to run until a request arrives (the benchmark's wait, 60-70)
    _span("sched.submit", 70, 71, 34, 0),
    _span("sched.step", 71, 90, 35, 0),
    _span("sched.prefill_tick", 72, 80, 36, 35, tokens=8),
    _span("engine.prefill_step", 73, 79, 37, 36, final=True, in_flight=0),
    _span("engine.host_prep", 73, 74, 38, 37),
    _span("engine.dispatch", 74, 75, 39, 37),
    _span("engine.readback", 75, 78, 40, 37, in_flight=1),
    _span("engine.device_wait", 75, 77, 41, 40),
    _span("engine.d2h", 77, 78, 42, 40),
]


def test_gaps_on_the_hand_made_record(monkeypatch):
    """Gap 1, 7-15 ms: after the chunk's readback until the decode's
    dispatch; under leaves 7 of its 8 ms (the prefill tick's own 9-10 is
    not), 2.5 of them the scheduler's (``sched.prepare`` less its ``gc``,
    ``pool.extend``). Gap 2, 24-35: after the decode's readback until the
    next chunk's dispatch; 6.5 of 11 ms named (1 of them
    ``sched.account``); 26.5-27, 27-28, 29-30, 30-31 and 31-32 are the
    containers' own. The decode dispatched with the chunk in flight is
    skipped; the stretch 54-75 holds the wait for an arrival and is left
    out."""
    monkeypatch.setattr(utils, "_host_events", deque(HAND_MADE))
    run = _stand_in((0.0, 0.1), waits=[(0.060, 0.070)])
    acc = host_gaps.account(run)
    assert [(g.lo / 1e6, g.hi / 1e6) for g in acc.gaps] == [(7, 15),
                                                            (24, 35)]
    assert [g.named_ns / 1e6 for g in acc.gaps] == pytest.approx([7, 6.5])
    assert [g.sched_ns / 1e6 for g in acc.gaps] == pytest.approx([2.5, 1])
    assert acc.skipped == 1 and acc.window_ns == pytest.approx(100e6)
    assert acc.tails == pytest.approx([3, 4])
    got = {name: _reader(name).read(run) for name in READERS}
    assert got == pytest.approx({
        "host_gap_ms.serve": 9.5, "host_gap_share.serve": 19.0,
        "readback_tail_ms.serve": 3.5, "gap_sched_ms.serve": 1.75,
        "gap_unnamed_share.serve": 100 * 5.5 / 19})


def test_a_gap_across_an_arrival_is_left_out(monkeypatch):
    monkeypatch.setattr(utils, "_host_events", deque(HAND_MADE))
    # without the benchmark's wait the third stretch counts too
    acc = host_gaps.account(_stand_in((0.0, 0.1)))
    assert [(g.lo / 1e6, g.hi / 1e6) for g in acc.gaps][-1] == (54, 75)
    # a wait across the first gap takes that one out alone
    acc = host_gaps.account(_stand_in((0.0, 0.1), waits=[(0.010, 0.011),
                                                         (0.060, 0.070)]))
    assert [(g.lo / 1e6, g.hi / 1e6) for g in acc.gaps] == [(24, 35)]


def test_the_window_starts_where_the_kept_records_do(monkeypatch):
    """The bounded buffer let the first step go: the share is of what
    the records cover."""
    kept = [s for s in HAND_MADE if s.start_ns >= 30e6]
    monkeypatch.setattr(utils, "_host_events", deque(kept))
    acc = host_gaps.account(_stand_in((0.0, 0.1), waits=[(0.060, 0.070)]))
    assert acc.window_ns == pytest.approx(70e6) and acc.gaps == []
    assert _reader("host_gap_share.serve").read(
        _stand_in((0.0, 0.1), waits=[(0.060, 0.070)])) == 0.0
    assert _reader("host_gap_ms.serve").read(
        _stand_in((0.0, 0.1), waits=[(0.060, 0.070)])) is None


def test_manifest_names_the_readers():
    manifest = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    entries = {m["name"]: m for m in manifest["per_layer"]}
    assert [m["name"] for m in manifest["per_layer"][-5:]] == list(READERS)
    for name in READERS:
        m = entries[name]
        assert (m["source"], m["layer"], m["moves"], m["workloads"]) == (
            "program_counter", "host between programs", "itl_p90_ms",
            SERVE_CELLS)
        assert os.path.exists(os.path.join(BENCH, "metrics", name + ".py"))


# ---- the recorder ----------------------------------------------------------

def test_kept_says_whether_the_span_is_recorded(monkeypatch):
    monkeypatch.setattr(utils, "_host_events", deque())
    ev = RecordEvent("x")
    with ev:
        assert not ev.kept
    utils._set_collecting(True)
    try:
        with ev:
            assert ev.kept
        assert not ev.kept
    finally:
        utils._set_collecting(False)


def test_the_device_wait_runs_only_while_kept(monkeypatch):
    from paddle_tpu.serving import engine_core
    waited = []
    monkeypatch.setattr(engine_core.jax, "block_until_ready", waited.append)
    monkeypatch.setattr(utils, "_host_events", deque())
    engine_core.wait_for_device("off")
    assert waited == [] and utils.recorded_spans() == []
    utils._set_collecting(True)
    try:
        engine_core.wait_for_device("on")
    finally:
        utils._set_collecting(False)
    assert waited == ["on"]
    assert [s.name for s in utils.recorded_spans()] == ["engine.device_wait"]


def test_gc_pauses_are_spans_while_kept(monkeypatch):
    utils.record_gc_pauses()
    utils.record_gc_pauses()
    assert gc.callbacks.count(utils._gc_span) == 1
    monkeypatch.setattr(utils, "_host_events", deque())
    gc.collect()
    assert utils.recorded_spans() == []
    utils._set_collecting(True)
    try:
        with RecordEvent("outer"):
            gc.collect(1)
    finally:
        utils._set_collecting(False)
    spans = utils.recorded_spans()
    outer = [s for s in spans if s.name == "outer"][0]
    pauses = [s for s in spans if s.name == "gc"]
    assert 1 in [s.attrs["generation"] for s in pauses
                 if s.parent_id == outer.span_id]
    assert all(isinstance(s.attrs["collected"], int) for s in pauses)
    assert all(outer.start_ns <= s.start_ns <= s.end_ns <= outer.end_ns
               for s in pauses if s.parent_id == outer.span_id)


# ---- the two ends the program cannot stamp (tools/idle_account.py) ----------

def _tool():
    spec = importlib.util.spec_from_file_location(
        "idle_account", os.path.join(REPO, "tools", "idle_account.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def test_runtime_ends_join_the_host_plane_by_run_id():
    """On a v5e trace (three train steps): a dispatch ending 0.1 ms
    before the runtime enqueues run 48, and a wait ending 0.05 ms after
    run 48's completion callbacks begin."""
    from jax.profiler import ProfileData
    tool = _tool()
    profile = ProfileData.from_file(
        os.path.join(BENCH, "harness", "recorded.xplane.pb"))
    host = tool.host_events(profile)
    modules = tool.device_modules(profile)
    assert sorted(modules) == list(range(48, 57))
    (enq,) = [e for e in host["DoEnqueueProgram"] if e[2]["run_id"] == 48]
    (cb,) = [e for e in host["CompleteCallbacks"] if e[2]["run_id"] == 48]
    host["engine.dispatch"] = [(enq[0] - 200_000, enq[0] - 100_000, {})]
    host["engine.device_wait"] = [(enq[1] + 10, cb[0] + 50_000, {})]
    ends = tool.runtime_ends(host, modules)
    assert ends["enqueue"] == [enq[1] - enq[0] + 100_000]
    assert ends["complete"] == [50_000]
    assert ends["device_start"] == [modules[48][0] - enq[1]]
    assert ends["device_end"] == [cb[0] - modules[48][1]]
    (done,) = [e for e in host["tpu::System::Execute=>Done"]
               if e[0] >= cb[0]][:1]
    assert ends["done"] == [cb[0] + 50_000 - done[1]]
    assert tool.overlap([(0, 10), (20, 30)], [(5, 25)]) == 10


def test_the_tool_says_where_the_gaps_went(monkeypatch):
    """On the hand-made record: the containers' own time in the two gaps
    (the first chunk's prefill tick 9-10, the second's 31-32; the
    decode's 26.5-27, its tick's 27-28, the two steps' 29-31) and what
    each leaf named there."""
    monkeypatch.setattr(utils, "_host_events", deque(HAND_MADE))
    acc = host_gaps.account(_stand_in((0.0, 0.1), waits=[(0.060, 0.070)]))
    tool = _tool()
    assert tool.unnamed_by_container(acc, HAND_MADE) == pytest.approx({
        "sched.prefill_tick": 2, "sched.step": 2, "sched.decode_tick": 1,
        "engine.decode": 0.5, "(none)": 0})
    assert tool.named_in_gaps(acc, HAND_MADE) == pytest.approx({
        "engine.host_prep": 3, "engine.unpack": 2.5, "engine.d2h": 2,
        "engine.dispatch": 2, "sched.prepare": 1.5, "pool.extend": 1,
        "sched.account": 1, "gc": 0.5})
