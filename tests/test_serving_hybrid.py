"""The hybrid engine (``serving/phi4flash_engine.py``: a page pool of one
layer beside a state pool of one slot a sequence) against the plain
reference, on the tiny config: 12 layers, hidden 64, 4/2 heads of 16,
window 8, 4 states, page 4, chunk 8, vocabulary 128. What is compared is
logits (``keep_logits=True``): the engine's at every served position
against the reference's full forward pass over the prompt and the served
tokens.
"""
import numpy as np
import pytest
import jax

import paddle_tpu  # noqa: F401
from paddle_tpu.models import phi4flash as M
from paddle_tpu.models import phi4flash_reference as ref
from paddle_tpu.profiler.utils import _drain_events, recorded_spans
from paddle_tpu.serving import ContinuousBatchingScheduler, EngineShapeError
from paddle_tpu.serving import phi4flash_engine as E
from paddle_tpu.serving.state_pool import StatePool, StatePoolFull

CFG = M.phi4flash_tiny_config()
TOL = 2e-5
PAGE, CHUNK = 4, 8


@pytest.fixture(scope="module")
def weights():
    return M.init_phi4flash_weights(CFG, 3)


def _engine(weights, **kw):
    base = dict(page_size=PAGE, num_pages=96, max_seq_len=64,
                decode_buckets=(1, 2, 4), prefill_chunk=CHUNK,
                use_kernel=False, aot=False, keep_logits=True)
    base.update(kw)
    return E.Phi4FlashServingEngine(weights, CFG, **base)


@pytest.fixture(scope="module")
def engine(weights):
    return _engine(weights)


@pytest.fixture(scope="module")
def kernel_engine(weights):
    return _engine(weights, use_kernel=True)


def _prompt(n, seed):
    return np.random.default_rng(seed).integers(0, CFG.vocab_size, n) \
        .astype(np.int32)


def _prefill(eng, sid, prompt):
    """Prefill ``prompt`` in chunks; ``(first token, its logits)``."""
    assert eng.prefill_begin(sid, prompt) == 0
    done = False
    while not done:
        _, done, tok = eng.prefill_step(sid)
    return tok, eng.last_logits


def _serve(eng, sid, prompt, steps, beside=()):
    """Prefill and ``steps`` greedy decodes, alone or in a bucket with
    the running sequences ``beside``. Returns ``(tokens, logits of every
    served position [steps + 1, V])``."""
    tok, logits = _prefill(eng, sid, prompt)
    toks, rows = [tok], [logits]
    for _ in range(steps):
        for s in (sid,) + tuple(beside):
            eng.pool.extend(s, 1)
        out = eng.decode([sid] + list(beside))
        toks.append(out[0])
        rows.append(eng.last_logits[0])
    return toks, np.stack(rows)


def _reference_rows(weights, prompt, toks):
    """The reference's logits at the positions that produced ``toks``."""
    full = ref.forward(weights, np.concatenate([prompt, toks[:-1]]), CFG)
    return np.asarray(full[len(prompt) - 1:])


# ------------------------------------- prefill in chunks, then decode

# shorter than a chunk, a whole chunk, not a multiple, longer than two
# chunks (and than the window), longer than three
@pytest.mark.parametrize("n", [5, 8, 13, 19, 27])
@pytest.mark.parametrize("which", ["engine", "kernel_engine"])
def test_prefill_then_decode_is_the_reference_at_every_position(
        request, weights, which, n):
    """14 decodes after the prompt: past the window's 8 rows and the
    ring's wrap, across page and chunk boundaries."""
    eng = request.getfixturevalue(which)
    prompt = _prompt(n, seed=n)
    toks, rows = _serve(eng, "a", prompt, 14)
    want = _reference_rows(weights, prompt, toks)
    assert np.abs(rows - want).max() < TOL
    assert toks == [int(t) for t in want.argmax(-1)]
    eng.release("a")
    assert eng.pool.pages_in_use == 0 and eng.state.slots_in_use == 0


def test_the_last_position_cross_decoder_is_every_layer_everywhere(
        engine, weights):
    """Prefill runs the cross-decoder and the head at the prompt's last
    position alone; the program's full-sequence form runs every layer at
    every position: the same first token and logits."""
    prompt = _prompt(21, seed=9)
    before = dict(engine.counters)
    tok, logits = _prefill(engine, "x", prompt)
    engine.release("x")
    full = M.forward_full(weights, jax.numpy.asarray(prompt), CFG)
    assert np.abs(logits - np.asarray(full[-1])).max() < TOL
    assert tok == int(full[-1].argmax())
    # three chunks, the cross-decoder under the last alone
    assert engine.counters["prefill_chunks"] - before["prefill_chunks"] == 3
    assert engine.counters["cross_chunks"] - before["cross_chunks"] == 1


def test_the_step_form_iterated_is_the_full_sequence_form(engine, weights):
    """One prompt token, then the decode program a position at a time
    over given tokens: the logits of the full-sequence form."""
    ids = _prompt(20, seed=6)
    _, first = _prefill(engine, "s", ids[:1])
    rows = [first]
    for t in ids[1:]:
        engine._last_token["s"] = int(t)
        engine.pool.extend("s", 1)
        engine.decode(["s"])
        rows.append(engine.last_logits[0])
    engine.release("s")
    full = M.forward_full(weights, jax.numpy.asarray(ids), CFG)
    assert np.abs(np.stack(rows) - np.asarray(full)).max() < TOL


def test_the_engine_agrees_with_a_window_of_eight_and_no_other(engine,
                                                               weights):
    prompt = _prompt(19, seed=4)
    toks, rows = _serve(engine, "w", prompt, 6)
    engine.release("w")
    ids = np.concatenate([prompt, toks[:-1]])
    at = slice(len(prompt) - 1, None)
    gap = {w: np.abs(rows - np.asarray(
        ref.forward(weights, ids, CFG, window=w)[at])).max()
        for w in (7, 8, 9)}
    assert gap[8] < TOL and gap[7] > 100 * TOL and gap[9] > 100 * TOL


# ----------------------- alone, in a full bucket, in a slot just left

def test_a_sequence_reads_the_same_alone_among_others_and_in_a_used_slot(
        weights):
    """State reset on reuse, no leak between slots, no dependence on the
    bucket or on which slot and pages a sequence holds."""
    eng = _engine(weights)
    prompt = _prompt(13, seed=1)
    alone_toks, alone = _serve(eng, "a", prompt, 10)
    slot = eng.state.slot("a")
    eng.release("a")
    # three others, running, then the same prompt in a full bucket
    for i, n in enumerate((9, 17, 5)):
        _serve(eng, f"o{i}", _prompt(n, seed=20 + i), 3)
    others = ("o0", "o1", "o2")
    assert eng.state.slot("o0") == slot         # the slot "a" just left
    among_toks, among = _serve(eng, "b", prompt, 10, beside=others)
    assert eng.decode_bucket(4) == 4 and eng.state.slots_in_use == 4
    assert among_toks == alone_toks
    assert np.abs(among - alone).max() < TOL
    # and again where another sequence has just been: its state and rows
    # are in the slot until the first chunk resets them
    eng.release("o0")
    again_toks, again = _serve(eng, "c", prompt, 10,
                               beside=("o1", "o2", "b"))
    assert eng.state.slot("c") == slot
    assert again_toks == alone_toks
    assert np.abs(again - alone).max() < TOL
    st = eng.state.stats()
    assert st["slots_peak"] == 4 and st["resets"] == 6
    for s in ("o1", "o2", "b", "c"):
        eng.release(s)
    assert eng.state.slots_in_use == 0 and eng.pool.pages_in_use == 0


def test_a_slot_that_is_not_reset_is_told_from_one_that_is(weights,
                                                           monkeypatch):
    """The planted fault: the first chunk's flag ignored, so the state
    of the sequence that left the slot stays in."""
    eng = _engine(weights)
    prompt = _prompt(11, seed=2)
    _, sound = _serve(eng, "a", prompt, 4)
    eng.release("a")
    _serve(eng, "junk", _prompt(23, seed=3), 6)
    eng.release("junk")
    inner = E.phi4flash_chunk_fn

    def unreset(*a, **kw):
        flags = a[12].at[E._F_RESET].set(0)
        return inner(*a[:12], flags, **kw)
    monkeypatch.setattr(E, "phi4flash_chunk_fn", unreset)
    eng._build_programs()
    _, stale = _serve(eng, "b", prompt, 4)
    assert np.abs(stale - sound).max() > 10 * TOL


# ----------------------------------------------------- the state pool

def _pool(n=3):
    return StatePool(n, n_ssm=2, d_state=4, d_inner=8, d_conv=4,
                     n_window=1, window=8, page_size=4, row_width=32)


def test_state_pool_allocates_frees_and_reuses():
    pool = _pool(3)
    assert pool.ssm.shape == (2, 4, 4, 8) and pool.conv.shape == (2, 4, 3, 8)
    assert pool.win_k.shape == pool.win_v.shape == (1, 4 * 2, 4, 32)
    assert [pool.alloc(s) for s in "abc"] == [1, 2, 3]     # 0 is the sink
    assert pool.slot("b") == 2 and pool.slots_in_use == 3
    assert list(pool.slots_array(["c", None, "a"])) == [3, 0, 1]
    with pytest.raises(StatePoolFull, match="all 3 state slots"):
        pool.alloc("d")
    with pytest.raises(ValueError, match="already holds"):
        pool.alloc("a")
    pool.free("b")
    pool.free("b")                      # a sequence that holds none
    assert pool.alloc("d") == 2         # the slot that was just left
    pool.free("a"), pool.free("c"), pool.free("d")
    st = pool.stats()
    assert st == {"slots": 3, "slots_in_use": 0, "slots_peak": 3,
                  "bytes_per_slot": pool.nbytes // 4,
                  "state_bytes": pool.nbytes, "resets": 4}
    with pytest.raises(ValueError, match="whole pages"):
        StatePool(2, n_ssm=1, d_state=4, d_inner=8, d_conv=4, n_window=1,
                  window=6, page_size=4, row_width=32)


def test_published_sizes_of_a_slot_and_of_a_token():
    """What ``status()`` reports at the published widths, from shapes
    alone: 5,120 B of page pool a token, 24.0 MB a slot."""
    cfg = M.Phi4FlashConfig()
    per_token = 2 * cfg.kv_pairs * cfg.pair_dim * 2
    assert per_token == 5120 == 163840 // 32
    ssm = cfg.n_mamba * cfg.d_state * cfg.d_inner * 4
    conv = cfg.n_mamba * (cfg.d_conv - 1) * cfg.d_inner * 2
    rows = cfg.n_self_pairs * cfg.sliding_window * per_token
    assert (ssm, conv, rows) == (2949120, 276480, 20971520)


# ----------------------------------------------- under the scheduler

def test_admission_never_finds_the_state_pool_empty(weights):
    """Twelve requests over four slots: the scheduler holds running +
    prefilling under the widest bucket, which is the number of slots."""
    eng = _engine(weights, keep_logits=False)
    sched = ContinuousBatchingScheduler(eng)
    assert sched.max_concurrency == eng.state.n_slots == 4
    reqs = [sched.submit(_prompt(3 + 5 * (i % 5), seed=i),
                         max_new_tokens=3 + i % 7) for i in range(12)]
    peak = 0
    while sched.pending:
        sched.step()
        peak = max(peak, eng.state.slots_in_use)
        assert eng.state.slots_in_use == eng.pool.live_sequences
    assert [r.state for r in reqs] == ["finished"] * 12
    assert [len(r.tokens) for r in reqs] == [3 + i % 7 for i in range(12)]
    st = eng.status()
    assert peak == st["state"]["slots_peak"] == 4
    assert st["state"]["slots_in_use"] == 0 and st["state"]["resets"] == 12
    assert st["cache_bytes_per_token"] == 2 * 32 * 4    # K, V: float32
    assert st["state_bytes_per_slot"] == eng.state.bytes_per_slot
    assert st["program_memory"]["state_bytes"] == eng.state.nbytes
    assert st["chunks"]["cross_chunks"] == 12
    with pytest.raises(StatePoolFull):          # driven past the rule
        for i in range(5):
            eng.prefill_begin(f"over{i}", _prompt(3, seed=i))
    assert eng.pool.live_sequences == 4         # the fifth took no page


def test_scheduler_output_is_the_reference_greedy_decode(weights):
    eng = _engine(weights, keep_logits=False)
    sched = ContinuousBatchingScheduler(eng)
    prompts = [_prompt(n, seed=40 + n) for n in (6, 21, 11)]
    reqs = [sched.submit(p, max_new_tokens=9) for p in prompts]
    sched.run()
    for p, r in zip(prompts, reqs):
        # greedy: every token is the reference's first at its position,
        # given the tokens before it
        want = _reference_rows(weights, p, r.tokens).argmax(-1)
        assert r.tokens == [int(t) for t in want]


def test_spans_and_counters_of_the_state_pool(weights, tmp_path):
    eng = _engine(weights, keep_logits=False)
    sched = ContinuousBatchingScheduler(eng)
    sched.submit(_prompt(5, seed=0), max_new_tokens=2)
    sched.run()                                  # compiles, untraced
    _drain_events()
    with jax.profiler.trace(str(tmp_path)):
        reqs = [sched.submit(_prompt(n, seed=n), max_new_tokens=4)
                for n in (19, 6)]
        sched.run()
    spans = recorded_spans()
    by_id = {s.span_id: s for s in spans}
    named = lambda name: [s for s in spans if s.name == name]
    assert len(named("state.alloc")) == len(named("state.free")) == 2
    for s in named("state.alloc"):
        assert by_id[s.parent_id].name == "engine.prefill_begin"
        assert s.attrs["slot"] in (1, 2)
    chunks = named("engine.prefill_step")
    assert [s.attrs["cross"] for s in chunks] == \
        [int(s.attrs["final"]) for s in chunks]
    assert sum(s.attrs["cross"] for s in chunks) == 2 and len(chunks) == 4
    ticks = named("engine.decode")
    assert ticks
    per_slot, per_token = eng.state.bytes_per_slot, eng.cache_bytes_per_token
    for s in ticks:
        a = s.attrs
        assert 1 <= a["state_slots"] <= 2 and a["n"] <= a["state_slots"]
        assert a["window_rows"] <= min(a["live_ctx"], 8 * a["n"])
        assert a["cache_bytes"] >= a["state_slots"] * per_slot \
            + a["live_ctx"] * per_token
    assert all(r.state == "finished" for r in reqs)


# ------------------------------------------------------------- refusals

def test_prefix_cache_and_migration_are_refused_by_name(weights):
    with pytest.raises(ValueError, match="prefix_cache"):
        _engine(weights, prefix_cache=True)
    with pytest.raises(ValueError, match="chunks only"):
        _engine(weights, prefill_chunk=None)
    eng = _engine(weights)
    assert eng.prefix_cache is None and not eng.can_migrate
    assert eng.block_len == 1 and eng.pool.num_layers == 1
    assert eng.pool.k_pages.shape == (1, 96, PAGE, 32)    # a pool of rows
    with pytest.raises(EngineShapeError, match="no room"):
        eng.prefill_begin("x", np.zeros(64, np.int32))
    assert eng.state.slots_in_use == 0 and eng.pool.pages_in_use == 0


# ---------------------- the benchmark's own copy of the reference

def _benchmark_family():
    import importlib.util
    import os
    import sys
    bench = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark")
    spec = importlib.util.spec_from_file_location(
        "bench_phi4flash_for_tests",
        os.path.join(bench, "models", "phi4flash.py"))
    module = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = module
    spec.loader.exec_module(module)
    return module, module.load_config(os.path.join(
        bench, "tests", "configs", "phi4flash-tiny.json"))


@pytest.fixture(scope="module")
def family():
    """The benchmark's family file, its tiny config (the program's tiny
    widths; N(0, 0.1) so that 4 states of 128 channels weigh what 16 of
    5,120 do), every layer of its float32 weights, and the same weights
    in the program's stacked layout."""
    phi, cfg = _benchmark_family()
    pcfg = phi.program_config(cfg)
    assert pcfg == M.phi4flash_tiny_config(initializer_range=0.1)
    return (phi, cfg, pcfg, phi.whole_weights(cfg, 11),
            phi.init_weights(cfg, 11, dtype="float32"))


def test_the_benchmarks_reference_is_the_programs_reference(family):
    phi, cfg, pcfg, layers, stacked = family
    ids = _prompt(33, seed=8)
    theirs = np.asarray(phi.reference_logits(cfg, layers, ids))
    ours = np.asarray(ref.forward(stacked, ids, pcfg))
    assert np.abs(theirs - ours).max() < TOL
    # the controls round every matmul operand at the same places (a
    # rounding that falls the other way after 1e-7 moves a few logits)
    for mode in ("bf16", "fp8"):
        apart = np.abs(
            np.asarray(phi.reference_logits(cfg, layers, ids, mode=mode))
            - np.asarray(ref.forward(stacked, ids, pcfg, mode=mode)))
        assert np.median(apart) < 1e-4 and apart.max() < 0.1
    assert phi.layer_kinds(cfg) == M.layer_kinds(pcfg)
    shapes = jax.tree_util.tree_map(lambda a: a.shape, stacked)
    assert shapes == M.phi4flash_weight_shapes(pcfg)


@pytest.mark.parametrize("fault", ["chunk_state_zeroed", "slot_not_reset",
                                   "window_ignored", "cross_stale",
                                   "memory_after_gate", "no_lambda"])
def test_each_planted_fault_is_told_from_the_sound_program(family, fault):
    """The engine serves the benchmark's weights to within rounding of
    the sound reference; the reference with the fault planted lies a
    thousand times farther from it."""
    phi, cfg, pcfg, layers, stacked = family
    assert fault in phi.FAULTS
    eng = E.Phi4FlashServingEngine(
        stacked, pcfg, page_size=PAGE, num_pages=64, max_seq_len=64,
        decode_buckets=(1,), prefill_chunk=CHUNK, use_kernel=False,
        aot=False, keep_logits=True)
    prompt = _prompt(21, seed=12)
    toks, rows = _serve(eng, "f", prompt, 12)
    ids = np.concatenate([prompt, toks[:-1]])
    at = slice(len(prompt) - 1, None)
    sound = np.asarray(phi.reference_logits(cfg, layers, ids))[at]
    broken = np.asarray(phi.reference_logits(cfg, layers, ids,
                                             fault=fault))[at]
    assert np.abs(rows - sound).max() < 5 * TOL
    assert np.abs(broken - sound).max() > 1000 * TOL
