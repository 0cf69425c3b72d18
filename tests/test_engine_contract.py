"""The engine contract, held for every engine that remains.

``serving/engine_core.py`` states once what the scheduler may rely on
(``EngineContract``) and what every paged engine does (``PagedEngine``).
Each case below runs for the GPT engine as the cells deploy it (chunked,
prefix cache), for SDAR's block engine, for the hybrid Phi-4-flash engine
(a state pool beside the pages, no prefix cache: the cases about cached
pages leave it out), for the sparse latent GLM-5.2 engine (a latent page
row and index keys under one table, no prefix cache), and, where the
one-shot prefill has something of its own to say, for the GPT engine
without a chunk program. Tiny configs, the kernels' reference paths: the contract is the
host's.
"""
import collections

import jax
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.models import glm5, phi4flash, sdar
from paddle_tpu.serving import (ContinuousBatchingScheduler, EngineContract,
                                EngineShapeError, PagedEngine,
                                SdarServingEngine, ServingEngine,
                                simulate_decode_signatures)
from paddle_tpu.serving import (engine as gpt_engine, glm5_engine,
                                phi4flash_engine, sdar_engine)
from paddle_tpu.serving.scheduler import _ShapeProbeEngine

SDAR_CFG = sdar.sdar_moe_tiny_config()
PHI4_CFG = phi4flash.phi4flash_tiny_config()
GLM5_CFG = glm5.glm5_tiny_config()
MAX_LEN = 128
COMMON_KEYS = {"decode_buckets", "prefill_chunk", "block_len", "pool",
               "compute_dtype", "weights_mb", "max_seq_len", "compile_s",
               "aot_programs", "program_memory"}


@pytest.fixture(scope="module")
def gpt_model():
    from paddle_tpu.models.gpt import (GPTForPretraining, GPTModel,
                                       gpt_tiny_config)
    paddle.seed(3)
    cfg = gpt_tiny_config()
    return GPTForPretraining(GPTModel(cfg)), cfg


@pytest.fixture(scope="module")
def sdar_weights():
    return sdar.init_sdar_weights(SDAR_CFG, 17)


@pytest.fixture(scope="module")
def phi4_weights():
    return phi4flash.init_phi4flash_weights(PHI4_CFG, 17)


@pytest.fixture(scope="module")
def glm5_weights():
    return glm5.init_glm5_weights(GLM5_CFG, 17)


@pytest.fixture(scope="module")
def build(gpt_model, sdar_weights, phi4_weights, glm5_weights):
    """``build(kind, **overrides)``: a new engine of that kind."""
    model, cfg = gpt_model

    def make(kind, **kw):
        base = dict(page_size=8, decode_buckets=(1, 2, 4), aot=False,
                    use_kernel=False)
        if kind == "gpt-oneshot":
            base.update(decode_buckets=(1, 2))
        else:
            base.update(prefill_chunk=16, prefix_cache=True)
        if kind == "sdar":
            base.update(num_pages=64, max_seq_len=MAX_LEN)
            base.update(kw)
            return SdarServingEngine(sdar_weights, SDAR_CFG, **base)
        if kind == "phi4":
            base.update(num_pages=64, max_seq_len=MAX_LEN,
                        prefix_cache=False)
            base.update(kw)
            return phi4flash_engine.Phi4FlashServingEngine(
                phi4_weights, PHI4_CFG, **base)
        if kind == "glm5":
            base.update(num_pages=64, max_seq_len=MAX_LEN,
                        prefix_cache=False)
            base.update(kw)
            return glm5_engine.Glm5ServingEngine(glm5_weights, GLM5_CFG,
                                                 **base)
        base.update(autofuse=False)
        base.update(kw)
        return ServingEngine(model, cfg, **base)
    return make


@pytest.fixture(scope="module")
def compiled(build):
    """One AOT-compiled engine a kind, shared by the cases that run its
    programs; every case leaves it empty (``_emptied``)."""
    made = {}

    def get(kind):
        if kind not in made:
            made[kind] = build(kind, aot=True)
        return made[kind]
    return get


def _vocab(eng):
    return eng.cfg.vocab_size - 1       # SDAR's last id is its mask


def _prompts(eng, lens, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, _vocab(eng), (n,)).astype(np.int32)
            for n in lens]


def _emptied(eng):
    """No sequence, no cached page: what a case must leave behind."""
    eng.reclaim_cache_pages(eng.pool.num_pages)
    assert eng.pool.live_sequences == 0
    assert eng.pool.pages_in_use == 0
    assert not eng._chunk_state
    if isinstance(eng, phi4flash_engine.Phi4FlashServingEngine):
        assert eng.state.slots_in_use == 0
    return eng


def _n_programs(eng):
    return len(eng._decode_exe) + (eng._chunk_exe is not None) \
        + len(getattr(eng, "_prefill_exe", ()))


ALL = ["gpt", "gpt-oneshot", "sdar", "phi4", "glm5"]
CHUNKED = ["gpt", "sdar"]           # chunked, with the prefix cache
PAGED = CHUNKED + ["phi4", "glm5"]  # chunked, with or without it


# ------------------------------------------------------- shapes refused

@pytest.mark.parametrize("kind", ALL)
def test_more_sequences_than_the_widest_bucket_raise(build, kind):
    eng = build(kind)
    widest = eng.decode_buckets[-1]
    assert eng.decode_bucket(widest) == widest
    assert eng.decode_bucket(1) == eng.decode_buckets[0]
    with pytest.raises(EngineShapeError, match="active sequences"):
        eng.decode_bucket(widest + 1)
    with pytest.raises(EngineShapeError):
        eng.decode(list(range(widest + 1)), bucket=widest)


@pytest.mark.parametrize("kind", ALL)
def test_a_prompt_with_no_room_raises(build, kind):
    eng = build(kind)
    full = np.zeros(eng.max_seq_len, np.int32)
    with pytest.raises(EngineShapeError, match="no room"):
        if eng.prefill_chunk is None:
            eng.prefill("x", full)
        else:
            eng.prefill_begin("x", full)
    assert eng.pool.pages_in_use == 0 and not eng._chunk_state
    if kind == "gpt-oneshot":
        with pytest.raises(EngineShapeError, match="prompt tokens"):
            eng.prefill_bucket(10_000)
    with pytest.raises(ValueError):
        build(kind, max_seq_len=1 << 20)    # past the model's positions


# ---------------------------------------------------------- AOT closure

@pytest.mark.parametrize("kind", ALL)
def test_aot_builds_the_bucket_set_and_a_mixed_run_compiles_nothing(
        compiled, kind):
    """One executable a decode bucket plus one chunk program (one-shot:
    one a prefill bucket); serving a ragged mix adds none."""
    eng = compiled(kind)
    assert set(eng._decode_exe) == set(eng.decode_buckets)
    if eng.prefill_chunk is None:
        assert eng._chunk_exe is None
        assert set(eng._prefill_exe) == set(eng.prefill_buckets)
    else:
        assert eng._chunk_exe is not None
        assert not getattr(eng, "_prefill_exe", None)
    n, compile_s = _n_programs(eng), eng.compile_s
    assert compile_s > 0
    sched = ContinuousBatchingScheduler(eng)
    reqs = [sched.submit(p, max_new_tokens=2 + i % 4) for i, p in
            enumerate(_prompts(eng, (3, 21, 9, 14, 5, 40), seed=8))]
    sched.run()
    assert [len(r.tokens) for r in reqs] == [2 + i % 4 for i in range(6)]
    assert _n_programs(eng) == n and eng.compile_s == compile_s
    eng.compile_buckets()               # nothing is missing: none is made
    assert _n_programs(eng) == n
    _emptied(eng)


@pytest.mark.parametrize("kind", ALL)
def test_closure_replay_stays_inside_the_engines_signatures(build, kind):
    """The device-free replay of the real scheduler allows exactly what
    the engine compiles, and asks for nothing else."""
    eng = build(kind)
    one_shot = eng.prefill_chunk is None
    used_d, used_p, ok_d, ok_p = simulate_decode_signatures(
        eng.decode_buckets,
        eng.prefill_buckets if one_shot else (eng.max_seq_len,),
        eng.pool.page_size, eng.pool.num_pages, eng.max_seq_len,
        n_requests=120, seed=7, prefill_chunk=eng.prefill_chunk,
        block_len=eng.block_len)
    assert ok_d == eng.decode_signatures()
    assert ok_p == eng.prefill_signatures()
    assert used_d and used_d <= ok_d
    assert used_p and used_p <= ok_p


# ------------------------------------- set-up is the bucket set, no more

_EVENTS = collections.Counter()     # jax's lowerings and backend compiles
jax.monitoring.register_event_duration_secs_listener(
    lambda event, *_a, **_k: _EVENTS.update([event.rsplit("/", 1)[-1]]))


@pytest.mark.parametrize("kind", PAGED)
def test_construction_lowers_and_compiles_the_bucket_set_and_no_more(
        build, kind):
    """With the cells' options (a chunk, the prefix cache, the kernels,
    auto-fusion as the environment has it): construction lowers and
    compiles nothing, and ``compile_buckets()`` one program a decode
    bucket, the chunk program and GPT's page-copy program."""
    cells = dict(use_kernel=True, autofuse=None) if kind == "gpt" else \
        dict(use_kernel=True)
    build(kind, **cells)                # the eager ops' own programs
    _EVENTS.clear()
    eng = build(kind, **cells)
    assert not _EVENTS["jaxpr_to_mlir_module_duration"]
    assert not _EVENTS["backend_compile_duration"]
    eng.compile_buckets()
    n = len(eng.decode_buckets) + 1 + (kind == "gpt")
    assert _EVENTS["jaxpr_to_mlir_module_duration"] == n
    assert _EVENTS["backend_compile_duration"] == n
    assert eng.status()["aot_programs"] == n
    _EVENTS.clear()
    build(kind, aot=True, **cells)      # the constructor does the same
    assert _EVENTS["jaxpr_to_mlir_module_duration"] == n
    assert _EVENTS["backend_compile_duration"] == n


class _Recorded:
    """A jitted program whose ``lower().compile().memory_analysis()``
    are written down: ``log`` gets ``(name, leading dim of the first
    argument after the pools)``, ``reads`` how often each executable's
    memory was asked for."""

    def __init__(self, name, jitted, log, reads):
        self.name, self.jitted, self.log, self.reads = \
            name, jitted, log, reads

    def lower(self, *avals):
        # the tick's packed int32 state: the first integer array after
        # the pools (the hybrid engine's state arrays come before it)
        key = (self.name, next(a for a in avals[3:] if a.dtype == np.int32)
               .shape[0] if self.name == "decode" else None)
        self.log.append(key)
        exe = self.jitted.lower(*avals).compile()
        rec = self

        class Lowered:
            def compile(self):
                return Compiled()

        class Compiled:
            def memory_analysis(self):
                rec.reads[key] += 1
                return exe.memory_analysis()
        return Lowered()


@pytest.mark.parametrize("kind", PAGED)
def test_compile_buckets_keeps_its_order_and_reads_memory_once(build, kind):
    """Decode buckets ascending, then the chunk program, then GPT's
    page-copy program; the memory of every pool-carrying program is read
    once; a second call lowers nothing."""
    eng = build(kind, decode_buckets=(4, 1, 2))
    log, reads = [], collections.Counter()
    eng._decode_jit = _Recorded("decode", eng._decode_jit, log, reads)
    eng._chunk_jit = _Recorded("chunk", eng._chunk_jit, log, reads)
    if kind == "gpt":
        eng._copy_page_jit = _Recorded("copy", eng._copy_page_jit, log,
                                       reads)
    eng.compile_buckets()
    carrying = [("decode", 1), ("decode", 2), ("decode", 4),
                ("chunk", None)]
    assert log == carrying + [("copy", None)] * (kind == "gpt")
    assert reads == dict.fromkeys(carrying, 1)
    eng.compile_buckets()
    assert len(log) == len(carrying) + (kind == "gpt")


# --------------------------------------------------------------- status

@pytest.mark.parametrize("kind", PAGED)
def test_status_carries_the_common_keys_and_every_programs_memory(
        compiled, build, kind):
    eng = compiled(kind)
    st = eng.status()
    assert COMMON_KEYS <= set(st)
    assert ("prefix_cache" in st) == (kind in CHUNKED)
    if kind == "phi4":
        assert {"state", "cache_bytes_per_token",
                "state_bytes_per_slot"} <= set(st)
        assert st["program_memory"]["state_bytes"] == eng.state.nbytes
    assert st["block_len"] == eng.block_len
    assert st["decode_buckets"] == list(eng.decode_buckets)
    assert st["aot_programs"] >= len(eng.decode_buckets) + 1
    mem = st["program_memory"]
    assert mem["pool_bytes"] == eng.pool.k_pages.nbytes \
        + eng.pool.v_pages.nbytes
    if kind == "glm5":
        # a latent row of every layer, an index key of the indexers'
        assert st["cache_bytes_per_token"] == 4 * (
            5 * GLM5_CFG.row_width + 2 * GLM5_CFG.index_head_dim)
        assert eng.pool.v_pages.shape[0] == 2
    assert sorted(mem["decode"]) == list(eng.decode_buckets)
    for sizes in list(mem["decode"].values()) + [mem["chunk"]]:
        assert set(sizes) == {"temp_bytes", "alias_bytes"}
    lazy = build(kind).status()
    assert COMMON_KEYS <= set(lazy)
    assert lazy["program_memory"]["decode"] == {}
    assert "chunk" not in lazy["program_memory"]
    assert lazy["aot_programs"] == 0


# -------------------------------------------------------------- release

@pytest.mark.parametrize("kind", CHUNKED)
def test_release_publishes_whole_pages_that_the_next_prefill_hits(
        compiled, kind):
    eng = compiled(kind)
    ps = eng.pool.page_size
    (prompt,) = _prompts(eng, (3 * ps + 3,), seed=5)
    sched = ContinuousBatchingScheduler(eng)
    first = sched.submit(prompt, max_new_tokens=6)
    sched.run()
    assert first.state == "finished" and first.cached_prefix_len == 0
    # its sequence is gone; what stays in use is the cache's whole pages
    assert eng.pool.live_sequences == 0
    assert eng.pool.pages_in_use >= 3
    cached = eng.prefill_begin("again", prompt)
    assert cached >= 2 * ps         # whole pages (GPT: and a boundary)
    assert eng.pool.stats()["prefix_hits"] >= 1
    eng.release("again")                # mid-prefill: no token ids
    _emptied(eng)


@pytest.mark.parametrize("kind", PAGED)
def test_release_without_a_cache_frees_every_page(build, kind):
    eng = build(kind, prefix_cache=False)
    assert eng.prefix_cache is None and eng.reclaim_cache_pages(4) == 0
    for sid, p in enumerate(_prompts(eng, (40, 9))):
        assert eng.prefill_begin(sid, p) == 0
    assert eng.pool.pages_in_use == 5 + 2 - (kind == "sdar")
    eng.release(0, token_ids=np.zeros(40, np.int32))
    eng.release(1)
    assert eng.pool.pages_in_use == 0 and not eng._chunk_state
    assert eng.pool.free_pages == eng.pool.num_pages - 1
    _emptied(eng)


@pytest.mark.parametrize("kind", CHUNKED)
def test_reclaim_cache_pages_returns_pages_under_pressure(compiled, kind):
    eng = compiled(kind)
    sched = ContinuousBatchingScheduler(eng)
    for p in _prompts(eng, (32, 32, 32), seed=9):
        sched.submit(p, max_new_tokens=2)
    sched.run()
    held = eng.pool.pages_in_use
    assert held >= 3 * 4 and eng.pool.live_sequences == 0
    free = eng.pool.free_pages
    assert eng.reclaim_cache_pages(2) >= 2
    assert eng.pool.free_pages >= free + 2
    # the scheduler's valve: a request the free list cannot hold gets in
    need = eng.pool.free_pages + 1
    assert sched._page_room(need)
    assert eng.pool.free_pages >= need
    _emptied(eng)


@pytest.mark.parametrize("kind", PAGED)
def test_a_request_cancelled_mid_prefill_leaves_no_state_and_no_page(
        compiled, kind):
    eng = compiled(kind)
    sched = ContinuousBatchingScheduler(eng)
    (prompt,) = _prompts(eng, (56,), seed=2)    # four chunks of 16
    r = sched.submit(prompt, max_new_tokens=8)
    sched.step()
    assert r.state == "prefilling" and r.prefill_chunks == 1
    assert r.rid in eng._chunk_state and eng.pool.pages_in_use >= 7
    assert sched.cancel(r.rid)
    assert r.state == "deadline_exceeded"
    assert not eng._chunk_state and eng.pool.live_sequences == 0
    assert sched._reserved_pages == 0
    _emptied(eng)


# ------------------------------------------------ what the scheduler reads

def _probe(**kw):
    return _ShapeProbeEngine((1, 2), (8, 64), 8, 32, 64, **kw)


@pytest.mark.parametrize("kind", ALL + ["probe", "probe-blocks"])
def test_scheduler_reads_every_declared_attribute(build, kind):
    eng = {"probe": _probe, "probe-blocks":
           lambda: _probe(prefill_chunk=8, block_len=4)}.get(
        kind, lambda: build(kind))()
    assert isinstance(eng, EngineContract)
    assert isinstance(eng, PagedEngine) == (not kind.startswith("probe"))
    sched = ContinuousBatchingScheduler(eng)
    assert sched.buckets == eng.decode_buckets
    assert sched.chunked == (eng.prefill_chunk is not None)
    assert sched.block_len == eng.block_len
    assert sched.block_len == (4 if kind in ("sdar", "probe-blocks") else 1)
    assert sched.prefill_token_budget == eng.prefill_chunk
    assert sched._cache_hit_tokens(np.arange(20)) == 0
    assert (eng.prefix_cache is not None) == (kind in CHUNKED)
    assert sched._page_room(1)
    assert {"decode_buckets", "prefill_chunk", "block_len", "pool"} \
        <= set(sched.status()["engine"])
    # only an engine that says so is asked to take a sequence over
    assert eng.can_migrate == kind.startswith("gpt")
    ok, why = sched.prepare_migration_in(1, [1, 2, 3], 3, 4)
    assert (ok, why) == ((True, 0) if eng.can_migrate
                         else (False, "engine_unsupported"))
    if eng.block_len > 1:
        for call in ("starts_block", "masked_positions", "note_emitted"):
            assert callable(getattr(eng, call))


# ----------------------------------- programs follow their module's names

@pytest.mark.parametrize("kind", PAGED)
def test_rebuilt_programs_are_made_from_the_modules_functions(
        monkeypatch, build, kind):
    """``_build_programs()`` re-makes the jitted programs from the step
    functions as the adapter's module holds them then, and
    ``compile_buckets()`` compiles what is missing."""
    module, names = {
        "gpt": (gpt_engine, ("decode_step_fn", "chunk_prefill_fn")),
        "sdar": (sdar_engine, ("sdar_block_step_fn",
                               "sdar_chunk_prefill_fn")),
        "phi4": (phi4flash_engine, ("phi4flash_decode_fn",
                                    "phi4flash_chunk_fn")),
        "glm5": (glm5_engine, ("glm5_decode_fn", "glm5_chunk_fn"))}[kind]
    eng = build(kind, decode_buckets=(1,))
    traced = []

    def counting(name):
        inner = getattr(module, name)

        def fn(*a, **kw):
            traced.append(name)
            return inner(*a, **kw)
        return fn
    for name in names:
        monkeypatch.setattr(module, name, counting(name))
    (prompt,) = _prompts(eng, (20,))
    eng.prefill_begin("a", prompt)
    eng.prefill_step("a")
    eng.release("a")
    assert traced == []                 # built before the names moved
    eng._build_programs()
    assert eng._decode_exe == {} and eng._chunk_exe is None
    eng.compile_buckets()
    assert sorted(set(traced)) == sorted(names)
    assert set(eng._decode_exe) == {1} and eng._chunk_exe is not None
