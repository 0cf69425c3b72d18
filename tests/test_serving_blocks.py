"""Block-diffusion serving through the page pool against the reference.

Tiny widths, seeded float32 weights. The programs' step functions are
called directly and return their logits: compared are the **logits of
every pass**, prefill then blocks through the pool, against the
reference's full forward pass (1e-4: the same float32 arithmetic in
another order). Tokens are compared only where the engine and the
reference's own ``generate()`` run the same float32 mathematics end to
end (same tokens at the same passes).
"""
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.models import sdar, sdar_reference as ref
from paddle_tpu.serving import (ContinuousBatchingScheduler,
                                MigrationUnsupported,
                                SdarServingEngine)
from paddle_tpu.serving.sdar_engine import (sdar_block_step_fn,
                                            sdar_chunk_prefill_fn)

TOL = 1e-4
CFG = sdar.sdar_moe_tiny_config()
PS, PAGES, WIDTH = 8, 24, 8             # page size, pool pages, table width


@pytest.fixture(scope="module")
def weights():
    return sdar.init_sdar_weights(CFG, 17)


def _engine(weights, **kw):
    base = dict(page_size=PS, num_pages=64, max_seq_len=128,
                decode_buckets=(1, 2, 4), prefill_chunk=16,
                prefix_cache=True, aot=False)
    base.update(kw)
    return SdarServingEngine(weights, CFG, **base)


def _drive(weights, prompt, n_new, threshold, use_kernel):
    """Prefill and decode one sequence by the step functions alone, as
    the engine would, yielding ``(committed + block ids, masked,
    logits [bl, V])`` of every denoising pass."""
    bl = CFG.block_length
    shape = (CFG.num_hidden_layers, PAGES, PS, CFG.num_key_value_heads,
             CFG.head_dim)
    kp, vp = jnp.zeros(shape, jnp.float32), jnp.zeros(shape, jnp.float32)
    table = np.arange(1, 1 + WIDTH, dtype=np.int32)
    prompt = [int(t) for t in prompt]
    n_full = len(prompt) // bl * bl
    for start in range(0, n_full, 16):
        clen = min(16, n_full - start)
        buf = np.zeros((1, 16), np.int32)
        buf[0, :clen] = prompt[start:start + clen]
        pos = start + np.arange(16)
        rows = np.where(pos < n_full, table[pos // PS % WIDTH] * PS
                        + pos % PS, pos % PS).astype(np.int32)
        kp, vp, _ = sdar_chunk_prefill_fn(
            weights, kp, vp, jnp.asarray(buf), start, clen,
            jnp.asarray(table[None]), jnp.asarray(rows), cfg=CFG,
            use_kernel=use_kernel)
    committed, block = prompt[:n_full], prompt[n_full:]
    masked = [False] * len(block) + [True] * (bl - len(block))
    block = block + [CFG.mask_token_id] * (bl - len(block))
    made = -len(prompt[n_full:])
    while made < n_new:
        while True:
            state = np.zeros((1, 2 * bl + 2 + WIDTH), np.int32)
            state[0, :bl], state[0, bl:2 * bl] = block, masked
            state[0, 2 * bl], state[0, 2 * bl + 1] = (len(committed),
                                                      len(committed) + bl)
            state[0, 2 * bl + 2:] = table
            kp, vp, out, logits = sdar_block_step_fn(
                weights, kp, vp, jnp.asarray(state), cfg=CFG,
                threshold=threshold, use_kernel=use_kernel,
                return_logits=True)
            if not any(masked):
                break                   # that was the commit pass
            yield committed + block, list(masked), np.asarray(logits)[0]
            out = np.asarray(out)
            after, picked = out[:bl], out[bl:2 * bl] > 0
            # the confidences ride the same readback as float32 bits
            lg = np.asarray(logits)[0].astype(np.float64)
            top = lg.max(-1)
            want = 1.0 / np.exp(lg - top[:, None]).sum(-1)
            assert np.allclose(out[2 * bl:3 * bl].view(np.float32), want,
                               rtol=1e-5)
            assert picked.any() and not (picked & ~np.array(masked)).any()
            block = [int(after[i]) if picked[i] else block[i]
                     for i in range(bl)]
            masked = [m and not p for m, p in zip(masked, picked)]
        made += bl
        committed, block, masked = committed + block, \
            [CFG.mask_token_id] * bl, [True] * bl


@pytest.mark.parametrize("use_kernel", [False, True])
@pytest.mark.parametrize("prompt_len,threshold", [
    (16, 0.0), (17, 0.0), (11, 0.0), (12, None), (3, 0.0)])
def test_logits_of_every_pass_match_the_reference(weights, use_kernel,
                                                  prompt_len, threshold):
    """P mod 4 of 0, 1, 3 (and a prompt shorter than a block); a
    threshold of 0.0 unmasks several positions a pass, the default one a
    pass. Every pass's logits at the block's positions are the
    reference's full forward pass over the committed tokens and the
    block as it stood."""
    rng = np.random.default_rng(prompt_len)
    prompt = rng.integers(0, CFG.vocab_size, prompt_len)
    if prompt_len == 17:
        prompt[[2, 16]] = CFG.mask_token_id     # a prompt holding the id
    n_pass = 0
    for ids, masked, logits in _drive(weights, prompt, 6, threshold,
                                      use_kernel):
        want = np.asarray(ref.forward(CFG, weights, ids))[-CFG.block_length:]
        assert np.abs(logits - want).max() < TOL, (n_pass, masked)
        n_pass += 1
    assert n_pass >= (2 if threshold == 0.0 else 6)


def _reference_confidences(trace, request):
    """Of ``generate()``'s trace (one entry a denoising pass): the
    softmax probability that each generated position's token had at the
    pass that unmasked it, in the order of the engine's record."""
    bl = CFG.block_length
    first = len(request.prompt) // bl * bl
    keep = len(request.prompt) - first
    conf, seen = {}, {}
    for start, _tokens, _masked, logits in trace:
        n_pass = seen[start] = seen.get(start, -1) + 1
        lg = logits.astype(np.float64)
        prob = np.exp(lg - lg.max(-1, keepdims=True))
        prob /= prob.sum(-1, keepdims=True)
        for i in range(bl):
            at = start + i - first - keep       # index into the record
            if at >= 0 and request.block_record[at][1] == n_pass:
                conf[at] = prob[i, request.block_record[at][0]]
    return [conf[at] for at in range(len(request.block_record))]


@pytest.mark.parametrize("threshold", [None, 0.0])
def test_engine_trajectory_is_the_references_generate(weights, threshold):
    """The same tokens at the same passes: the engine's record under the
    scheduler against ``generate()`` by repeated full forward passes.
    ``max_new_tokens`` is not a multiple of the block."""
    eng = _engine(weights, threshold=threshold)
    sched = ContinuousBatchingScheduler(eng)
    rng = np.random.default_rng(5)
    reqs = [sched.submit(rng.integers(0, CFG.vocab_size, p), max_new_tokens=n)
            for p, n in ((9, 7), (16, 9), (3, 5), (22, 10), (35, 6))]
    sched.run()
    thr = CFG.confidence_threshold if threshold is None else threshold
    for r in reqs:
        tokens, passes, trace = ref.generate(CFG, weights, r.prompt,
                                         r.max_new_tokens, threshold=thr)
        assert r.state == "finished" and r.tokens == tokens
        assert len(r.tokens) == r.max_new_tokens
        assert [p for _, p, _ in r.block_record][:len(passes)] == passes
        assert _reference_confidences(trace, r) == pytest.approx(
            [c for _, _, c in r.block_record], rel=1e-4)
        assert r.first_token_time is not None \
            and r.first_token_time >= r.admit_time
    c = eng.status()["passes"]
    assert c["tokens_emitted"] == sum(r.max_new_tokens for r in reqs)
    assert c["tokens_dropped"] == sum(
        len(r.block_record) - len(r.tokens) for r in reqs) > 0
    # all that is left in the pool is what the prefix cache keeps
    assert eng.pool.live_sequences == 0
    assert eng.pool.pages_in_use == eng.prefix_cache.stats()["nodes"]
    if threshold is None:   # nothing reaches 0.9: a block is 4 + 1 passes
        total = c["passes_denoise"] + c["passes_commit"] + c["passes_mixed"]
        assert total >= 5 * 3 and all(
            sorted(p for _, p, _ in r.block_record[-4:]) == [0, 1, 2, 3]
            for r in reqs)


def test_status_reports_the_expert_products_row_tile_and_fill(weights):
    """The row tile of every compiled program and, from the last pass's
    per-expert counts, the share of its row-tile visits' rows that held
    an assignment; the reference path has no tile to report."""
    from paddle_tpu.kernels.grouped_matmul import row_tile, tile_visits
    eng = _engine(weights)
    E, k, bl = CFG.num_experts, CFG.num_experts_per_tok, CFG.block_length
    st = eng.status()["expert_product"]
    assert st["row_tile"] == {
        "decode": {b: row_tile(b * bl * k, E) for b in (1, 2, 4)},
        "chunk": row_tile(16 * k, E)}
    assert st["tile_fill"] is None      # no pass yet
    sched = ContinuousBatchingScheduler(eng)
    rng = np.random.default_rng(8)
    for p in (9, 14, 5):
        sched.submit(rng.integers(0, CFG.vocab_size, p), max_new_tokens=6)
    sched.run()
    load = eng.last_pass_load
    tm = st["row_tile"]["decode"][eng.decode_bucket(
        int(load[0].sum()) // (bl * k))]
    fill = eng.status()["expert_product"]["tile_fill"]
    assert fill == load.sum() / (tile_visits(load, tm) * tm)
    # every layer's assignments lie in one or two visits of 128 rows
    assert 0 < fill <= load[0].sum() / tm
    assert "expert_product" not in _engine(
        weights, use_kernel=False).status()


def test_prefix_hit_is_exact(weights):
    """A page is a whole number of blocks: the second request maps the
    first one's pages and generates the same tokens as without a cache."""
    rng = np.random.default_rng(8)
    shared = rng.integers(0, CFG.vocab_size, 2 * PS)
    prompts = [np.concatenate([shared, rng.integers(0, CFG.vocab_size, k)])
               for k in (5, 7)]
    out = {}
    for cache in (True, False):
        eng = _engine(weights, prefix_cache=cache)
        sched = ContinuousBatchingScheduler(eng)
        reqs = []
        for p in prompts:
            reqs.append(sched.submit(p, max_new_tokens=6))
            sched.run()
        out[cache] = [r.tokens for r in reqs]
        if cache:
            assert reqs[0].cached_prefix_len == 0
            assert reqs[1].cached_prefix_len == 2 * PS
            assert eng.pool.stats()["tokens_reused"] == 2 * PS
    assert out[True] == out[False]


def test_cancel_mid_block_frees_every_page(weights):
    eng = _engine(weights, prefix_cache=False)
    sched = ContinuousBatchingScheduler(eng)
    free = eng.pool.free_pages
    r = sched.submit(np.arange(10), max_new_tokens=12)
    for _ in range(3):                  # prefill, then two passes
        sched.step()
    assert r.state == "running" and eng.masked_positions([r.rid]) > 0
    assert sched.cancel(r.rid)
    assert r.state == "deadline_exceeded"
    assert eng.pool.free_pages == free and sched._reserved_pages == 0


def test_migration_is_refused_for_a_block_engine(weights):
    eng = _engine(weights)
    sched = ContinuousBatchingScheduler(eng)
    r = sched.submit(np.arange(9), max_new_tokens=8)
    sched.step()
    sched.step()
    assert r.state == "running"
    # by name, not by an empty answer that a drain would take for "done"
    with pytest.raises(MigrationUnsupported, match="blocks of 4"):
        sched.migratable_rids()
    with pytest.raises(MigrationUnsupported):
        sched.checkpoint_request(r.rid)
    assert r.state == "running" and r.rid in sched._running
    assert sched.checkpoint_request(10 ** 6) is None    # not running
    assert sched.prepare_migration_in(7, [1, 2, 3], 3, 4) == \
        (False, "engine_unsupported")
    sched.run()
    assert r.state == "finished" and len(r.tokens) == 8


def test_eos_ends_a_request_inside_a_block(weights):
    eng = _engine(weights, threshold=0.0)
    sched = ContinuousBatchingScheduler(eng)
    prompt = np.random.default_rng(2).integers(0, CFG.vocab_size, 8)
    free = sched.submit(prompt, max_new_tokens=8)
    sched.run()
    eos = free.tokens[1]
    r = sched.submit(prompt, max_new_tokens=8, eos_id=eos)
    sched.run()
    assert r.tokens == free.tokens[:free.tokens.index(eos) + 1]


def test_engine_refuses_a_pass_without_its_rows(weights):
    from paddle_tpu.serving import EngineShapeError
    eng = _engine(weights, prefix_cache=False)
    eng.prefill_begin(0, np.arange(8))
    eng.prefill_step(0)
    assert eng.starts_block(0)
    with pytest.raises(EngineShapeError):
        eng.decode([0])                 # the pool was not extended
    eng.pool.extend(0, eng.block_len)
    assert eng.decode([0]) == [([], [], [])]
    with pytest.raises(ValueError):
        _engine(weights, page_size=6)


def test_one_token_engines_are_untouched():
    """The GPT scheduler path takes no block tick."""
    from paddle_tpu.serving.scheduler import _ShapeProbeEngine
    probe = _ShapeProbeEngine((1, 2), (8, 16), 8, 16, 16)
    sched = ContinuousBatchingScheduler(probe)
    assert sched.block_len == 1
    r = sched.submit(np.arange(5), max_new_tokens=3)
    sched.run()
    assert r.state == "finished" and len(r.tokens) == 3
    assert r.passes == 0 and r.block_record is None
