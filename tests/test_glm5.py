"""GLM-5.2 served from a latent page row and index keys
(``serving/glm5_engine.py``) held to its plain float32 reference
(``models/glm5_reference.py``), on a tiny configuration whose contexts
cross the selection: 16 positions selected over 20 to 100.

The weights are float32 and drawn wide (``initializer_range`` 0.07, the
attention's up-projections three times that): the selection then moves a
layer's output by far more than float32 rounds it, so agreement to 1e-4
says the program selects, shares and routes as the reference does.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.kernels import sparse_mla as K
from paddle_tpu.models import glm5 as M
from paddle_tpu.models import glm5_reference as R
from paddle_tpu.serving import ContinuousBatchingScheduler
from paddle_tpu.serving.glm5_engine import Glm5ServingEngine

CFG = M.glm5_tiny_config(initializer_range=0.07)
TOL = 1e-4


@pytest.fixture(scope="module")
def weights():
    return M.init_glm5_weights(CFG, 3)


def _engine(weights, **kw):
    base = dict(page_size=16, num_pages=64, max_seq_len=256,
                decode_buckets=(1, 2, 4), prefill_chunk=32, aot=False,
                keep_logits=True, selection_every=1)
    base.update(kw)
    return Glm5ServingEngine(weights, CFG, **base)


def _serve(eng, sid, prompt, n_new):
    """Chunked prefill, then ``n_new`` decodes of one sequence: the logits
    at every served position, the tokens, and the selections read."""
    eng.prefill_begin(sid, prompt)
    done = False
    while not done:
        _, done, tok = eng.prefill_step(sid)
    logits, tokens = [eng.last_logits[None]], [tok]
    for _ in range(n_new):
        eng.pool.extend(sid)
        tokens.append(eng.decode([sid])[0])
        logits.append(eng.last_logits)
    return np.concatenate(logits), tokens


@pytest.mark.parametrize("use_kernel", [False, True])
def test_chunked_prefill_and_decode_agree_with_the_full_forward(
        weights, use_kernel):
    """Prompts inside a chunk, across chunks, and longer than the
    selection; every served position's logits against the reference's
    full forward of the prompt and the served tokens; every decode's
    selections against the reference's exact top-k."""
    eng = _engine(weights, use_kernel=use_kernel)
    rng = np.random.default_rng(0)
    for P in (5, 40, 70):
        prompt = rng.integers(0, CFG.vocab_size, P)
        got, tokens = _serve(eng, f"s{P}", prompt, 6)
        ids = np.concatenate([prompt, tokens[:-1]])
        ref, masks = R.forward(weights, ids, CFG, return_selections=True)
        assert np.abs(got - np.asarray(ref)[P - 1:]).max() < TOL
        log = eng.selection_log[f"s{P}"]
        assert [at for at, *_ in log] == list(range(P, P + 6))
        for at, rows, experts, gates in log:
            # each expert layer's choice among every routed expert, its
            # gates normalised and scaled
            assert experts.shape == gates.shape == (
                len(CFG.moe_layers), CFG.num_experts_per_tok)
            assert all(len(set(e)) == len(e) for e in experts.tolist())
            assert 0 <= experts.min() and experts.max() < CFG.n_routed_experts
            np.testing.assert_allclose(gates.sum(1), 2.5, rtol=1e-5)
            for l in range(CFG.num_hidden_layers):
                want = set(np.nonzero(np.asarray(
                    masks[CFG.share_source(l)])[at])[0])
                assert set(int(v) for v in rows[l] if v >= 0) == want
                assert len(want) == min(at + 1, CFG.index_topk)
        eng.release(f"s{P}")


def test_a_batch_of_ragged_sequences_under_the_scheduler(weights):
    """The scheduler's mix (chunked prefill and a ragged decode batch)
    gives each request the tokens of its own greedy reference."""
    eng = _engine(weights, use_kernel=False, keep_logits=False,
                  selection_every=0)
    sched = ContinuousBatchingScheduler(eng)
    rng = np.random.default_rng(4)
    prompts = [rng.integers(0, CFG.vocab_size, n) for n in (9, 33, 52, 20)]
    reqs = [sched.submit(p, max_new_tokens=5) for p in prompts]
    sched.run()
    for p, r in zip(prompts, reqs):
        ids = np.concatenate([p, r.tokens[:-1]])
        ref = np.asarray(R.forward(weights, ids, CFG))[len(p) - 1:]
        assert len(r.tokens) == 5
        assert (ref[np.arange(5), r.tokens] >= ref.max(-1) - TOL).all()


def test_the_exact_top_k_agrees_with_lax_top_k_ties_included():
    """The bit-by-bit selection keeps the set ``jax.lax.top_k`` keeps (ties
    to the lower position), and every valid position of a row that has
    fewer than ``k``; the decode tick's positions are the same sets."""
    rng = np.random.default_rng(1)
    R_, W, k = 6, 300, 16
    scores = rng.standard_normal((R_, W)).astype(np.float32)
    scores[1, 100:140] = np.sort(scores[1])[-10]    # 40 ties at the edge
    scores[2] = np.round(scores[2] * 2) / 2         # ties everywhere
    scores[3, 5:] = -1e30                   # a floor of equal scores
    scores[4] = -np.abs(scores[4])          # negatives only
    valid = np.ones((R_, W), bool)
    valid[5, 10:] = False                   # a row with 10 positions
    mask = np.asarray(M.exact_topk_mask(jnp.asarray(scores),
                                        jnp.asarray(valid), k))
    idx, ok = (np.asarray(v) for v in M.top_positions(
        jnp.asarray(scores), jnp.asarray(valid), k))
    _, want = jax.lax.top_k(jnp.asarray(scores[:5]), k)
    for r in range(5):
        expect = set(np.asarray(want[r]).tolist())
        assert set(np.nonzero(mask[r])[0].tolist()) == expect, r
        assert set(idx[r][ok[r]].tolist()) == expect, r
    assert set(np.nonzero(mask[5])[0].tolist()) == set(range(10))
    assert set(idx[5][ok[5]].tolist()) == set(range(10))
    assert mask.sum(1).tolist() == ok.sum(1).tolist() == [k] * 5 + [10]


def test_a_shared_layer_attends_over_the_full_layers_selection(weights):
    """The program agrees with the reference in which the shared layers
    take layer 0's selection, and not with one in which they attend over
    every position: IndexShare is what it computes."""
    eng = _engine(weights, use_kernel=False)
    prompt = np.random.default_rng(2).integers(0, CFG.vocab_size, 60)
    got, _ = _serve(eng, "a", prompt, 0)
    x = jnp.asarray(weights["embed"])[prompt]
    S = len(prompt)
    dense = jnp.tril(jnp.ones((S, S), bool))
    shared, unshared = x, x
    sel_a, sel_b = {}, {}
    with jax.default_matmul_precision("highest"):
        for l, p in enumerate(weights["layers"]):
            shared = R.layer(p, shared, l, CFG, sel_a)
            if CFG.indexer_types[l] == "shared":
                sel_b[CFG.share_source(l)] = dense
                unshared = R.layer(p, unshared, l, CFG, sel_b)
                sel_b[CFG.share_source(l)] = sel_a[CFG.share_source(l)]
            else:
                unshared = R.layer(p, unshared, l, CFG, sel_b)
        head = lambda h: np.asarray(M.rms_norm(
            h, weights["norm"], CFG.rms_norm_eps) @ weights["head"])[-1]
    assert np.abs(got[0] - head(shared)).max() < TOL
    assert np.abs(got[0] - head(unshared)).max() > 100 * TOL


def test_sigmoid_routing_with_bias_normalisation_and_scale(weights):
    p = weights["layers"][1]
    a = jax.random.normal(jax.random.key(5), (7, CFG.hidden_size))
    gates, idx = M.route(a, p["router"], p["router_bias"], CFG)
    s = 1 / (1 + np.exp(-(np.asarray(a, np.float64)
                          @ np.asarray(p["router"], np.float64))))
    choice = s + np.asarray(p["router_bias"], np.float64)
    for t in range(7):
        want = np.argsort(-choice[t])[:CFG.num_experts_per_tok]
        assert set(np.asarray(idx[t]).tolist()) == set(want.tolist())
        g = s[t, np.asarray(idx[t])]
        np.testing.assert_allclose(
            np.asarray(gates[t]), 2.5 * g / g.sum(), rtol=1e-5)
    # the bias moves the choice: without it some token picks otherwise
    plain = np.argsort(-s, 1)[:, :CFG.num_experts_per_tok]
    assert any(set(plain[t]) != set(np.asarray(idx[t]).tolist())
               for t in range(7))


@pytest.mark.parametrize("use_kernel", [False, True])
def test_the_expert_shares_add_up_to_the_uncut_layer(weights, use_kernel):
    """Every rank's share of the routed experts, plus the shared expert
    counted once, is the reference's layer with all experts held."""
    E, G = CFG.n_routed_experts, CFG.experts_held
    key = jax.random.key(9)
    up = 0.07 * jax.random.normal(key, (E, CFG.hidden_size,
                                        2 * CFG.moe_intermediate_size))
    down = 0.07 * jax.random.normal(jax.random.fold_in(key, 1), (
        E, CFG.moe_intermediate_size, CFG.hidden_size))
    p = dict(weights["layers"][1])
    a = jax.random.normal(jax.random.fold_in(key, 2), (12, CFG.hidden_size))
    whole = CFG.__class__(**dict(CFG.__dict__, experts_held=E,
                                 expert_offset=0))
    with jax.default_matmul_precision("highest"):
        want = R.expert_share(dict(p, experts_gate_up=up,
                                   experts_down=down), a, whole) \
            + M.swiglu(a, p["shared_gate_up"], p["shared_down"])
        total = M.swiglu(a, p["shared_gate_up"], p["shared_down"])
        for r in range(E // G):
            rank = CFG.__class__(**dict(CFG.__dict__, expert_offset=r * G))
            share = dict(p, experts_gate_up=up[r * G:(r + 1) * G],
                         experts_down=down[r * G:(r + 1) * G])
            out, counts, _ = M.held_expert_ffn(share, a, rank,
                                               use_kernel=use_kernel)
            total = total + out
            assert int(counts.sum()) <= 12 * CFG.num_experts_per_tok
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               atol=1e-4, rtol=1e-4)


def test_the_kernels_agree_with_their_references():
    """Interpret mode: the index score over paged keys (decode: a query a
    sequence; prefill: a chunk's queries in tiles), the writes of packed
    rows, the attention over each query's selected rows fetched row by
    row through the table (bfloat16 rows two to a word, float32 one), and
    a chunk's attention over its pages under a mask."""
    rng = np.random.default_rng(3)
    nI, dI, ps, P = 4, 16, 8, 12
    keys = jnp.asarray(rng.standard_normal((2, P, ps, dI)), jnp.float32)
    table = jnp.asarray([[3, 1, 7, 0], [5, 2, 0, 0]], jnp.int32)
    lens = jnp.asarray([27, 11], jnp.int32)
    q = jnp.asarray(rng.standard_normal((2, 1, nI, dI)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((2, 1, nI)), jnp.float32)
    for layer in (0, 1):
        got = K.index_scores(q, w, keys, table, lens, layer, tq=1)
        want = K.index_scores_reference(q, w, keys, table, lens, layer)
        assert np.abs(np.asarray(got - want)).max() < 1e-5
    qc = jnp.asarray(rng.standard_normal((1, 16, nI, dI)), jnp.float32)
    wc = jnp.asarray(rng.standard_normal((1, 16, nI)), jnp.float32)
    got = K.index_scores(qc, wc, keys, table[:1], lens[:1], 1, tq=8)
    want = K.index_scores_reference(qc, wc, keys, table[:1], lens[:1], 1)
    assert np.abs(np.asarray(got - want)).max() < 1e-5
    for dtype, width in ((jnp.float32, 128), (jnp.bfloat16, 256)):
        rows = jnp.asarray(rng.standard_normal((3 * P * ps, width)), dtype)
        assert np.array_equal(np.asarray(K.unpack_rows(
            K.pack_rows(rows), dtype)[0]), np.asarray(
            rows[:, :K.row_words(width, dtype)]))
        words = K.row_words(width, dtype)
        empty = jnp.zeros((3, P, ps, 1, words), jnp.uint32)
        at = jnp.asarray(rng.permutation(P * ps)[:40], jnp.int32)
        new = K.pack_rows(rows[:40])
        pages = K.write_rows(empty, 1, at, new)
        assert np.array_equal(np.asarray(pages), np.asarray(
            K.write_rows_reference(empty, 1, at, new)))
        pages = K.write_rows_reference(
            empty, 1, jnp.arange(P * ps), K.pack_rows(rows[:P * ps]))
        # a tick: three queries, one idle, over their pages' rows
        qa = jnp.asarray(rng.standard_normal((3, 4, width)), dtype)
        tab = jnp.asarray([[4, 2, 9, 1], [3, 0, 0, 0], [0, 0, 0, 0]],
                          jnp.int32)
        positions = jnp.asarray(np.stack([
            rng.permutation(30)[:16], rng.permutation(16),
            np.zeros(16, int)]), jnp.int32)
        counts = jnp.asarray([16, 5, 0], jnp.int32)
        got = K.sparse_mla(qa, pages, 1, tab, positions, counts, 0.2, 64)
        want = K.sparse_mla_reference(qa, pages, 1, tab, positions, counts,
                                      0.2, 64)
        tol = 1e-5 if dtype == jnp.float32 else 2e-2
        assert np.abs(np.asarray(got - want)).max() < tol
        assert np.abs(np.asarray(got[2])).max() == 0
        # a chunk of 32 queries at positions 40..71 over its masked pages
        C, nh, ps2 = 32, 8, 16
        qc = jnp.asarray(rng.standard_normal((C, nh, width)), dtype)
        pages2 = K.pack_rows(jnp.asarray(
            rng.standard_normal((3, P, ps2, 1, width)), dtype))
        tab = jnp.asarray([[4, 2, 9, 1, 0, 0]], jnp.int32)
        pos = 40 + np.arange(C)
        sel = (rng.random((C, 6 * ps2)) < 0.3) \
            & (np.arange(6 * ps2)[None] <= pos[:, None])
        sel[np.arange(C), pos] = True
        bias = jnp.asarray(np.where(sel, 0.0, -1e30), jnp.float32)
        got = K.masked_mla_chunk(qc, pages2, 1, tab, 40, bias, 0.3, 64)
        want = K.masked_mla_chunk_reference(qc, pages2, 1, tab, bias, 0.3,
                                            64)
        assert np.abs(np.asarray(got - want)).max() < tol


def test_pool_rows_and_status(weights):
    eng = _engine(weights, use_kernel=False)
    # float32 rows: a number a 32-bit word, a row alone on its axis
    assert eng.pool.k_pages.shape == (5, 64, 16, 1, CFG.row_width)
    assert eng.pool.k_pages.dtype == jnp.uint32
    assert eng.pool.aux_pages is eng.pool.v_pages
    assert eng.pool.aux_pages.shape == (2, 64, 16, CFG.index_head_dim)
    st = eng.status()
    assert st["cache_bytes_per_token"] == 4 * (5 * 256 + 2 * 16)
    assert st["cache_bytes_needed"] == 4 * (5 * 40 + 2 * 16)
    assert st["pool"]["pool_bytes"] == eng.pool.k_pages.nbytes \
        + eng.pool.aux_pages.nbytes
    with pytest.raises(ValueError, match="prefix_cache"):
        _engine(weights, prefix_cache=True)


def _benchmark_family():
    import importlib.util
    import os
    import sys
    bench = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark")
    spec = importlib.util.spec_from_file_location(
        "bench_glm5_for_tests", os.path.join(bench, "models", "glm5.py"))
    module = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = module
    spec.loader.exec_module(module)
    return module, module.load_config(os.path.join(
        bench, "tests", "configs", "glm5-tiny.json"))


def test_the_benchmarks_reference_is_the_programs_reference():
    """The benchmark's own copy (blocks of queries, the absorbed form,
    selections as positions) against this package's reference (the
    expanded form, selections as masks) on the same weights: logits and
    every full layer's selection at every position."""
    fam, cfg = _benchmark_family()
    pcfg = fam.program_config(cfg)
    assert pcfg == CFG
    whole = fam.whole_weights(cfg, 13)
    ids = np.random.default_rng(6).integers(0, CFG.vocab_size, 70)
    theirs, sel, _ = fam.reference_logits(cfg, whole, ids)
    ours, masks = R.forward(whole, ids, pcfg, return_selections=True)
    assert np.abs(np.asarray(theirs) - np.asarray(ours)).max() < TOL
    assert sorted(sel) == sorted(masks) == list(pcfg.full_layers)
    for l, (positions, ok) in sel.items():
        for t in range(len(ids)):
            assert set(positions[t][ok[t]].tolist()) == set(
                np.nonzero(np.asarray(masks[l])[t])[0].tolist()), (l, t)


def test_the_published_shapes_count_what_the_configuration_says():
    cfg = M.Glm5Config(vocab_size=19360)
    assert cfg.row_width == 768 and cfg.latent_dim == 576
    assert cfg.full_layers == (0, 4)
    assert [cfg.share_source(l) for l in range(5)] == [0, 0, 0, 0, 4]
    assert M.param_count(cfg) == 3_881_517_056
