"""The KV pool stays where it is: the decode and chunk programs carry the
whole ``[L, P, ps, nkv, d]`` pool through their layer loop and hand the
paged kernels the pool with a layer index.

What the CPU can show (interpret mode): the layer-indexed kernels read
the layer they are told to, a program writes the rows it was given and no
other row of any layer, and the served tokens are those of the programs
this replaced (the layer loop that took the pool as the scan's ``xs`` and
gave it back as stacked ``ys``). That the compiled programs alias the
pool and keep no second one is ``tests/test_chip_compile.py``'s to show.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.kernels.paged_attention import (paged_attention_decode,
                                                paged_attention_reference,
                                                paged_prefill_attention,
                                                ragged_prefill_attention)
from paddle_tpu.serving import ContinuousBatchingScheduler, ServingEngine
from paddle_tpu.serving import engine as engine_module

L, P, PS, NH, D = 5, 12, 8, 4, 16
LAYERS = [0, L // 2, L - 1]


def _pool(seed):
    rng = np.random.default_rng(seed)
    return (jnp.asarray(rng.standard_normal((L, P, PS, NH, D)), jnp.float32),
            jnp.asarray(rng.standard_normal((L, P, PS, NH, D)), jnp.float32))


# ------------------------------------------------------------- kernels

@pytest.mark.parametrize("layer", LAYERS)
def test_decode_kernel_layer_of_pool(layer):
    """kernel(whole pool, layer) == kernel(that layer's pages) == XLA
    reference, the layer traced."""
    kp, vp = _pool(1)
    rng = np.random.default_rng(2)
    B, npt = 4, 5
    q = jnp.asarray(rng.standard_normal((B, NH, D)), jnp.float32)
    pt = jnp.asarray(rng.integers(1, P, (B, npt)), jnp.int32)
    sl = jnp.asarray([npt * PS, 0, 1, 19], jnp.int32)   # full, idle, ...
    live = np.asarray(sl) > 0

    whole = jax.jit(lambda l: paged_attention_decode(
        q, kp, vp, pt, sl, layer=l))(jnp.int32(layer))
    alone = paged_attention_decode(q, kp[layer], vp[layer], pt, sl)
    np.testing.assert_array_equal(np.asarray(whole), np.asarray(alone))
    ref = jax.jit(lambda l: paged_attention_reference(
        q, kp, vp, pt, sl, layer=l))(jnp.int32(layer))
    np.testing.assert_array_equal(
        np.asarray(ref),
        np.asarray(paged_attention_reference(q, kp[layer], vp[layer],
                                             pt, sl)))
    np.testing.assert_allclose(np.asarray(whole)[live],
                               np.asarray(ref)[live], rtol=2e-5, atol=2e-5)
    # another layer holds other keys: the index is not ignored
    other = paged_attention_decode(q, kp, vp, pt, sl,
                                   layer=(layer + 1) % L)
    assert np.abs(np.asarray(other) - np.asarray(whole))[live].max() > 1e-2


@pytest.mark.parametrize("layer", LAYERS)
def test_ragged_prefill_kernel_layer_of_pool(layer):
    kp, vp = _pool(3)
    rng = np.random.default_rng(4)
    C, npt = 16, 5
    q = jnp.asarray(rng.standard_normal((1, C, NH, D)), jnp.float32)
    pt = jnp.asarray(rng.integers(1, P, (1, npt)), jnp.int32)
    off = jnp.int32(npt * PS - C - 3)

    whole = jax.jit(lambda l, o: ragged_prefill_attention(
        q, kp, vp, pt, o, layer=l))(jnp.int32(layer), off)
    alone = ragged_prefill_attention(q, kp[layer], vp[layer], pt, off)
    np.testing.assert_array_equal(np.asarray(whole), np.asarray(alone))
    ref = jax.jit(lambda l, o: paged_prefill_attention(
        q, kp, vp, pt, o, layer=l))(jnp.int32(layer), off)
    np.testing.assert_array_equal(
        np.asarray(ref),
        np.asarray(paged_prefill_attention(q, kp[layer], vp[layer], pt,
                                           off)))
    np.testing.assert_allclose(np.asarray(whole), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
    other = ragged_prefill_attention(q, kp, vp, pt, off,
                                     layer=(layer + 1) % L)
    assert np.abs(np.asarray(other) - np.asarray(whole)).max() > 1e-2


@pytest.mark.parametrize("attend", [
    paged_attention_decode, paged_attention_reference,
    ragged_prefill_attention, paged_prefill_attention])
def test_pool_rank_and_layer_go_together(attend):
    """A rank-5 pool needs its layer; a rank-4 pool holds just one."""
    kp, vp = _pool(5)
    prefill = attend in (ragged_prefill_attention, paged_prefill_attention)
    q = jnp.zeros((1, 8, NH, D) if prefill else (1, NH, D), jnp.float32)
    pt = jnp.ones((1, 2), jnp.int32)
    last = jnp.int32(0) if prefill else jnp.ones((1,), jnp.int32)
    with pytest.raises(ValueError, match="rank-5 pool needs the `layer`"):
        attend(q, kp, vp, pt, last)
    with pytest.raises(ValueError, match="rank-5 pool needs the `layer`"):
        attend(q, kp[0], vp[0], pt, last, layer=0)


# -------------------------------------------------------------- engine

def _tiny_model(seed=0):
    from paddle_tpu.models.gpt import (GPTForPretraining, GPTModel,
                                       gpt_tiny_config)
    paddle.seed(seed)
    cfg = gpt_tiny_config()
    return GPTForPretraining(GPTModel(cfg)), cfg


def _pools(engine):
    return (np.array(engine.pool.k_pages), np.array(engine.pool.v_pages))


def _assert_only_rows_written(before, after, rows, page_size):
    """Every token row of every layer is bitwise what it was, except
    ``rows`` (all of them written, in every layer) and the sink page."""
    rows = np.unique(np.asarray(rows))
    real = rows[rows >= page_size]
    assert real.size
    for old, new in zip(before, after):
        nl, npg, ps, nkv, d = old.shape
        old = old.reshape(nl, npg * ps, nkv, d)
        new = new.reshape(nl, npg * ps, nkv, d)
        keep = np.ones(npg * ps, bool)
        keep[real] = False
        keep[:page_size] = False                     # the sink page
        np.testing.assert_array_equal(new[:, keep], old[:, keep])
        moved = np.abs(new[:, real] - old[:, real]).reshape(
            nl, real.size, -1).max(-1)
        assert (moved > 0).all(), "a row of some layer was not written"


@pytest.mark.parametrize("use_kernel", [True, False])
def test_engine_programs_write_their_rows_and_no_other(use_kernel):
    model, cfg = _tiny_model()
    eng = ServingEngine(model, cfg, page_size=8, decode_buckets=(1, 2, 4),
                        prefill_chunk=16, use_kernel=use_kernel,
                        autofuse=False)
    ps = eng.pool.page_size
    rng = np.random.default_rng(7)
    # a first sequence fills some pages, so that "what it was" is not zero
    eng.prefill("a", rng.integers(0, cfg.vocab_size, (21,)).astype(np.int32))

    # one chunk (16 rows of a 27-token prompt; the second would be 11 + pad)
    eng.prefill_begin("b", rng.integers(0, cfg.vocab_size,
                                        (27,)).astype(np.int32))
    before = _pools(eng)
    rows = eng.pool.chunk_rows("b", 0, 16)
    eng.prefill_step("b")
    _assert_only_rows_written(before, _pools(eng), rows, ps)
    # the padded last chunk: its padding lands in the sink page
    before = _pools(eng)
    rows = eng.pool.chunk_rows("b", 16, 16)
    assert (np.asarray(rows)[11:] < ps).all()
    eng.prefill_step("b")
    _assert_only_rows_written(before, _pools(eng), rows, ps)

    # one decode of both, two idle slots beside them (bucket 4)
    for sid in ("a", "b"):
        eng.pool.extend(sid)
    before = _pools(eng)
    rows = [eng.pool.table(sid)[(eng.pool.seq_len(sid) - 1) // ps] * ps
            + (eng.pool.seq_len(sid) - 1) % ps for sid in ("a", "b")]
    eng.decode(["a", "b"], bucket=4)
    _assert_only_rows_written(before, _pools(eng), rows, ps)


# The programs this replaced, kept as the reference (python guide: the
# version that was, held equal): the pool rides the scan as ``xs`` and
# comes back as stacked ``ys``, each layer attending its own rank-4 pages.

def _parent_layer_loop(block_step, x, blocks, k_pages, v_pages, rows):
    np_, ps = k_pages.shape[1], k_pages.shape[2]

    def layer(carry, p_kp_vp):
        (x,) = carry
        p, kp, vp = p_kp_vp
        nkv, d = kp.shape[2], kp.shape[3]

        def write(pages, new):
            return pages.reshape(np_ * ps, nkv, d).at[rows].set(
                new.astype(pages.dtype)).reshape(np_, ps, nkv, d)
        x, kp, vp = block_step(x, p, kp, vp, write)
        return (x,), (kp, vp)

    (x,), (k_pages, v_pages) = jax.lax.scan(
        layer, (x,), (blocks, k_pages, v_pages))
    return x, k_pages, v_pages


def _parent_mlp(x, p, dt, eps):
    E = engine_module
    h2 = E._ln(x, p["ln2_w"], p["ln2_b"], eps)
    u = jax.nn.gelu(E._mm("bsh,hf->bsf", h2, p["w1"], dt) + p["b1"],
                    approximate=True)
    return x + E._mm("bsf,fh->bsh", u, p["w2"], dt) + p["b2"]


def _parent_decode_step_fn(params, k_pages, v_pages, tokens, positions,
                           page_table, seq_lens, key, *, eps, temperature,
                           top_k, use_kernel, compute_dtype=None):
    E = engine_module
    wte, wpe = params["wte"], params["wpe"]
    dt = E._compute_dtype(params, compute_dtype)
    B, ps = tokens.shape[0], k_pages.shape[2]
    pos = jnp.maximum(positions, 0).astype(jnp.int32)
    page_table = page_table.astype(jnp.int32)
    seq_lens = seq_lens.astype(jnp.int32)
    x = (E._emb(wte, tokens, dt)[:, None, :]
         + E._emb(wpe, pos, dt)[:, None, :]).astype(dt)
    rows = page_table[jnp.arange(B), pos // ps] * ps + pos % ps
    attend = paged_attention_decode if use_kernel \
        else paged_attention_reference

    def block(x, p, kp, vp, write):
        h = E._ln(x, p["ln1_w"], p["ln1_b"], eps)
        qkv = E._mm("bsh,hknd->bsknd", h, p["wqkv"], dt) + p["bqkv"]
        q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
        kp, vp = write(kp, k[:, 0]), write(vp, v[:, 0])
        attn = attend(q[:, 0], kp, vp, page_table, seq_lens)
        o = E._mm("bnd,ndh->bh", attn.astype(x.dtype), p["wo"], dt)
        x = x + o[:, None, :] + p["bo"]
        return _parent_mlp(x, p, dt, eps), kp, vp

    x, k_pages, v_pages = _parent_layer_loop(
        block, x, params["blocks"], k_pages, v_pages, rows)
    h = E._ln(x, params["lnf_w"], params["lnf_b"], eps)
    logits = E._mm("bsh,vh->bsv", h, wte, dt)[:, 0]
    nxt = E.sample_logits(logits, key, temperature, top_k)
    return k_pages, v_pages, nxt.astype(jnp.int32)


def _parent_chunk_prefill_fn(params, k_pages, v_pages, ids, q_offset,
                             chunk_len, page_table, dest_rows, key, *, eps,
                             temperature, top_k, use_kernel=False,
                             compute_dtype=None):
    E = engine_module
    wte, wpe = params["wte"], params["wpe"]
    dt = E._compute_dtype(params, compute_dtype)
    C = ids.shape[1]
    q_offset = jnp.asarray(q_offset, jnp.int32)
    chunk_len = jnp.asarray(chunk_len, jnp.int32)
    positions = jnp.minimum(q_offset + jnp.arange(C, dtype=jnp.int32),
                            wpe.shape[0] - 1)
    x = (E._emb(wte, ids, dt) + E._emb(wpe, positions, dt)[None]).astype(dt)
    page_table = page_table.astype(jnp.int32)
    attend = ragged_prefill_attention if use_kernel \
        else paged_prefill_attention

    def block(x, p, kp, vp, write):
        h = E._ln(x, p["ln1_w"], p["ln1_b"], eps)
        qkv = E._mm("bsh,hknd->bsknd", h, p["wqkv"], dt) + p["bqkv"]
        q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
        kp, vp = write(kp, k[0]), write(vp, v[0])
        attn = attend(q, kp, vp, page_table, q_offset)
        o = E._mm("bsnd,ndh->bsh", attn.astype(x.dtype), p["wo"], dt)
        return _parent_mlp(x + o + p["bo"], p, dt, eps), kp, vp

    x, k_pages, v_pages = _parent_layer_loop(
        block, x, params["blocks"], k_pages, v_pages,
        dest_rows.astype(jnp.int32))
    h_last = jax.lax.dynamic_slice_in_dim(
        x, jnp.maximum(chunk_len - 1, 0), 1, axis=1)
    h_last = E._ln(h_last, params["lnf_w"], params["lnf_b"], eps)
    logits = E._mm("bsh,vh->bsv", h_last, wte, dt)[:, 0]
    tok = E.sample_logits(logits, key, temperature, top_k)
    return k_pages, v_pages, tok.astype(jnp.int32)


def _serve(engine, prompts, max_new):
    sched = ContinuousBatchingScheduler(engine)
    rids = [sched.submit(p, max_new_tokens=max_new).rid for p in prompts]
    finished = {r.rid: r for r in sched.run()}
    assert all(finished[r].state == "finished" for r in rids)
    return [list(map(int, finished[r].tokens)) for r in rids]


@pytest.mark.parametrize("use_kernel", [True, False])
def test_chunked_engine_tokens_equal_parents_programs(monkeypatch,
                                                      use_kernel):
    """Greedy tokens of the chunked engine == those of the programs that
    stacked the pool, through the scheduler on a ragged mix, and the two
    pools end bitwise equal outside the sink page."""
    model, cfg = _tiny_model()
    kw = dict(page_size=8, decode_buckets=(1, 2, 4), prefill_chunk=16,
              prefix_cache=True, use_kernel=use_kernel, autofuse=False)
    rng = np.random.default_rng(11)
    prompts = [rng.integers(0, cfg.vocab_size, (n,)).astype(np.int32)
               for n in (23, 9, 40, 17, 33)]
    eng = ServingEngine(model, cfg, **kw)
    got = _serve(eng, prompts, 10)

    monkeypatch.setattr(engine_module, "decode_step_fn",
                        _parent_decode_step_fn)
    monkeypatch.setattr(engine_module, "chunk_prefill_fn",
                        _parent_chunk_prefill_fn)
    parent = ServingEngine(model, cfg, **kw)
    want = _serve(parent, prompts, 10)
    assert got == want
    assert len({tuple(t) for t in got}) > 1      # not one token for all
    for new, old in zip(_pools(eng), _pools(parent)):
        np.testing.assert_array_equal(new[:, 1:], old[:, 1:])


def test_status_reports_each_programs_memory():
    model, cfg = _tiny_model()
    eng = ServingEngine(model, cfg, page_size=8, decode_buckets=(1, 2),
                        prefill_chunk=16)
    mem = eng.status()["program_memory"]
    assert mem["pool_bytes"] == 2 * eng.pool.k_pages.nbytes
    assert sorted(mem["decode"]) == [1, 2]
    for sizes in list(mem["decode"].values()) + [mem["chunk"]]:
        assert set(sizes) == {"temp_bytes", "alias_bytes"}
        assert sizes["temp_bytes"] >= 0 and sizes["alias_bytes"] >= 0
    lazy = ServingEngine(model, cfg, page_size=8, decode_buckets=(1,),
                         aot=False)
    assert lazy.status()["program_memory"]["decode"] == {}
    assert "chunk" not in lazy.status()["program_memory"]
