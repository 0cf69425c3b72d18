"""SDAR-MoE's layer functions and kernels against the plain reference.

Tiny widths, seeded random weights, float32 (``conftest.py`` sets exact
float32 matmuls). Tolerances: the program and the reference do the same
float32 arithmetic in another order (grouped against looped experts,
online against dense softmax), so logits of size ~1 agree to 1e-4; the
reference with float8 operands, the control, must miss that by far.
"""
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.kernels import paged_attention as pa
from paddle_tpu.models import sdar, sdar_reference as ref
from paddle_tpu.serving.sdar_engine import sdar_chunk_prefill_fn

TOL = 1e-4      # float32 against float32, another order of summation

CFG = sdar.sdar_moe_tiny_config()


@pytest.fixture(scope="module")
def weights():
    return sdar.init_sdar_weights(CFG, 11)


def _pool(cfg, pages=12, ps=8):
    shape = (cfg.num_hidden_layers, pages, ps, cfg.num_key_value_heads,
             cfg.head_dim)
    return jnp.zeros(shape, jnp.float32), jnp.zeros(shape, jnp.float32)


def _prefill_logits(weights, ids, use_kernel, chunk=16, ps=8):
    """The chunk program over a prompt's whole blocks, chunk by chunk
    through the pool: logits ``[S, V]``."""
    kp, vp = _pool(CFG, ps=ps)
    S = len(ids)
    table = np.arange(1, 1 + -(-S // ps), dtype=np.int32)
    table = np.pad(table, (0, 11 - len(table)))[None]
    out = []
    for start in range(0, S, chunk):
        clen = min(chunk, S - start)
        buf = np.zeros((1, chunk), np.int32)
        buf[0, :clen] = ids[start:start + clen]
        pos = start + np.arange(chunk)
        rows = np.where(pos < S, table[0, np.minimum(pos // ps, 10)] * ps
                        + pos % ps, pos % ps).astype(np.int32)
        kp, vp, _, logits = sdar_chunk_prefill_fn(
            weights, kp, vp, jnp.asarray(buf), start, clen,
            jnp.asarray(table), jnp.asarray(rows), cfg=CFG,
            use_kernel=use_kernel, return_logits=True)
        out.append(np.asarray(logits)[:clen])
    return np.concatenate(out)


@pytest.mark.parametrize("use_kernel", [False, True])
@pytest.mark.parametrize("length", [16, 28])
def test_full_model_logits_match_the_reference(weights, use_kernel, length):
    ids = np.random.default_rng(length).integers(0, CFG.vocab_size, length)
    got = _prefill_logits(weights, ids, use_kernel)
    want = np.asarray(ref.forward(CFG, weights, ids))
    assert np.abs(got - want).max() < TOL


def test_one_layer_matches_the_reference(weights):
    cfg = sdar.sdar_moe_tiny_config(num_hidden_layers=1)
    w = sdar.init_sdar_weights(cfg, 5)
    ids = np.random.default_rng(1).integers(0, cfg.vocab_size, 12)
    kp, vp = _pool(cfg)
    table = np.zeros((1, 11), np.int32)
    table[0, :2] = (1, 2)
    rows = (8 + np.arange(16)).astype(np.int32)
    rows[12:] = np.arange(12, 16) % 8
    buf = np.zeros((1, 16), np.int32)
    buf[0, :12] = ids
    _, _, load, logits = sdar_chunk_prefill_fn(
        w, kp, vp, jnp.asarray(buf), 0, 12, jnp.asarray(table),
        jnp.asarray(rows), cfg=cfg, use_kernel=False, return_logits=True)
    want = np.asarray(ref.forward(cfg, w, ids))
    assert np.abs(np.asarray(logits)[:12] - want).max() < TOL
    # every real token is counted once per expert it was given
    assert int(np.asarray(load).sum()) == 12 * cfg.num_experts_per_tok


def test_float8_control_fails_and_bfloat16_does_not(weights):
    """The control: float8 operands miss the tolerance of the float32
    comparison by orders of magnitude."""
    ids = np.random.default_rng(2).integers(0, CFG.vocab_size, 24)
    want = np.asarray(ref.forward(CFG, weights, ids))
    gap = {m: np.abs(np.asarray(ref.forward(CFG, weights, ids, mode=m))
                     - want).max() for m in ("bf16", "fp8")}
    assert gap["fp8"] > 100 * TOL
    assert gap["fp8"] > 3 * gap["bf16"]


# ---- the grouped expert product -------------------------------------------

def _looped_moe(a, w_router, gate_up, down, cfg):
    """The expert layer as a loop over tokens and their experts."""
    a = np.asarray(a, np.float64)
    logits = a @ np.asarray(w_router, np.float64)
    probs = np.exp(logits - logits.max(-1, keepdims=True))
    probs /= probs.sum(-1, keepdims=True)
    out = np.zeros_like(a)
    for n in range(a.shape[0]):
        top = np.argsort(-probs[n], kind="stable")[:cfg.num_experts_per_tok]
        weights = probs[n, top] / probs[n, top].sum()
        for e, w in zip(top, weights):
            gate, up = np.split(a[n] @ np.asarray(gate_up[e], np.float64), 2)
            out[n] += w * ((gate / (1 + np.exp(-gate)) * up)
                           @ np.asarray(down[e], np.float64))
    return out


@pytest.mark.parametrize("use_kernel", [True, False],
                         ids=["kernel", "ragged_dot"])
@pytest.mark.parametrize("layer", [0, 2])
def test_grouped_expert_product_against_the_loop(weights, layer, use_kernel):
    """One expert gets every token and one gets none; through the
    grouped-matmul kernel and through the reference path."""
    E = CFG.num_experts
    rng = np.random.default_rng(7)
    a = jnp.asarray(rng.standard_normal((13, CFG.hidden_size)), jnp.float32)
    # there is no bias: steer with one input feature that is 1 on every row
    a = a.at[:, 0].set(1.0)
    router = 0.1 * np.array(weights["blocks"]["router"][layer])
    router[0, :] = 0.0
    router[0, 0], router[0, 1] = 40.0, -40.0
    out, load = sdar.moe_ffn(a, layer, jnp.asarray(router),
                             weights["experts"], CFG, use_kernel=use_kernel)
    sl = slice(layer * E, (layer + 1) * E)
    want = _looped_moe(a, router, weights["experts"]["gate_up"][sl],
                       weights["experts"]["down"][sl], CFG)
    load = np.asarray(load)
    assert load[0] == 13 and load[1] == 0
    assert load.sum() == 13 * CFG.num_experts_per_tok
    assert np.abs(np.asarray(out) - want).max() < 1e-5


def test_route_keeps_every_token_and_renormalises(weights):
    a = jnp.asarray(np.random.default_rng(3).standard_normal(
        (40, CFG.hidden_size)), jnp.float32)
    w, idx = sdar.route(a, weights["blocks"]["router"][0], CFG)
    assert w.shape == idx.shape == (40, CFG.num_experts_per_tok)
    assert np.allclose(np.asarray(w).sum(-1), 1.0, atol=1e-6)
    assert all(len(set(row)) == len(row) for row in np.asarray(idx))
    # the router runs in float32 whatever the weights' type
    w16, idx16 = sdar.route(a.astype(jnp.bfloat16),
                            weights["blocks"]["router"][0]
                            .astype(jnp.bfloat16), CFG)
    assert w16.dtype == jnp.float32


def test_choose_unmask_is_the_references_rule():
    rng = np.random.default_rng(9)
    conf = rng.random((64, 4)).astype(np.float32)
    masked = rng.random((64, 4)) < 0.6
    for thr, per in ((0.9, 1), (0.5, 1), (0.0, 1), (0.7, 2)):
        got = np.asarray(sdar.choose_unmask(jnp.asarray(conf),
                                            jnp.asarray(masked), thr, per))
        for b in range(64):
            want = ref.unmask_choice(conf[b], masked[b], thr, per) \
                if masked[b].any() else np.zeros(4, bool)
            assert (got[b] == want).all(), (thr, per, b)


def test_published_sizes():
    cfg = sdar.SdarMoeConfig(num_hidden_layers=7)
    assert abs(sdar.active_matmul_params(cfg) - 709.36e6) < 0.01e6
    shapes = sdar.sdar_weight_shapes(cfg)
    n = sum(int(np.prod(s)) for group in shapes.values()
            for s in (group.values() if isinstance(group, dict)
                      else [group]))
    assert abs(n - 4984e6) < 1e6        # seven layers and both tables
    assert cfg.group == 8 and cfg.unmask_per_pass == 1


# ---- the paged kernels: grouped heads and the block rule -------------------

def _paged_case(rng, nh, nkv, d=16, ps=8, pages=9, S=40):
    kp = jnp.asarray(rng.standard_normal((pages, ps, nkv, d)), jnp.float32)
    vp = jnp.asarray(rng.standard_normal((pages, ps, nkv, d)), jnp.float32)
    table = jnp.asarray(rng.permutation(np.arange(1, pages))[None, :6],
                        jnp.int32)
    k = np.asarray(kp)[np.asarray(table)[0]].reshape(-1, nkv, d)[:S]
    v = np.asarray(vp)[np.asarray(table)[0]].reshape(-1, nkv, d)[:S]
    return kp, vp, table, k, v


def _dense(q, k, v, mask, g):
    k, v = np.repeat(k, g, 1), np.repeat(v, g, 1)
    s = np.einsum("snd,tnd->nst", q, k) / np.sqrt(q.shape[-1])
    s = np.where(mask[None], s, -1e30)
    p = np.exp(s - s.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    return np.einsum("nst,tnd->snd", p, v)


@pytest.mark.parametrize("block", [1, 4])
@pytest.mark.parametrize("nh,nkv", [(4, 4), (8, 2)])
@pytest.mark.parametrize("kernel", ["ragged", "xla"])
def test_chunk_attention_grouped_heads_and_block_rule(block, nh, nkv,
                                                      kernel):
    rng = np.random.default_rng(nh * 10 + block)
    kp, vp, table, k, v = _paged_case(rng, nh, nkv)
    off, C = 24, 16
    q = rng.standard_normal((C, nh, 16)).astype(np.float32)
    fn = pa.ragged_prefill_attention if kernel == "ragged" \
        else pa.paged_prefill_attention
    got = np.asarray(fn(jnp.asarray(q)[None], kp, vp, table, off,
                        block=block))[0]
    rows, cols = off + np.arange(C), np.arange(40)
    mask = cols[None, :] // block <= rows[:, None] // block
    assert np.abs(got - _dense(q, k, v, mask, nh // nkv)).max() < 1e-5


def test_block_of_one_is_todays_kernel_bit_for_bit():
    rng = np.random.default_rng(0)
    kp, vp, table, _, _ = _paged_case(rng, 4, 4)
    q = jnp.asarray(rng.standard_normal((1, 16, 4, 16)), jnp.float32)
    make = lambda **kw: lambda q, kp, vp: pa.ragged_prefill_attention(
        q, kp, vp, table, 24, **kw)
    plain, one = make(), make(block=1)
    assert (np.asarray(plain(q, kp, vp)) == np.asarray(one(q, kp, vp))).all()
    assert jax.jit(plain).lower(q, kp, vp).as_text() == \
        jax.jit(one).lower(q, kp, vp).as_text()


@pytest.mark.parametrize("use_kernel", [False, True])
def test_block_attention_through_the_decode_kernel(use_kernel):
    """A block's positions ride the decode kernel as one group a KV head:
    every position sees the prefix and the whole block."""
    from paddle_tpu.serving.sdar_engine import block_attention
    rng = np.random.default_rng(4)
    nh, nkv, d, bl = 8, 2, 16, 4
    kp, vp, table, k, v = _paged_case(rng, nh, nkv, S=36)
    q = rng.standard_normal((bl, nh, d)).astype(np.float32)
    got = np.asarray(block_attention(
        jnp.asarray(q)[None], kp, vp, table, jnp.asarray([36], jnp.int32),
        None, use_kernel))[0]
    want = _dense(q, k, v, np.ones((bl, 36), bool), nh // nkv)
    assert np.abs(got - want).max() < 1e-5


def _decode_case(nh, nkv):
    rng = np.random.default_rng(nh)
    d, ps, pages = 16, 8, 12
    kp = jnp.asarray(rng.standard_normal((3, pages, ps, nkv, d)), jnp.float32)
    vp = jnp.asarray(rng.standard_normal((3, pages, ps, nkv, d)), jnp.float32)
    q = jnp.asarray(rng.standard_normal((3, nh, d)), jnp.float32)
    table = jnp.asarray(rng.integers(1, pages, (3, 5)), jnp.int32)
    return q, kp, vp, table, jnp.asarray([37, 0, 9], jnp.int32)


def _decode_kernel_names(*args):
    text = str(jax.make_jaxpr(
        lambda *a: pa.paged_attention_decode(*a, layer=1))(*args))
    return set(re.findall(r"name=(paged_attention_decode\w*)", text))


# queries a KV head: 32, 32, 16 and 24 on either side of the edge at 16,
# 8, and GPT's 1
@pytest.mark.parametrize("nh,nkv", [(64, 2), (128, 4), (32, 2), (48, 2),
                                    (16, 2), (8, 8)])
def test_decode_kernel_narrow_and_wide_groups(nh, nkv):
    """Groups that are whole sublane tiles (16, 32 queries a KV head) go
    through the MXU head by head under a name of their own, the others
    (8, 24, GPT's 1) stay on the VPU under the kernel's old name: both
    against the dense reference, an idle slot among the sequences."""
    q, kp, vp, table, lens = _decode_case(nh, nkv)
    got = pa.paged_attention_decode(q, kp, vp, table, lens, layer=1)
    want = pa.paged_attention_reference(q, kp, vp, table, lens, layer=1)
    live = np.array([0, 2])
    assert np.abs(np.asarray(got - want))[live].max() < 1e-5
    assert np.isfinite(np.asarray(got)).all()
    wide = (nh // nkv) % 16 == 0
    assert _decode_kernel_names(q, kp, vp, table, lens) == {
        "paged_attention_decode_grouped" if wide
        else "paged_attention_decode"}


@pytest.mark.parametrize("group", [16, 32])
def test_decode_kernel_bodies_agree_at_the_edge(monkeypatch, group):
    """The same wide group through the MXU body and, with the edge moved
    out of reach, through the VPU body: one answer (float32 sums in
    another order: 1e-5)."""
    args = _decode_case(2 * group, 2)
    mxu = pa.paged_attention_decode(*args, layer=1)
    monkeypatch.setattr(pa, "_GROUP_ON_MXU", 1 << 20)
    assert _decode_kernel_names(*args) == {"paged_attention_decode"}
    vpu = pa.paged_attention_decode(*args, layer=1)
    live = np.array([0, 2])
    assert np.abs(np.asarray(mxu - vpu))[live].max() < 1e-5


# ---- the benchmark's own copy of the reference -----------------------------

def test_benchmark_reference_is_the_programs_reference(weights):
    """``benchmark/models/sdar.py`` imports nothing of the program; this
    holds its forward pass to :mod:`paddle_tpu.models.sdar_reference`."""
    bench = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark")
    sys.path.insert(0, bench)
    try:
        from harness import core
        theirs = core.load_module(os.path.join(bench, "models", "sdar.py"))
    finally:
        sys.path.remove(bench)
    cfg = theirs.load_config(os.path.join(bench, "tests", "configs",
                                          "sdar-tiny.json"))
    mine = sdar.sdar_moe_tiny_config(**{
        k: cfg[k] for k in ("vocab_size", "hidden_size", "num_hidden_layers",
                            "num_attention_heads", "num_key_value_heads",
                            "head_dim", "moe_intermediate_size",
                            "num_experts", "num_experts_per_tok")},
        mask_token_id=cfg["generation"]["mask_token_id"])
    w = theirs.init_weights(cfg, 21)
    ids = np.random.default_rng(5).integers(0, mine.vocab_size, 20)
    want = np.asarray(ref.forward(mine, w, ids))
    pos = np.arange(20)
    got = np.asarray(theirs.reference_logits(
        cfg, w, ids, pos, ref.block_mask(pos, mine.block_length)))
    assert np.abs(got - want).max() < 1e-5
