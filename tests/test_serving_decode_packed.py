"""A decode tick whose inputs cross to the device once.

``ServingEngine.decode`` fills one int32 array a tick (last token, length,
call counter, page-table row of every slot) and hands the program the
engine's base key, which stays on the device; ``decode_packed_fn`` unpacks
it and folds the key inside the program. Held here, on the CPU at a tiny
size: the packed program is ``decode_step_fn`` on the unpacked arguments
bit for bit, the sampled stream is the one eager ``fold_in(engine._key,
calls)`` keys gave, a decode call makes exactly one array from host memory
and runs nothing of ``jax.random`` eagerly, and a disaggregated engine's
array lands on its decode device.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.models.gpt import (GPTForPretraining, GPTModel,
                                   gpt_tiny_config)
from paddle_tpu.profiler import utils as profiler_utils
from paddle_tpu.serving import ServingEngine
from paddle_tpu.serving.engine import decode_packed_fn, decode_step_fn


@pytest.fixture(scope="module")
def model():
    paddle.seed(5)
    cfg = gpt_tiny_config()
    return GPTForPretraining(GPTModel(cfg)), cfg


def _engine(model, **kw):
    net, cfg = model
    base = dict(page_size=8, decode_buckets=(1, 2, 8), prefill_chunk=16,
                use_kernel=False, autofuse=False, aot=False)
    base.update(kw)
    return ServingEngine(net, cfg, **base)


def _prompts(cfg, lens, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab_size, (n,)).astype(np.int32)
            for n in lens]


def _static(eng):
    return dict(eps=eng.cfg.layer_norm_epsilon, temperature=eng.temperature,
                top_k=eng.top_k, use_kernel=eng.use_kernel,
                compute_dtype=str(np.dtype(eng.compute_dtype)))


def _parent_decode(eng, step, seq_ids, bucket):
    """``ServingEngine.decode`` as it was: five host arrays a tick, the
    key folded eagerly (the version that was, held equal)."""
    slots = list(seq_ids) + [None] * (bucket - len(seq_ids))
    lens = eng.pool.lens_array(slots)
    table = eng.pool.table_array(slots)
    tokens = np.asarray([eng._last_token.get(s, 0) for s in slots], np.int32)
    positions = np.maximum(lens - 1, 0).astype(np.int32)
    kp, vp, nxt = step(eng.params, eng.pool.k_pages, eng.pool.v_pages,
                       jnp.asarray(tokens), jnp.asarray(positions),
                       jnp.asarray(table), jnp.asarray(lens),
                       eng._next_key())
    eng.pool.bind(kp, vp)
    out = [int(t) for t in np.asarray(nxt)[:len(seq_ids)]]
    for sid, t in zip(seq_ids, out):
        eng._last_token[sid] = t
    return out


# (i) the packed program is the step function on the unpacked arguments
@pytest.mark.parametrize("temperature", [0.0, 0.8])
@pytest.mark.parametrize("bucket,n_live", [(1, 1), (2, 1), (8, 5)])
def test_packed_program_equals_step_fn_bit_for_bit(model, bucket, n_live,
                                                   temperature):
    eng = _engine(model, temperature=temperature, top_k=40, seed=3)
    ids = [f"s{i}" for i in range(n_live)]
    for sid, prompt in zip(ids, _prompts(eng.cfg, (5, 17, 9, 24, 12))):
        eng.prefill(sid, prompt)
        eng.pool.extend(sid)
    slots = ids + [None] * (bucket - n_live)
    lens = eng.pool.lens_array(slots)
    table = eng.pool.table_array(slots)
    tokens = np.asarray([eng._last_token.get(s, 0) for s in slots], np.int32)
    calls = 2 ** 31 + 11            # a counter past int32: its uint32 bits
    state = np.concatenate([
        tokens[:, None], lens[:, None],
        np.full((bucket, 1), np.uint32(calls).astype(np.int32)), table],
        axis=1)
    assert state.dtype == np.int32
    assert state.shape == (bucket, 3 + eng.pool.max_pages_per_seq)
    step = functools.partial(decode_step_fn, **_static(eng))
    pools = (eng.pool.k_pages, eng.pool.v_pages)
    got = jax.jit(functools.partial(decode_packed_fn, step))(
        eng.params, *pools, jnp.asarray(state), eng._key)
    want = jax.jit(step)(
        eng.params, *pools, jnp.asarray(tokens),
        jnp.asarray(np.maximum(lens - 1, 0)), jnp.asarray(table),
        jnp.asarray(lens), jax.random.fold_in(eng._key, calls))
    for new, old in zip(got, want):
        np.testing.assert_array_equal(np.asarray(new), np.asarray(old))


# (ii) sampled tokens for a fixed seed are the parent's stream
@pytest.mark.parametrize("kw", [
    dict(temperature=0.8, top_k=40), dict(temperature=0.0)],
    ids=["sampled", "greedy"])
def test_tokens_of_16_ticks_are_the_eager_keys_stream(model, kw):
    prompts = _prompts(model[1], (11, 20, 6), seed=2)
    ids = ["a", "b", "c"]

    def run(decode):
        eng = _engine(model, seed=7, **kw)
        first = [eng.prefill(sid, p) for sid, p in zip(ids, prompts)]
        ticks = []
        for _ in range(16):
            for sid in ids:
                eng.pool.extend(sid)
            ticks.append(decode(eng, ids))
        return first, ticks, eng._calls

    def parent(eng, seq_ids):
        step = jax.jit(functools.partial(decode_step_fn, **_static(eng)))
        return _parent_decode(eng, step, seq_ids, 8)

    got = run(lambda eng, seq_ids: eng.decode(seq_ids, bucket=8))
    want = run(parent)
    assert got == want
    flat = [t for tick in got[1] for t in tick]
    assert len(set(flat)) > 1           # not one token for all
    # a key a chunk (20 tokens are two chunks), a key a tick
    assert got[2] == 4 + 16


# (iii) one array from host memory a call, nothing of jax.random run eagerly
def test_decode_sends_one_host_array_and_runs_no_eager_random(
        model, monkeypatch, tmp_path):
    eng = _engine(model, temperature=0.8, top_k=40, seed=1)
    ids = ["a", "b", "c"]
    for sid, prompt in zip(ids, _prompts(eng.cfg, (9, 14, 5))):
        eng.prefill(sid, prompt)
    for sid in ids:
        eng.pool.extend(sid)
    eng.decode(ids, bucket=8)           # compiled before the counting
    for sid in ids:
        eng.pool.extend(sid)

    sent, handed = [], []
    for name in ("asarray", "array"):
        inner = getattr(jnp, name)

        def counted(x, *a, _inner=inner, **k):
            if not isinstance(x, jax.Array):
                sent.append(np.shape(x))
            return _inner(x, *a, **k)
        monkeypatch.setattr(jnp, name, counted)
    put = jax.device_put

    def counted_put(x, *a, **k):
        sent.extend(np.shape(leaf) for leaf in jax.tree_util.tree_leaves(x)
                    if not isinstance(leaf, jax.Array))
        return put(x, *a, **k)
    monkeypatch.setattr(jax, "device_put", counted_put)

    def no_eager(*a, **k):
        raise AssertionError("jax.random dispatched eagerly in decode()")
    for name in ("fold_in", "split", "key", "PRNGKey", "categorical"):
        monkeypatch.setattr(jax.random, name, no_eager)
    program = eng._decode_jit

    def recording(*args):
        handed.append(args)
        return program(*args)
    monkeypatch.setattr(eng, "_decode_jit", recording)

    calls = eng._calls
    profiler_utils._drain_events()
    with jax.profiler.trace(str(tmp_path)):
        out = eng.decode(ids, bucket=8)
    spans = profiler_utils._drain_events()
    assert len(out) == 3 and eng._calls == calls + 1
    assert sent == [(8, 3 + eng.pool.max_pages_per_seq)]
    (args,) = handed
    assert all(isinstance(leaf, jax.Array)
               for leaf in jax.tree_util.tree_leaves(args))
    params, kp, vp, state, key = args
    assert state.dtype == jnp.int32 and key is eng._decode_key
    assert int(np.asarray(state)[0, 2]) == eng._calls
    by_name = {s.name: s for s in spans}
    prep = by_name["engine.host_prep"]
    assert len(sent) == 1
    assert prep.parent_id == by_name["engine.decode"].span_id
    assert {"engine.dispatch", "engine.readback"} <= set(by_name)


# (iv) a disaggregated engine's packed array lands on its decode device
def test_disaggregated_state_and_key_land_on_the_decode_device(
        model, monkeypatch):
    devs = jax.devices()
    if len(devs) < 2:
        pytest.skip("one device: nothing to tell apart")
    eng = _engine(model, prefill_chunk=None, disaggregated=True,
                  temperature=0.8, top_k=40)
    assert eng._decode_device == devs[-1] != eng._prefill_device
    eng.prefill("a", _prompts(eng.cfg, (9,))[0])
    eng.pool.extend("a")
    handed = []
    program = eng._decode_jit

    def recording(*args):
        handed.append(args)
        return program(*args)
    monkeypatch.setattr(eng, "_decode_jit", recording)
    (tok,) = eng.decode(["a"])
    assert 0 <= tok < eng.cfg.vocab_size
    (_, kp, _, state, key), = handed
    want = {eng._decode_device}
    assert state.devices() == want and key.devices() == want
    assert kp.devices() == want
    assert state.shape == (1, 3 + eng.pool.max_pages_per_seq)


def test_decode_avals_are_the_packed_state_and_the_key(model):
    eng = _engine(model)
    for b in eng.decode_buckets:
        state, key = eng._decode_avals(b)
        assert (state.shape, state.dtype) == (
            (b, 3 + eng.pool.max_pages_per_seq), jnp.int32)
        assert (key.shape, key.dtype) == (eng._key.shape, eng._key.dtype)
