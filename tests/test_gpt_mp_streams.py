"""Two half-batch streams in the train step's layer scan.

Where the ``"mp"`` axis holds more than one device and the local batch is
even, ``gpt.scan_blocks`` carries the batch as two halves that share no
value, so that one half's row-parallel sums can be in flight while the
other half computes; over two devices a sum is ``x + ppermute(x)``. The
step's loss and updated parameters are the one-stream step's (the plain
scan of the ``psum`` block), on GPipe and 1F1B, with and without a
checkpoint; with one device on the axis, or an odd local batch, the step
is the one-stream program itself. Float32 on the CPU mesh, no kernel.
"""
import jax
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.distributed import mesh as mesh_mod
from paddle_tpu.distributed.mesh import HybridCommunicateGroup
from paddle_tpu.models import gpt
from paddle_tpu.profiler import utils

S = 16


@pytest.fixture(autouse=True)
def reset_mesh():
    saved = (mesh_mod._global_mesh, mesh_mod._hcg)
    yield
    mesh_mod._global_mesh, mesh_mod._hcg = saved


def _one_stream(blocks, x, eps, use_flash, remat):
    """The layer scan before the split: one stream, sums by ``psum``."""
    blk = gpt.train_layer(eps, use_flash, remat)
    return jax.lax.scan(lambda h, p: (blk(p, h), None), x, blocks)[0]


def _cfg():
    return gpt.gpt_tiny_config(num_layers=4, hidden_size=64, num_heads=4,
                               max_position_embeddings=S)


def _hcg(dp, mp, pp):
    return HybridCommunicateGroup(dp_degree=dp, mp_degree=mp, pp_degree=pp)


def _batch(cfg, b):
    rng = np.random.default_rng(41)
    ids = rng.integers(0, cfg.vocab_size, (b, S)).astype(np.int32)
    return ids, np.roll(ids, -1, axis=1)


def _grads(step, ids, labels):
    ids, labels = jax.numpy.asarray(ids), jax.numpy.asarray(labels)
    if step.pipeline_schedule == "1f1b":
        return jax.jit(step._loss_and_grads_1f1b)(step.params, ids, labels)
    return jax.jit(jax.value_and_grad(step._loss_fn))(step.params, ids,
                                                      labels)


def _train(monkeypatch, model, mesh, kw, ids, labels, split, spans=False):
    """Loss and gradients at the start, the two steps' losses and
    ``blocks`` after them, and with ``spans`` the ``train.step`` spans of
    the two steps; ``split=False`` runs the one-stream scan."""
    with monkeypatch.context() as m:
        if not split:
            m.setattr(gpt, "scan_blocks", _one_stream)
        step = gpt.GPTHybridTrainStep(model, _cfg(), _hcg(*mesh), lr=1e-3,
                                      **kw)
        first = _grads(step, ids, labels)
        if spans:
            m.setattr(utils, "_host_events",
                      type(utils._host_events)(maxlen=64))
            utils._set_collecting(True)
        try:
            losses = [float(step(ids, labels).numpy()) for _ in range(2)]
        finally:
            utils._set_collecting(False)
        recorded = [s for s in utils._drain_events()
                    if s.name == "train.step"]
    blocks = jax.tree.map(np.asarray, step.params["blocks"])
    return step, first, losses, blocks, recorded


# (dp, mp, pp), schedule, n_micro, remat, batch, streams: the local batch
# is batch / dp / n_micro, split in two where it is even and mp > 1
CASES = [
    ((2, 2, 1), "gpipe", 1, "dots", 8, 2),
    ((2, 2, 1), "gpipe", 1, False, 8, 2),
    ((1, 2, 2), "gpipe", 2, "dots", 8, 2),
    ((2, 2, 2), "gpipe", 2, True, 8, 2),
    ((1, 2, 2), "1f1b", 2, "dots", 8, 2),
    ((2, 2, 2), "1f1b", 2, False, 8, 2),
    ((2, 2, 1), "gpipe", 1, "dots", 6, 1),      # local batch 3: one stream
    ((1, 4, 1), "gpipe", 1, "dots", 4, 2),      # four on the axis: psum
]


@pytest.mark.parametrize("mesh,schedule,n_micro,remat,batch,streams", CASES)
def test_two_streams_train_as_one(monkeypatch, mesh, schedule, n_micro,
                                  remat, batch, streams):
    cfg = _cfg()
    ids, labels = _batch(cfg, batch)
    paddle.seed(41)
    model = gpt.GPTForPretraining(gpt.GPTModel(cfg))
    kw = dict(n_micro=n_micro, remat=remat, pipeline_schedule=schedule)
    _, (want_loss, want_grads), want_losses, want, _ = _train(
        monkeypatch, model, mesh, kw, ids, labels, split=False)
    step, (loss, grads), losses, got, _ = _train(
        monkeypatch, model, mesh, kw, ids, labels, split=True)
    assert step.mp_streams == streams
    np.testing.assert_allclose(loss, want_loss, rtol=1e-6)
    for g, w in zip(jax.tree.leaves(grads), jax.tree.leaves(want_grads)):
        np.testing.assert_allclose(g, w, rtol=1e-5, atol=1e-7)
    np.testing.assert_allclose(losses, want_losses, rtol=1e-5)
    # AdamW's m / (sqrt(v) + eps) gives a gradient that rounding dominates
    # a whole step either way (the key part of bqkv has a true gradient of
    # zero: the softmax does not see a bias of the keys); the gradients
    # above are the check of the arithmetic, atol is 1/50 of a step (lr)
    for k in want:
        np.testing.assert_allclose(got[k], want[k], rtol=1e-5, atol=2e-5,
                                   err_msg=k)


def test_train_step_span_reads_two_streams(monkeypatch):
    """dp2·mp2, the benchmark's four-chip layout: ``mp_streams`` 2 on
    every ``train.step`` span."""
    cfg = _cfg()
    ids, labels = _batch(cfg, 8)
    paddle.seed(41)
    model = gpt.GPTForPretraining(gpt.GPTModel(cfg))
    *_, spans = _train(monkeypatch, model, (2, 2, 1),
                       dict(n_micro=1, remat="dots"), ids, labels,
                       split=True, spans=True)
    assert [s.attrs["mp_streams"] for s in spans] == [2, 2]


@pytest.mark.parametrize("dp,remat", [(1, "dots"), (2, "dots"), (2, False)])
def test_one_device_on_mp_is_the_one_stream_program(monkeypatch, dp, remat):
    """mp1, as in the one-chip training cell: the step's jaxpr is the one
    the one-stream scan gives, and it reads one stream."""
    step = gpt.GPTHybridTrainStep.abstract(_cfg(), _hcg(dp, 1, 1),
                                           n_micro=1, remat=remat)
    got = step.step_jaxpr(4, S)
    assert gpt.mp_streams(got) == 1
    with monkeypatch.context() as m:
        m.setattr(gpt, "scan_blocks", _one_stream)
        want = gpt.GPTHybridTrainStep.abstract(
            _cfg(), _hcg(dp, 1, 1), n_micro=1, remat=remat).step_jaxpr(4, S)
    assert str(got) == str(want)


def test_exchange_sum_is_psum():
    """Over an axis of two devices the exchange is the ``psum``, bitwise."""
    mesh = jax.make_mesh((2,), ("mp",), devices=jax.devices()[:2])
    x = np.random.default_rng(41).standard_normal((2, 3, 8)).astype(
        np.float32)

    def both(v):
        return gpt.exchange_sum(v, "mp"), jax.lax.psum(v, "mp")

    got, want = jax.jit(jax.shard_map(
        both, mesh=mesh, in_specs=jax.P("mp"),
        out_specs=(jax.P("mp"), jax.P("mp"))))(x)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
