"""``remat="dots"`` keeps the flash kernel's output and row statistics.

The block's checkpoint policy saves every matmul's output and the two
residuals ``kernels/flash_attention.py`` names (``FLASH_OUT``,
``FLASH_LSE``), so the backward pass reads them and does not run the
forward kernel again. The train step counts the forward calls a layer in
its own jaxpr (``flash_fwd_per_layer``): under ``"dots"`` as many as
without any checkpoint, one fewer than under the matmul-only policy.
Gradients are those of the block without a checkpoint. Interpret mode,
a tiny stack with the kernel forced on (S 128, two heads of 64).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.distributed import mesh as mesh_mod
from paddle_tpu.distributed.mesh import HybridCommunicateGroup
from paddle_tpu.models import gpt
from paddle_tpu.profiler import utils

B, S = 4, 128
_MATMULS_ONLY = jax.checkpoint_policies.dots_with_no_batch_dims_saveable


@pytest.fixture(autouse=True)
def reset_mesh():
    saved = (mesh_mod._global_mesh, mesh_mod._hcg)
    yield
    mesh_mod._global_mesh, mesh_mod._hcg = saved


def _cfg():
    return gpt.gpt_tiny_config(num_layers=4, hidden_size=128, num_heads=2,
                               max_position_embeddings=S)


def _step(pp, schedule, remat, model=None):
    hcg = HybridCommunicateGroup(dp_degree=1, mp_degree=1, pp_degree=pp)
    kw = dict(n_micro=2, remat=remat, use_flash=True,
              pipeline_schedule=schedule)
    if model is None:
        return gpt.GPTHybridTrainStep.abstract(_cfg(), hcg, **kw)
    return gpt.GPTHybridTrainStep(model, _cfg(), hcg, lr=1e-3, **kw)


def _grads(step, ids, labels):
    if step.pipeline_schedule == "1f1b":
        return jax.jit(step._loss_and_grads_1f1b)(step.params, ids, labels)
    return jax.jit(jax.value_and_grad(step._loss_fn))(step.params, ids,
                                                      labels)


# (pp, schedule, forward calls a layer without a checkpoint): 1F1B
# re-derives a stage from its saved input in every backward tick
PATHS = [(1, "gpipe", 1), (2, "gpipe", 1), (2, "1f1b", 2)]


@pytest.mark.parametrize("pp,schedule,plain", PATHS)
@pytest.mark.parametrize("remat", [False, True, "dots"])
def test_forward_kernel_calls_a_layer(pp, schedule, plain, remat):
    per_layer = gpt.flash_fwd_per_layer(
        _step(pp, schedule, remat).step_jaxpr(B, S))
    assert per_layer == plain + (remat is True)


@pytest.mark.parametrize("pp,schedule,plain", PATHS)
def test_matmul_only_policy_runs_the_kernel_again(monkeypatch, pp, schedule,
                                                  plain):
    """The parent's policy: the backward runs the forward kernel again."""
    monkeypatch.setattr(gpt, "_DOTS_POLICY", _MATMULS_ONLY)
    assert gpt.flash_fwd_per_layer(
        _step(pp, schedule, "dots").step_jaxpr(B, S)) == plain + 1


@pytest.mark.parametrize("pp,schedule", [(1, "gpipe"), (2, "1f1b")])
def test_dots_gradients_are_the_plain_blocks(monkeypatch, pp, schedule):
    """Loss and gradients under ``"dots"`` equal the block's without a
    checkpoint (float32), and the first call records the count on the
    ``train.step`` span."""
    cfg = _cfg()
    rng = np.random.default_rng(37)
    ids = rng.integers(0, cfg.vocab_size, (B, S)).astype(np.int32)
    labels = np.roll(ids, -1, axis=1)
    paddle.seed(37)
    model = gpt.GPTForPretraining(gpt.GPTModel(cfg))
    got = {}
    for remat in (False, "dots"):
        step = _step(pp, schedule, remat, model)
        got[remat] = _grads(step, jnp.asarray(ids), jnp.asarray(labels))
    loss, grads = got["dots"]
    want_loss, want = got[False]
    np.testing.assert_allclose(loss, want_loss, rtol=1e-6)
    for g, w in zip(jax.tree.leaves(grads), jax.tree.leaves(want)):
        np.testing.assert_allclose(g, w, rtol=2e-5, atol=1e-6)

    monkeypatch.setattr(utils, "_host_events", type(utils._host_events)(
        maxlen=64))
    utils._set_collecting(True)
    try:
        step(ids, labels)
    finally:
        utils._set_collecting(False)
    span, = [s for s in utils._drain_events() if s.name == "train.step"]
    assert span.attrs["flash_fwd_per_layer"] == \
        step.flash_fwd_per_layer == (1 if schedule == "gpipe" else 2)
