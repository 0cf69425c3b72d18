"""ERNIE-MoE model family: init parity, train step, static capture."""
import math

import numpy as np

import paddle_tpu as paddle
from paddle_tpu import amp, optimizer, static
from paddle_tpu.models import (ErnieMoeForPretraining, ErnieMoeModel,
                               ernie_moe_tiny_config)


def _data(cfg, B=2, S=64, seed=0):
    rng = np.random.default_rng(seed)
    ids = rng.integers(0, cfg.vocab_size, (B, S)).astype(np.int64)
    return ids


def test_init_loss_near_ln_vocab():
    cfg = ernie_moe_tiny_config()
    m = ErnieMoeForPretraining(ErnieMoeModel(cfg))
    ids = paddle.to_tensor(_data(cfg))
    logits = m(ids)
    assert tuple(logits.shape) == (2, 64, cfg.vocab_size)
    ce = paddle.nn.CrossEntropyLoss()
    loss = float(ce(paddle.reshape(logits, [-1, cfg.vocab_size]),
                    paddle.reshape(ids, [-1])).numpy())
    assert abs(loss - math.log(cfg.vocab_size)) < 0.5, loss


def test_eager_train_reaches_moe_experts():
    cfg = ernie_moe_tiny_config()
    m = ErnieMoeForPretraining(ErnieMoeModel(cfg))
    ids = paddle.to_tensor(_data(cfg))
    ce = paddle.nn.CrossEntropyLoss()
    opt = optimizer.AdamW(learning_rate=1e-3, parameters=m.parameters())
    losses = []
    for _ in range(4):
        loss = ce(paddle.reshape(m(ids), [-1, cfg.vocab_size]),
                  paddle.reshape(ids, [-1]))
        loss.backward()
        opt.step()
        opt.clear_grad()
        losses.append(float(loss.numpy()))
    assert losses[-1] < losses[0]
    # the MoE experts actually train (gradients reached them)
    moe_block = m.ernie.layers[1].moe
    g0 = np.asarray(moe_block.experts[0].htoh4.weight._value)
    m2 = ErnieMoeForPretraining(ErnieMoeModel(cfg))
    assert not np.allclose(
        g0, np.asarray(m2.ernie.layers[1].moe.experts[0].htoh4.weight._value))


def test_static_capture_trains_param_only_ops():
    """Ops whose only tensor inputs are concrete Parameters (stacked MoE
    expert weights, position-embedding lookups of a constant arange) must
    record into the program, not fold to constants — else those weights
    silently never train under the static Executor."""
    cfg = ernie_moe_tiny_config()
    ids_np = _data(cfg)
    static.enable_static()
    try:
        main = static.Program()
        with static.program_guard(main):
            ids = static.data("ids", [2, 64], "int64")
            labels = static.data("labels", [2, 64], "int64")
            model = ErnieMoeForPretraining(ErnieMoeModel(cfg))
            logits = model(ids)
            loss = paddle.nn.functional.cross_entropy(
                paddle.reshape(logits, [-1, cfg.vocab_size]),
                paddle.reshape(labels, [-1]))
            opt = optimizer.AdamW(learning_rate=1e-2,
                                  parameters=model.parameters())
            opt.minimize(loss)
        exe = static.Executor()
        expert_w = model.ernie.layers[1].moe.experts[0].htoh4.weight
        pos_w = model.ernie.embeddings.position_embeddings.weight
        before = (np.asarray(expert_w._value).copy(),
                  np.asarray(pos_w._value).copy())
        for _ in range(3):
            exe.run(main, feed={"ids": ids_np, "labels": ids_np},
                    fetch_list=[loss])
        assert not np.allclose(before[0], np.asarray(expert_w._value)), \
            "MoE expert weights did not train under static capture"
        assert not np.allclose(before[1], np.asarray(pos_w._value)), \
            "position embeddings did not train under static capture"
    finally:
        static.disable_static()


def test_static_amp_capture_trains():
    cfg = ernie_moe_tiny_config()
    ids_np = _data(cfg)
    static.enable_static()
    try:
        main = static.Program()
        with static.program_guard(main):
            ids = static.data("ids", [2, 64], "int64")
            labels = static.data("labels", [2, 64], "int64")
            with amp.auto_cast(enable=True, dtype="bfloat16"):
                model = ErnieMoeForPretraining(ErnieMoeModel(cfg))
                logits = model(ids)
                loss = paddle.nn.functional.cross_entropy(
                    paddle.reshape(logits, [-1, cfg.vocab_size]),
                    paddle.reshape(labels, [-1]))
            opt = optimizer.AdamW(learning_rate=1e-3,
                                  parameters=model.parameters())
            opt.minimize(loss)
        exe = static.Executor()
        feed = {"ids": ids_np, "labels": ids_np}
        ls = [float(exe.run(main, feed=feed, fetch_list=[loss])[0])
              for _ in range(4)]
        assert ls[-1] < ls[0], ls
    finally:
        static.disable_static()


def test_ernie_fused_mlm_loss_matches_unfused():
    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu.models import (ErnieMoeForPretraining, ErnieMoeModel,
                                   ernie_moe_tiny_config)

    cfg = ernie_moe_tiny_config()
    model = ErnieMoeForPretraining(ErnieMoeModel(cfg))
    model.eval()
    rng = np.random.default_rng(0)
    ids = paddle.to_tensor(
        rng.integers(0, cfg.vocab_size, (2, 16)).astype(np.int64))
    labels_np = rng.integers(0, cfg.vocab_size, (2, 16)).astype(np.int64)
    labels_np[0, :2] = -100
    labels = paddle.to_tensor(labels_np)
    logits = model(ids)
    want = paddle.nn.functional.cross_entropy(
        paddle.reshape(logits, [-1, cfg.vocab_size]),
        paddle.to_tensor(labels_np.reshape(-1)),
        ignore_index=-100)
    got = model.forward_with_mlm_loss(ids, labels)
    np.testing.assert_allclose(float(got.numpy()), float(want.numpy()),
                               rtol=2e-4)


def test_mlm_loss_includes_gate_aux_loss_in_training():
    """GShard §2.2: the pretraining loss must include the gates'
    load-balance aux term (weight 0.01) in training mode — the analysis
    deadcode pass flagged it as computed-and-dropped before this."""
    cfg = ernie_moe_tiny_config()
    model = ErnieMoeForPretraining(ErnieMoeModel(cfg))
    ids = paddle.to_tensor(_data(cfg, S=16))
    model.train()
    # same seed → identical gshard random-routing draws, so the delta is
    # EXACTLY the weighted aux term
    paddle.seed(7)
    l_noaux = float(model.forward_with_mlm_loss(
        ids, ids, aux_loss_weight=0.0).numpy())
    paddle.seed(7)
    l_aux = float(model.forward_with_mlm_loss(ids, ids).numpy())
    assert l_aux > l_noaux, (l_aux, l_noaux)
    # aux = E * sum(me * ce) >= 1 by Cauchy-Schwarz, so the 0.01-weighted
    # delta is at least ~0.01
    assert l_aux - l_noaux > 0.005, (l_aux, l_noaux)


def test_gate_aux_loss_cleared_in_eval():
    """Eval forwards must CLEAR the stashed gate loss (not leave a stale
    training-mode value — possibly a leaked tracer — readable by
    gate_aux_loss/get_loss)."""
    cfg = ernie_moe_tiny_config()
    model = ErnieMoeModel(cfg)
    ids = paddle.to_tensor(_data(cfg, S=16))
    model.train()
    model(ids)  # stashes a loss nobody consumes
    gates = [blk.moe.gate for blk in model.layers
             if hasattr(blk, "moe")]
    assert gates and all(g.has_loss for g in gates)
    model.eval()
    model(ids)
    assert all(not g.has_loss for g in gates)


def test_generator_is_greedy_causal_and_restores_the_mode():
    """``ErnieMoeGenerator`` (the eager oracle; the engine it was the
    oracle of is gone): a longer generation begins with the shorter one,
    and a model in training mode is in training mode again afterwards."""
    from paddle_tpu.models import ErnieMoeGenerator
    paddle.seed(0)
    cfg = ernie_moe_tiny_config(
        num_hidden_layers=2, hidden_size=32, num_attention_heads=2,
        intermediate_size=64, num_experts=4, capacity_factor=100.0,
        max_position_embeddings=64)     # no-drop: the parity caveat
    m = ErnieMoeForPretraining(ErnieMoeModel(cfg))
    m.train()
    gen = ErnieMoeGenerator(m)
    ids = _data(cfg, B=2, S=9, seed=3)
    three = gen(ids, max_new_tokens=3)
    assert m.training
    assert three.shape == (2, 3) and three.dtype == np.int64
    assert (three >= 0).all() and (three < cfg.vocab_size).all()
    m.eval()
    np.testing.assert_array_equal(gen(ids, max_new_tokens=2), three[:, :2])
    assert not m.training
