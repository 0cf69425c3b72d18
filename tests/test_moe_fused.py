"""Fused Pallas MoE dispatch/combine kernels (kernels/moe_dispatch.py).

Tier-1 parity contract: the fused kernels == the gather-based reference
in CPU interpret mode — ragged token counts, capacity-overflow drops,
top-k 1 and 2, uneven expert load — plus gradients (reference-recompute
VJP), MoELayer(fused_dispatch=True) equivalence, trajectory equivalence
over a short train run, the PTCS004 fusion-opportunity diagnostic
(fires on the unfused chain, clean on the fused kernels), the fused
pallas_call cost-model pricing, and the moe_utils count diagnostics.
"""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import ops
from paddle_tpu.distributed import mesh as mesh_mod
from paddle_tpu.incubate.distributed.models.moe import (ExpertLayer,
                                                        MoELayer)
from paddle_tpu.incubate.distributed.models.moe.gate import GShardGate
from paddle_tpu.kernels.moe_dispatch import (fused_moe_combine,
                                             fused_moe_dispatch,
                                             reference_moe_combine,
                                             reference_moe_dispatch)


@pytest.fixture(autouse=True)
def reset_mesh():
    saved = (mesh_mod._global_mesh, mesh_mod._hcg)
    yield
    mesh_mod._global_mesh, mesh_mod._hcg = saved


def _np(t):
    return np.asarray(t._value if hasattr(t, "_value") else t)


def _rand(rng, *shape):
    import jax.numpy as jnp
    return jnp.asarray(rng.standard_normal(shape).astype(np.float32))


# ---------------------------------------------------------------------------
# kernel == reference (interpret mode)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("S,E,C,K,kind", [
    (16, 4, 5, 2, "gshard"),     # plain top-2
    (13, 4, 2, 2, "renorm"),     # ragged token count + tight capacity
    (7, 3, 1, 1, "switch"),      # top-1, capacity-1 overflow drops
    (32, 8, 3, 2, "naive"),      # raw-logit combine weights
    (5, 4, 20, 2, "gshard"),     # capacity >> tokens (no drops)
    (130, 4, 40, 2, "gshard"),   # crosses the 128-token block boundary
])
def test_fused_dispatch_matches_reference(S, E, C, K, kind):
    rng = np.random.default_rng(S * 31 + E)
    M = 8
    x = _rand(rng, S, M)
    gw = _rand(rng, M, E)
    gb = _rand(rng, E) * 0.1
    ref = reference_moe_dispatch(x, gw, gb, num_expert=E, capacity=C,
                                 top_k=K, gate_kind=kind)
    got = fused_moe_dispatch(x, gw, gb, num_expert=E, capacity=C,
                             top_k=K, gate_kind=kind)
    for name, a, b in zip(("expert_in", "comb_idx", "val", "me", "ce"),
                          got, ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-5, err_msg=name)


def test_fused_dispatch_uneven_expert_load():
    """A heavily skewed gate (one hot expert) must produce identical
    drop/slot behavior — the priority-major counter walk is where a
    fused implementation would most plausibly diverge."""
    import jax.numpy as jnp
    rng = np.random.default_rng(7)
    S, M, E, C, K = 24, 8, 4, 3, 2
    x = _rand(rng, S, M)
    gw = _rand(rng, M, E) * 0.01
    gb = jnp.asarray([4.0, 0.0, -1.0, -1.0], jnp.float32)  # expert 0 hot
    ref = reference_moe_dispatch(x, gw, gb, num_expert=E, capacity=C,
                                 top_k=K, gate_kind="gshard")
    got = fused_moe_dispatch(x, gw, gb, num_expert=E, capacity=C,
                             top_k=K, gate_kind="gshard")
    # expert 0 overflows: exactly C of its >= C assignments survive
    drops = int((np.asarray(ref[1]) == E * C).sum())
    assert drops > 0, "fixture must actually overflow capacity"
    for a, b in zip(got, ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-5)


def test_fused_combine_matches_reference_with_drops():
    import jax.numpy as jnp
    rng = np.random.default_rng(3)
    S, M, E, C, K = 12, 8, 4, 2, 2
    eo = _rand(rng, E * C, M)
    val = jnp.abs(_rand(rng, S, K))
    comb = rng.integers(0, E * C + 1, (S, K)).astype(np.int32)  # incl. drop
    comb = jnp.asarray(comb)
    want = reference_moe_combine(eo, val, comb)
    got = fused_moe_combine(eo, val, comb)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_fused_gradients_match_reference():
    import jax
    import jax.numpy as jnp
    rng = np.random.default_rng(11)
    S, M, E, C, K = 12, 8, 4, 3, 2
    x = _rand(rng, S, M)
    gw = _rand(rng, M, E)
    gb = jnp.zeros((E,), jnp.float32)

    def loss(dispatch, combine, x, gw, gb):
        ei, comb, val, me, ce = dispatch(x, gw, gb, num_expert=E,
                                         capacity=C, top_k=K,
                                         gate_kind="gshard")
        eo = jnp.tanh(ei.reshape(E * C, M))
        y = combine(eo, val, comb)
        return jnp.sum(y * y) + jnp.sum(me * ce) * E

    gf = jax.grad(lambda *a: loss(fused_moe_dispatch, fused_moe_combine,
                                  *a), argnums=(0, 1, 2))(x, gw, gb)
    gr = jax.grad(lambda *a: loss(reference_moe_dispatch,
                                  reference_moe_combine, *a),
                  argnums=(0, 1, 2))(x, gw, gb)
    for a, b, n in zip(gf, gr, ("x", "gate_w", "gate_b")):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-6, err_msg=n)


# ---------------------------------------------------------------------------
# MoELayer(fused_dispatch=True) + ep_moe_ffn(fused_dispatch=True)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("gate,train", [
    ({"type": "gshard", "top_k": 2}, False),
    ({"type": "naive", "top_k": 2}, True),
    ({"type": "switch", "top_k": 1}, False),
])
def test_moe_layer_fused_matches_reference(gate, train):
    paddle.seed(0)
    E, M, S = 4, 8, 16
    experts = [ExpertLayer(M, 16) for _ in range(E)]
    ref = MoELayer(M, experts, gate=dict(gate), capacity_factor=1.0)
    fz = MoELayer(M, experts, gate=dict(gate), capacity_factor=1.0,
                  fused_dispatch=True)
    fz.gate.gate.weight.set_value(_np(ref.gate.gate.weight))
    fz.gate.gate.bias.set_value(_np(ref.gate.gate.bias))
    (ref.train(), fz.train()) if train else (ref.eval(), fz.eval())
    rng = np.random.default_rng(3)
    x = paddle.to_tensor(rng.standard_normal((S, M)).astype(np.float32))
    np.testing.assert_allclose(_np(fz(x)), _np(ref(x)),
                               rtol=1e-5, atol=1e-5)


def test_moe_layer_fused_falls_back_on_random_gate():
    """GShard random routing draws framework RNG the kernel cannot
    replicate — the fused layer must take the reference path in
    training mode (and the fused path in eval)."""
    paddle.seed(1)
    E, M = 4, 8
    moe = MoELayer(M, [ExpertLayer(M, 16) for _ in range(E)],
                   gate={"type": "gshard", "top_k": 2},
                   fused_dispatch=True)
    moe.train()
    assert moe._fused_gate_kind() is None
    moe.eval()
    assert moe._fused_gate_kind() == "gshard"


def test_moe_layer_fused_aux_loss_matches():
    """Training with fused dispatch keeps the GShard load-balance loss —
    rebuilt from the kernel's me/ce outputs, same value as the gate's."""
    paddle.seed(2)
    E, M, S = 4, 8, 16
    experts = [ExpertLayer(M, 16) for _ in range(E)]
    g1 = GShardGate(M, E, 1, topk=2, random_routing=False)
    g2 = GShardGate(M, E, 1, topk=2, random_routing=False)
    g2.gate.weight.set_value(_np(g1.gate.weight))
    g2.gate.bias.set_value(_np(g1.gate.bias))
    ref = MoELayer(M, experts, gate=g1, capacity_factor=2.0)
    fz = MoELayer(M, experts, gate=g2, capacity_factor=2.0,
                  fused_dispatch=True)
    ref.train()
    fz.train()
    rng = np.random.default_rng(5)
    x = paddle.to_tensor(rng.standard_normal((S, M)).astype(np.float32))
    np.testing.assert_allclose(_np(fz(x)), _np(ref(x)), rtol=1e-5,
                               atol=1e-5)
    a1 = float(_np(ref.gate.get_loss()))
    a2 = float(_np(fz.gate.get_loss()))
    np.testing.assert_allclose(a2, a1, rtol=1e-5)


def test_moe_trajectory_equivalence_fused_vs_unfused():
    """Short train run: fused and unfused layers from identical init
    follow the same loss trajectory (the custom-VJP backward is the
    reference's, so steps match to float tolerance)."""
    from paddle_tpu import optimizer

    def build(fused):
        paddle.seed(42)
        E, M = 4, 8
        gate = GShardGate(M, E, 1, topk=2, random_routing=False)
        return MoELayer(M, [ExpertLayer(M, 16) for _ in range(E)],
                        gate=gate, capacity_factor=1.5,
                        fused_dispatch=fused)

    def run(layer):
        layer.train()
        opt = optimizer.AdamW(learning_rate=1e-2,
                              parameters=layer.parameters())
        rng = np.random.default_rng(9)
        losses = []
        for _ in range(4):
            x = paddle.to_tensor(
                rng.standard_normal((16, 8)).astype(np.float32))
            out = layer(x)
            loss = ops.mean(out * out) + 0.01 * layer.gate.get_loss()
            loss.backward()
            opt.step()
            opt.clear_grad()
            losses.append(float(_np(loss)))
        return losses

    l_ref = run(build(False))
    l_fused = run(build(True))
    np.testing.assert_allclose(l_fused, l_ref, rtol=1e-4)


def test_ep_moe_ffn_fused_matches_unfused():
    import jax.numpy as jnp
    from paddle_tpu.incubate.distributed.models.moe import ep_moe_ffn
    rng = np.random.default_rng(17)
    E, S, M, H = 4, 24, 8, 16
    a = dict(ep_axis=None, num_expert=E, capacity=8, top_k=2)
    args = (_rand(rng, S, M), _rand(rng, M, E) * 0.5,
            _rand(rng, E) * 0.1, _rand(rng, E, M, H) * 0.2,
            _rand(rng, E, H) * 0.1, _rand(rng, E, H, M) * 0.2,
            _rand(rng, E, M) * 0.1)
    y_ref = ep_moe_ffn(*args, **a)
    y_fused = ep_moe_ffn(*args, fused_dispatch=True, **a)
    np.testing.assert_allclose(np.asarray(y_fused), np.asarray(y_ref),
                               rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# cost model: fused pricing + PTCS004 + the all_to_all_q what-if
# ---------------------------------------------------------------------------

def _stage_jaxprs(S=4096, M=512, E=16, K=2):
    import jax
    import jax.numpy as jnp
    C = int(1.2 * K * S / E)
    sds = jax.ShapeDtypeStruct
    f32 = jnp.float32
    avals = (sds((S, M), f32), sds((M, E), f32), sds((E,), f32),
             sds((E * C, M), f32))

    def stage(dispatch, combine):
        def run(x, gw, gb, eo):
            ei, comb, val, _, _ = dispatch(x, gw, gb, num_expert=E,
                                           capacity=C, top_k=K,
                                           gate_kind="renorm")
            return ei, combine(eo, val, comb)
        return jax.make_jaxpr(run)(*avals)

    return (stage(reference_moe_dispatch, reference_moe_combine),
            stage(fused_moe_dispatch, fused_moe_combine))


def test_ptcs004_fires_on_unfused_clean_on_fused():
    from paddle_tpu.analysis.passes.cost import _moe_fusion_opportunities
    ju, jf = _stage_jaxprs()
    fires = _moe_fusion_opportunities(ju.jaxpr)
    assert fires and fires[0]["ratio"] > 2.0, fires
    assert _moe_fusion_opportunities(jf.jaxpr) == []


def test_pallas_call_priced_as_fused_anchor():
    """The cost model charges a pallas_call body FLOPs × grid but HBM
    only for the call's operands/results — so the fused dispatch prices
    strictly less HBM (and less step time on a v5e) than the identical
    unfused chain."""
    from paddle_tpu.analysis.passes.cost import estimate_jaxpr_cost
    from paddle_tpu.observability.instrument import chip_specs
    chip = chip_specs("v5e")
    ju, jf = _stage_jaxprs()
    cu = estimate_jaxpr_cost(ju, chip=chip)
    cf = estimate_jaxpr_cost(jf, chip=chip)
    assert "pallas_call" in cf.by_prim and "pallas_call" not in cu.by_prim
    assert cf.hbm_bytes < cu.hbm_bytes
    assert cf.step_ms < cu.step_ms, (cf.step_ms, cu.step_ms)


def test_ptcs004_diagnostic_through_analyzer():
    """End to end through the registered pass: analyzing the unfused
    dispatch stage emits exactly one PTCS004 info; the fused stage none."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.analysis import ProgramAnalyzer
    S, M, E, K = 4096, 512, 16, 2
    C = int(1.2 * K * S / E)
    sds = jax.ShapeDtypeStruct
    f32 = jnp.float32

    from paddle_tpu.ops._dispatch import unwrap

    def unfused(x, gw, gb, eo):
        x, gw, gb, eo = (unwrap(t) for t in (x, gw, gb, eo))
        ei, comb, val, _, _ = reference_moe_dispatch(
            x, gw, gb, num_expert=E, capacity=C, top_k=K,
            gate_kind="renorm")
        return ei, reference_moe_combine(eo, val, comb)

    def fused(x, gw, gb, eo):
        x, gw, gb, eo = (unwrap(t) for t in (x, gw, gb, eo))
        ei, comb, val, _, _ = fused_moe_dispatch(
            x, gw, gb, num_expert=E, capacity=C, top_k=K,
            gate_kind="renorm")
        return ei, fused_moe_combine(eo, val, comb)

    avals = (sds((S, M), f32), sds((M, E), f32), sds((E,), f32),
             sds((E * C, M), f32))
    rep_u = ProgramAnalyzer().analyze(unfused, *avals,
                                      name="moe.unfused", emit=False)
    rep_f = ProgramAnalyzer().analyze(fused, *avals, name="moe.fused",
                                      emit=False)
    codes_u = [d.code for d in rep_u.diagnostics]
    codes_f = [d.code for d in rep_f.diagnostics]
    assert codes_u.count("PTCS004") == 1, codes_u
    assert "PTCS004" not in codes_f, codes_f


def test_expert_all_to_all_priced_with_int8_whatif():
    """The expert all_to_all inside the shard-mapped ep_moe_ffn carries
    the int8 wire what-if (PR 9's ``all_to_all_q`` pricing): the cost
    summary's compressed bytes are ~4x below the f32 wire, and a
    ``wire_dtype='int8'`` run of the SAME program prices at the what-if
    — the auto-enable loop's decision inputs."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu._jax_compat import shard_map
    from jax.sharding import PartitionSpec as P
    from paddle_tpu.analysis.passes.cost import estimate_jaxpr_cost
    from paddle_tpu.distributed.mesh import HybridCommunicateGroup
    from paddle_tpu.incubate.distributed.models.moe import ep_moe_ffn

    mesh_mod._global_mesh, mesh_mod._hcg = None, None
    hcg = HybridCommunicateGroup(dp_degree=1, sharding_degree=8)
    mesh = hcg.mesh
    ep = 8
    # M sized so quantized rows land exactly on the 256-element chunk
    # grid — the what-if formula does not model sub-chunk padding
    E, S, M, H = 8, 64, 64, 32
    S_local = S // ep
    sds = jax.ShapeDtypeStruct
    f32 = jnp.float32

    def run(wire):
        def prog(xl, gw, gb, w1l, b1l, w2l, b2l):
            return ep_moe_ffn(xl, gw, gb, w1l, b1l, w2l, b2l,
                              ep_axis="sharding", num_expert=E,
                              capacity=S_local, top_k=2,
                              wire_dtype=wire)
        f = shard_map(
            prog, mesh=mesh,
            in_specs=(P("sharding"), P(), P(), P("sharding"),
                      P("sharding"), P("sharding"), P("sharding")),
            out_specs=P("sharding"), check_vma=False)
        j = jax.make_jaxpr(f)(
            sds((S, M), f32), sds((M, E), f32), sds((E,), f32),
            sds((E, M, H), f32), sds((E, H), f32), sds((E, H, M), f32),
            sds((E, M), f32))
        sizes = {k: int(v) for k, v in dict(mesh.shape).items()}
        return estimate_jaxpr_cost(j, axis_sizes=sizes)

    fp = run(None)
    assert fp.comm_bytes > 0
    assert fp.comm_bytes_int8 < fp.comm_bytes / 3.0, \
        (fp.comm_bytes, fp.comm_bytes_int8)
    i8 = run("int8")
    # the compressed program's ACTUAL wire (int8 shards + f32 scales)
    # lands within ~10% of the uncompressed program's int8 what-if
    assert i8.comm_bytes < fp.comm_bytes / 3.0
    np.testing.assert_allclose(i8.comm_bytes, fp.comm_bytes_int8,
                               rtol=0.15)


# ---------------------------------------------------------------------------
# moe_utils: count diagnostics name the offending expert
# ---------------------------------------------------------------------------

def test_global_scatter_count_mismatch_names_expert():
    from paddle_tpu.distributed.utils import global_gather, global_scatter
    x = paddle.to_tensor(
        np.random.default_rng(0).standard_normal((6, 4)).astype(np.float32))
    lc = paddle.to_tensor(np.array([2, 4], np.int64))
    gc = paddle.to_tensor(np.array([3, 3], np.int64))
    for fn in (global_scatter, global_gather):
        with pytest.raises(ValueError) as ei:
            fn(x, lc, gc)
        msg = str(ei.value)
        assert "expert bin 0" in msg, msg
        assert "2" in msg and "3" in msg

    # totals wrong: the error names the first diverging bin too
    lc2 = paddle.to_tensor(np.array([2, 3], np.int64))
    with pytest.raises(ValueError) as ei:
        global_scatter(x, lc2, lc2)
    assert "sums to 5 rows but x has 6" in str(ei.value)

    # shape mismatch between the two count vectors
    with pytest.raises(ValueError) as ei:
        global_scatter(x, lc, paddle.to_tensor(np.array([6], np.int64)))
    assert "expert bins" in str(ei.value)

    # the happy path still round-trips
    y = global_scatter(x, lc, lc)
    z = global_gather(y, lc, lc)
    np.testing.assert_allclose(_np(z), _np(x))


# ---------------------------------------------------------------------------
# the static cost model's price of the stage the kernels fuse
# ---------------------------------------------------------------------------

def test_predicted_fused_dispatch_row_beats_baseline():
    """The bench acceptance bar: the fused dispatch+combine stage beats
    the gather chain in the static cost model, the PTCS004 diagnostic
    fires on the old path and is clean on the new — all carried in the
    anchor row itself."""
    from paddle_tpu.serving.predict import predicted_fused_dispatch_row
    row = predicted_fused_dispatch_row()
    assert row["predicted_speedup"] > 1.0, row
    assert row["hbm_mb_fused"] < row["hbm_mb_unfused"]
    assert row["ptcs004_fires_unfused"] is True
    assert row["ptcs004_clean_fused"] is True
