"""Auto-fusion rewrite pass (``paddle_tpu.analysis.rewrite``): per-rule
interpret-parity fixtures, near-miss negatives that must NOT rewrite,
the PTCS004 -> PTCS005 analyzer flip on the rewritten program, the env
opt-outs, the serving engines compiling rewritten programs with greedy
parity, and the bench anchor row.
"""
import os
import sys

import numpy as np
import pytest
import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.analysis import rewrite

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _fresh_records():
    rewrite.reset_records()
    yield
    rewrite.reset_records()


# ---------------------------------------------------------------------------
# rule: int8_dequant_matmul
# ---------------------------------------------------------------------------

def _int8_operands(M=16, K=32, N=24, seed=0):
    rng = np.random.RandomState(seed)
    x = jnp.asarray(rng.standard_normal((M, K)).astype(np.float32))
    wq = jnp.asarray(rng.randint(-127, 127, (K, N)).astype(np.int8))
    ws = jnp.asarray(rng.rand(N).astype(np.float32) + 0.1)
    return x, wq, ws


def _dequant_matmul(x, wq, ws):
    return (x @ wq.astype(jnp.float32)) * ws


def test_int8_rule_fires_with_parity():
    x, wq, ws = _int8_operands()
    fused = rewrite.autofuse(_dequant_matmul, label="t.int8")
    got = fused(x, wq, ws)
    fired = rewrite.fired_records()
    assert [r["rule"] for r in fired] == ["int8_dequant_matmul"]
    assert fired[0]["label"] == "t.int8"
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(_dequant_matmul(x, wq, ws)),
                               rtol=2e-5, atol=2e-5)


def test_int8_rule_fires_under_jit():
    x, wq, ws = _int8_operands(seed=3)
    fused = jax.jit(rewrite.autofuse(_dequant_matmul, label="t.int8jit"))
    got = fused(x, wq, ws)
    assert any(r["rule"] == "int8_dequant_matmul"
               for r in rewrite.fired_records())
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(_dequant_matmul(x, wq, ws)),
                               rtol=2e-5, atol=2e-5)


def test_int8_near_miss_not_rewritten():
    # per-ROW scale: same convert->dot->mul spine, but the broadcast is
    # not a per-output-channel dequant scale — the matcher must refuse
    x, wq, _ = _int8_operands()
    ws_row = jnp.asarray(
        np.random.RandomState(1).rand(16, 1).astype(np.float32) + 0.1)

    def near(x, wq, ws_row):
        return (x @ wq.astype(jnp.float32)) * ws_row

    got = rewrite.autofuse(near, label="t.int8_near")(x, wq, ws_row)
    assert rewrite.fired_records() == []
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(near(x, wq, ws_row)),
                               rtol=1e-6, atol=1e-6)


# ---------------------------------------------------------------------------
# rule: moe_gate_dispatch
# ---------------------------------------------------------------------------

_MOE = dict(S=64, M=32, E=8, K=2)


def _moe_operands(seed=0):
    rng = np.random.RandomState(seed)
    S, M, E = _MOE["S"], _MOE["M"], _MOE["E"]
    xm = jnp.asarray(rng.standard_normal((S, M)).astype(np.float32))
    gw = jnp.asarray(rng.standard_normal((M, E)).astype(np.float32) * 0.1)
    gb = jnp.asarray(rng.standard_normal((E,)).astype(np.float32) * 0.01)
    return xm, gw, gb


def _moe_fn(xm, gw, gb):
    from paddle_tpu.kernels.moe_dispatch import reference_moe_dispatch
    C = int(1.2 * _MOE["K"] * _MOE["S"] / _MOE["E"])
    return reference_moe_dispatch(xm, gw, gb, num_expert=_MOE["E"],
                                  capacity=C, top_k=_MOE["K"],
                                  gate_kind="gshard")


def test_moe_rule_fires_with_parity():
    xm, gw, gb = _moe_operands()
    got = rewrite.autofuse(_moe_fn, label="t.moe")(xm, gw, gb)
    fired = rewrite.fired_records()
    assert [r["rule"] for r in fired] == ["moe_gate_dispatch"]
    assert fired[0]["meta"].get("gate_kind") == "gshard"
    for g, w in zip(got, _moe_fn(xm, gw, gb)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=2e-5, atol=2e-5)


def test_moe_rule_fires_under_jit():
    xm, gw, gb = _moe_operands(seed=5)
    got = jax.jit(rewrite.autofuse(_moe_fn, label="t.moejit"))(xm, gw, gb)
    assert any(r["rule"] == "moe_gate_dispatch"
               for r in rewrite.fired_records())
    for g, w in zip(got, _moe_fn(xm, gw, gb)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=2e-5, atol=2e-5)


def test_moe_near_miss_not_rewritten():
    # a hand-rolled router with a temperature no gate kind uses: the
    # top_k anchor and glue shape are there, but the gate-kind trial
    # can match no reference gate — must fall through unrewritten
    E = _MOE["E"]

    def near(xm, gw, gb):
        probs = jax.nn.softmax(2.0 * (xm @ gw + gb), axis=-1)
        vals, idx = jax.lax.top_k(probs, _MOE["K"])
        onehot = jax.nn.one_hot(idx, E) * vals[..., None]
        return onehot.sum(1)

    xm, gw, gb = _moe_operands(seed=7)
    got = rewrite.autofuse(near, label="t.moe_near")(xm, gw, gb)
    assert not any(r["rule"] == "moe_gate_dispatch"
                   for r in rewrite.fired_records())
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(near(xm, gw, gb)),
                               rtol=1e-6, atol=1e-6)


# ---------------------------------------------------------------------------
# env gates
# ---------------------------------------------------------------------------

def test_no_autofuse_env_disables(monkeypatch):
    monkeypatch.setenv("PADDLE_NO_AUTOFUSE", "1")
    assert not rewrite.autofuse_enabled()
    x, wq, ws = _int8_operands(seed=9)
    got = rewrite.autofuse(_dequant_matmul, label="t.off")(x, wq, ws)
    assert rewrite.fired_records() == []
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(_dequant_matmul(x, wq, ws)),
                               rtol=1e-6, atol=1e-6)


def test_suppress_site_env(monkeypatch):
    x, wq, ws = _int8_operands(seed=11)
    rewrite.autofuse(_dequant_matmul, label="t.sup_probe")(x, wq, ws)
    fired = rewrite.fired_records()
    assert fired, "probe run must fire to learn the site id"
    # site ids carry the trace call-site line, so suppress by a stable
    # substring token (the matched primitive) — _is_suppressed matches
    # any token contained in the site id
    token = fired[0]["site"].rsplit(":", 1)[-1]
    assert token == "dot_general"
    monkeypatch.setenv("PADDLE_AUTOFUSE_SUPPRESS", token)
    assert token in rewrite.suppressed_sites()
    rewrite.reset_records()
    got = rewrite.autofuse(_dequant_matmul, label="t.sup")(x, wq, ws)
    assert rewrite.fired_records() == []
    assert any(r["status"] == "suppressed"
               for r in rewrite.match_records())
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(_dequant_matmul(x, wq, ws)),
                               rtol=1e-6, atol=1e-6)


# ---------------------------------------------------------------------------
# analyzer: PTCS004 -> PTCS005 on the rewritten program
# ---------------------------------------------------------------------------

def test_ptcs004_flips_to_ptcs005():
    sys.path.insert(0, os.path.join(REPO, "tools"))
    import check_program
    reports = check_program.lint_fusion()
    probe = reports[0]
    gate = reports[1]
    n004 = sum(1 for d in probe.diagnostics if d.code == "PTCS004")
    p005 = [d for d in probe.diagnostics if d.code == "PTCS005"]
    assert n004 == 0, [d.message for d in probe.diagnostics
                       if d.code == "PTCS004"]
    assert p005, "rewritten probe must carry the PTCS005 annotation"
    info = p005[0].extra["autofusion"]
    assert info["rule"] == "moe_gate_dispatch"
    assert not [d for d in gate.diagnostics if d.severity == "error"]


def test_records_api_and_export(tmp_path):
    x, wq, ws = _int8_operands(seed=13)
    rewrite.autofuse(_dequant_matmul, label="t.export")(x, wq, ws)
    assert rewrite.fired_delta("int8_dequant_matmul") is not None
    path = rewrite.export_records(str(tmp_path / "autofusion.json"))
    from paddle_tpu.observability import doctor
    af = doctor.load_autofusion(path)
    assert af and any(r["status"] == "fired" for r in af["records"])
    findings = doctor.collect_findings({}, autofusion=af)
    kinds = {f["kind"] for f in findings}
    assert "autofusion_fired" in kinds and "autofusion_site" in kinds


# ---------------------------------------------------------------------------
# engines compile rewritten programs; greedy parity vs autofuse=False
# ---------------------------------------------------------------------------

def test_serving_engine_autofuse_parity():
    from paddle_tpu.models.gpt import (GPTForPretraining, GPTModel,
                                       gpt_tiny_config)
    from paddle_tpu.serving import ServingEngine

    paddle.seed(0)
    cfg = gpt_tiny_config()
    model = GPTForPretraining(GPTModel(cfg))
    mk = lambda **kw: ServingEngine(  # noqa: E731
        model, cfg, page_size=8, decode_buckets=(1,), aot=False,
        prefill_chunk=16, quantize="int8", **kw)
    eng, base = mk(autofuse=True), mk(autofuse=False)
    # the engine's own chunk program calls the ragged kernel on the
    # whole pool; the dense gather the rule matches is the XLA path's
    dense = mk(autofuse=True, use_kernel=False)
    assert eng.status()["autofuse"] and not base.status()["autofuse"]
    prompt = np.random.default_rng(0).integers(
        0, cfg.vocab_size, (23,)).astype(np.int32)
    assert eng.prefill("a", prompt) == base.prefill("a", prompt) \
        == dense.prefill("a", prompt)
    toks = ([], [])
    for _ in range(4):
        eng.pool.extend("a")
        base.pool.extend("a")
        toks[0].append(eng.decode(["a"])[0])
        toks[1].append(base.decode(["a"])[0])
    assert toks[0] == toks[1]
    rules = {r["rule"] for r in rewrite.fired_records()}
    assert "int8_dequant_matmul" in rules
    assert "ragged_prefill" in rules


# ---------------------------------------------------------------------------
# bench anchor row
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_bench_autofusion_predicted_rows(capsys, tmp_path):
    sys.path.insert(0, REPO)
    import bench
    bench.emit_autofusion_predicted_rows(export_dir=str(tmp_path))
    import json
    rows = [json.loads(ln) for ln in
            capsys.readouterr().out.splitlines() if ln.strip()]
    metrics = {r["metric"] for r in rows}
    assert "autofusion_predicted" in metrics, metrics
    agg = next(r for r in rows if r["metric"] == "autofusion_predicted")
    assert agg["value"] > 0
    assert agg["extras"]["calibration_id"]
    assert set(agg["extras"]["rules_fired"]) == set(rewrite.RULE_NAMES)
    for rule in rewrite.RULE_NAMES:
        assert f"autofusion_{rule}_predicted" in metrics, metrics
    assert (tmp_path / "autofusion.json").exists()
