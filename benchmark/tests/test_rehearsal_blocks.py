"""CPU rehearsals of the block-diffusion cell, beside ``test_rehearsal.py``
(run by hand: ``python -m pytest benchmark/tests -q``). A PR that adds a
cell may not edit the files the benchmark has, so the ``tiny-blocks`` cell
stands in a rehearsal manifest of its own (``manifest_blocks.json``), made
of data files like the other: ``configs/sdar-tiny.json``,
``traffic/tiny-blocks.json``, ``limits/tiny-blocks.json``.
"""
import io
import json
import os
import time
from contextlib import redirect_stdout

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
MANIFEST = os.path.join(HERE, "manifest_blocks.json")


def drive(cell, trace=0, seed=(1 << 31) + 23, seconds=1.5, **steering):
    import run
    from harness.core import Steer
    steer = Steer(manifest=MANIFEST, root=HERE, allow_cpu=True, **steering)
    out = io.StringIO()
    with redirect_stdout(out):
        record = run.execute(
            ["--workload", cell, "--seed", str(seed), "--seconds",
             str(seconds), "--trace", str(trace)], steer=steer,
            t_start=time.perf_counter())
    return json.loads(out.getvalue().strip().splitlines()[-1]), record


def test_cell_end_to_end():
    line, record = drive("tiny-blocks")
    assert line["correct"] is True, line["compared"]
    assert line["failed"] == 0 and line["attempted"] > 0
    assert set(line["metrics"]) == {"itl_p90_ms", "setup_s"}
    assert set(line["compared"]) == {"logit_gap", "order_gap", "conf_gap",
                                     "never_finished",
                                     "compiles_in_window"}
    assert record.counters["checked_tokens"] > 0
    # a block comes out whole: most gaps between tokens are 0
    gaps = np.asarray(record.counters["itl_s"])
    assert (gaps == 0).mean() > 0.5
    # a tick holds a length for every position of every block
    assert all(len(lens) % 4 == 0 for _, lens, _ in record.counters["ticks"])


def test_cell_traced():
    line, record = drive("tiny-blocks", trace=1)
    assert line["correct"] is True, line["compared"]
    names = {m["name"] for m in json.load(open(MANIFEST))["per_layer"]}
    assert set(line["metrics"]) <= names
    for name in ("denoise_pass_ms.serve", "passes_per_token.serve",
                 "expert_load_peak.serve", "decode_host_ms.serve",
                 "decode_wait_ms.serve", "sched_self_ms.serve",
                 "prefill_chunk_ms.serve", "prefill_begin_ms.serve",
                 "decode_tick_ms.serve", "pool_peak_share.serve"):
        assert name in line["metrics"], name
    assert 1.2 < line["metrics"]["passes_per_token.serve"]["value"] < 2.5
    assert line["metrics"]["expert_load_peak.serve"]["value"] >= 1.0
    # a share of a peak or of a roofline has nothing to read off the chip
    assert not [m for m in line["metrics"] if "mfu" in m or "roofline" in m]
    from harness import core
    for kernel, calls in (("block_attention.py", 1), ("expert_ffn.py", 2)):
        needs = core.load_module(os.path.join(
            BENCH, "kernels", kernel)).needs(record)
        (each,) = needs.values()
        assert each and len(each) % (
            calls * record.config["num_hidden_layers"]) == 0
        assert all(f >= 0 and b > 0 for f, b in each)
    # the expert rows come from the shapes, and the engine's count of
    # assignments has to be theirs: one that counts otherwise leaves the
    # roofline nothing sound to read
    top_k = record.config["num_experts_per_tok"]
    passes = record.counters["passes"]
    assert all(a == p[1] * 4 * top_k for p in passes for a in p[-1])
    assert all(a == c[1] * top_k for c in record.counters["chunk_loads"]
               for a in c[-1])
    record.counters["passes"] = [
        p[:-1] + ([a + top_k for a in p[-1]],) for p in passes]
    assert core.load_module(os.path.join(
        BENCH, "kernels", "expert_ffn.py")).needs(record) == {}


def test_control_is_not_correct():
    """The float8 reference in the program's place, through the harness's
    own comparison."""
    line, _ = drive("tiny-blocks", lower_precision=True)
    assert line["correct"] is False, line["compared"]
    gaps = line["compared"]
    assert max(gaps[k]["value"] / gaps[k]["limit"]
               for k in ("logit_gap", "order_gap")) > 1.5


def _stale_commit(engine):
    """A commit pass that stores the rows of the block's last denoising
    pass: it is fed the tokens as they stood before that pass."""
    advance = engine._advance
    fed = {}

    def remember(block, commit, after, picked, conf):
        if not commit:
            fed[id(block)] = block.tokens.copy()
        return advance(block, commit, after, picked, conf)

    engine._advance = remember
    engine._pass_tokens = lambda block: block.tokens if block.masked.any() \
        else fed.get(id(block), block.tokens)
    return engine


def _patched_model(name, replacement):
    """A fault planted in the program's layer functions: the engine's
    programs are traced anew with it in place."""
    def plant(engine):
        from paddle_tpu.models import sdar
        original = getattr(sdar, name)
        setattr(sdar, name, replacement(original))
        try:
            engine._build_programs()
            engine.compile_buckets()
        finally:
            setattr(sdar, name, original)
        return engine
    return plant


def _drop_last_expert(route):
    import jax.numpy as jnp

    def faulty(a, w_router, cfg):
        w, idx = route(a, w_router, cfg)
        w = w.at[:, -1].set(0.0)
        return w / jnp.sum(w, -1, keepdims=True), idx
    return faulty


def _no_renorm(route):
    import dataclasses
    return lambda a, w_router, cfg: route(
        a, w_router, dataclasses.replace(cfg, norm_topk_prob=False))


def _causal_in_block(engine):
    """A causal mask inside the block: every position of a block sees the
    keys up to itself, not the whole block."""
    from paddle_tpu.serving import sdar_engine

    def causal(q, k_pages, v_pages, page_table, seq_lens, layer,
               use_kernel=True):
        import jax.numpy as jnp
        from paddle_tpu.kernels.paged_attention import \
            paged_attention_reference
        B, bl, nh, d = q.shape
        lens = (seq_lens[:, None] - bl + 1
                + jnp.arange(bl, dtype=seq_lens.dtype)[None])
        lens = jnp.where(seq_lens[:, None] > 0, lens, 0).reshape(-1)
        out = paged_attention_reference(
            q.reshape(B * bl, nh, d), k_pages, v_pages,
            jnp.repeat(page_table, bl, axis=0), lens, layer=layer)
        return out.reshape(B, bl, nh, d)

    original = sdar_engine.block_attention
    sdar_engine.block_attention = causal
    try:
        engine._build_programs()
        engine.compile_buckets()
    finally:
        sdar_engine.block_attention = original
    return engine


def _least_confident(choose):
    return lambda conf, masked, threshold, per_pass: choose(
        -conf, masked, threshold, per_pass)


FAULTS = {"stale_commit": _stale_commit,
          "wrong_order": _patched_model("choose_unmask", _least_confident),
          "causal_in_block": _causal_in_block,
          "drop_last_expert": _patched_model("route", _drop_last_expert),
          "no_renorm": _patched_model("route", _no_renorm)}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_planted_fault_is_not_correct(fault):
    line, _ = drive("tiny-blocks", break_program=FAULTS[fault])
    assert line["correct"] is False, line["compared"]


@pytest.mark.parametrize("fault", ["stale_commit", "causal_in_block",
                                   "drop_last_expert", "no_renorm",
                                   "wrong_order"])
def test_faulty_reference_in_the_programs_place(fault):
    """The same four faults planted in the reference and held against the
    sound reference (what ``blocks_controls.py`` does on the chip, where
    a second engine build is chip time)."""
    from models import sdar
    cfg = sdar.load_config(os.path.join(HERE, "configs", "sdar-tiny.json"))
    limits = json.load(open(os.path.join(HERE, "limits",
                                         "tiny-blocks.json")))["limits"]
    rng = np.random.default_rng(3)
    served = []
    for p, n in ((10, 9), (17, 7), (32, 24)):
        prompt = rng.integers(0, 500, p)
        blocks = -(-(p % 4 + n) // 4)
        record = []
        for _ in range(blocks):
            order = rng.permutation(4)
            record += [(int(rng.integers(0, 500)), int(s), 1e-3)
                       for s in order]
        served.append((prompt, record[p % 4:] if p % 4 else record))
    sound = sdar.served_gaps(cfg, 5, served)
    broken = sdar.served_gaps(cfg, 5, served, fault=fault)
    assert sound["checked_tokens"] > 0 and broken["checked_tokens"] > 0
    assert max(broken[k] / limits[k] for k in ("logit_gap", "order_gap")) > 1
    # the stand-in's own confidences against the reference's: numbers,
    # whatever the choices (the wrong order changes no number)
    assert (broken["conf_gap"] > limits["conf_gap"]) == (
        fault != "wrong_order"), broken


def test_weights_by_layer_are_the_stacked_weights():
    from models import sdar
    cfg = sdar.load_config(os.path.join(HERE, "configs", "sdar-tiny.json"))
    whole = sdar.init_weights(cfg, (1 << 31) + 7)
    E = cfg["num_experts"]
    for layer in (0, 2):
        block, experts = sdar.layer_weights(cfg, (1 << 31) + 7, layer)
        for k, v in block.items():
            assert (np.asarray(whole["blocks"][k][layer])
                    == np.asarray(v)).all(), k
        for k, v in experts.items():
            assert (np.asarray(whole["experts"][k][layer * E:(layer + 1) * E])
                    == np.asarray(v)).all(), k
    assert abs(float(np.asarray(whole["blocks"]["ln1"]).mean()) - 1) < 0.01
    assert sdar.active_params(dict(
        cfg, hidden_size=2048, head_dim=128, num_attention_heads=32,
        num_key_value_heads=4, num_experts=128, num_experts_per_tok=8,
        moe_intermediate_size=768, vocab_size=151936,
        num_hidden_layers=7)) == 709361664


def test_manifest_additions_are_within_the_contract():
    m = json.load(open(os.path.join(os.path.dirname(BENCH),
                                    "BENCHMARK.json")))
    cell = {c["name"]: c for c in m["workloads"]}[
        "serve-sdar-reasoning-steady"]
    assert cell["chips"] == 1 and len(cell["why"]) <= 200
    cfg = json.load(open(os.path.join(BENCH, "configs",
                                      "sdar-30b-a3b-chat.json")))
    assert cfg["reduced"] == ["num_hidden_layers"] == \
        {c["name"]: c for c in m["configs"]}["sdar-30b-a3b-chat"]["reduced"]
    mix = json.load(open(os.path.join(BENCH, "traffic",
                                      "reasoning-steady.json")))
    assert mix["pool_tokens"] == 64 * mix["max_seq_len"] == 131072
    assert mix["prompt_tokens"]["max"] + mix["output_tokens"]["max"] \
        <= mix["max_seq_len"]
    for x in m["per_layer"]:
        if "serve-sdar-reasoning-steady" in x.get("workloads", []):
            assert os.path.exists(os.path.join(BENCH, "metrics",
                                               x["name"] + ".py"))
